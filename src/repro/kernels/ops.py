"""Host-side helpers for the Pallas kernels.

The kernels take ``interpret=`` from their caller: True runs the kernel body
as traced jax ops (the CPU tests check it against ref.py), False compiles it
with Mosaic for the TPU.
"""
from __future__ import annotations

import numpy as np


def group_updates_by_page(page_idx: np.ndarray, n_pages: int,
                          vals: np.ndarray, slots: np.ndarray,
                          apply_mask: np.ndarray, max_upd: int | None = None):
    """Host-side packer: (flat update stream) -> per-page dense batches for
    the delta_apply kernel.  Preserves log order within each page (so
    last-writer-wins assign semantics match LSN order)."""
    order = np.argsort(page_idx, kind="stable")
    width = vals.shape[-1]
    counts = np.bincount(page_idx, minlength=n_pages)
    m = int(counts.max()) if counts.size else 0
    max_upd = max_upd or max(m, 1)
    v = np.zeros((n_pages, max_upd, width), vals.dtype)
    s = np.zeros((n_pages, max_upd), np.int32)
    msk = np.zeros((n_pages, max_upd), bool)
    fill = np.zeros(n_pages, np.int32)
    for u in order:
        p = page_idx[u]
        j = fill[p]
        if j >= max_upd:
            raise ValueError(f"page {p} exceeds max_upd={max_upd}")
        v[p, j] = vals[u]
        s[p, j] = slots[u]
        msk[p, j] = apply_mask[u]
        fill[p] = j + 1
    return v, s, msk
