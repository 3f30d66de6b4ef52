"""delta_apply: the paper's REDOOPERATION hot loop as a Pallas TPU kernel.

Recovery redo applies a batch of logged record deltas to state pages after
the DPT/pLSN tests decided which ops actually re-execute (Algorithm 5 line
14).  For the training state store, records are fixed-width fp32 chunks and
pages are arrays of slots — so redo is a masked batched scatter:

    pages[page_idx[u], slot[u], :] = value[u]        where mask[u]

The host packer (ops.group_updates_by_page) groups updates by destination
page (sort + pad to a per-page budget) so the kernel's grid walks pages: each
page tile is resident in VMEM exactly once while all its updates stream
through — the TPU-native analogue of "fetch the page once, apply every log record for it"
(the same IO-locality insight the paper's prefetch/DPT machinery serves).

mode='assign' replays after-images (idempotent, any order within a page once
LSN-sorted); mode='add' merges additive deltas (gradient-style logs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _delta_kernel(vals_ref, slot_ref, mask_ref, page_in_ref, page_out_ref, *,
                  max_upd: int, additive: bool):
    # slot_ref / mask_ref: (1, 1, max_upd) int32 in SMEM — scalar reads
    page_out_ref[...] = page_in_ref[...]          # (1, slots, width)

    def body(u, carry):
        slot = slot_ref[0, 0, u]

        @pl.when(mask_ref[0, 0, u] != 0)
        def _apply():
            new = vals_ref[0, pl.ds(u, 1), :]
            if additive:
                new = new + page_out_ref[0, pl.ds(slot, 1), :]
            page_out_ref[0, pl.ds(slot, 1), :] = new

        return carry

    jax.lax.fori_loop(0, max_upd, body, 0)


def delta_apply(pages, vals, slot_idx, mask, *, additive: bool = False,
                interpret: bool = False):
    """pages: (n_pages, slots, width) f32 — per-page update batches:
    vals: (n_pages, max_upd, width); slot_idx: (n_pages, max_upd) int32;
    mask: (n_pages, max_upd) bool.  Returns updated pages."""
    n_pages, slots, width = pages.shape
    max_upd = vals.shape[1]
    kernel = functools.partial(_delta_kernel, max_upd=max_upd,
                               additive=additive)
    return pl.pallas_call(
        kernel,
        grid=(n_pages,),
        in_specs=[
            pl.BlockSpec((1, max_upd, width), lambda p: (p, 0, 0)),
            pl.BlockSpec((1, 1, max_upd), lambda p: (p, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, max_upd), lambda p: (p, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, slots, width), lambda p: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, slots, width), lambda p: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        interpret=interpret,
    )(vals, slot_idx[:, None, :], mask.astype(jnp.int32)[:, None, :],
      pages)
