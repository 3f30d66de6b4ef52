"""RWKV-6 WKV chunked Pallas TPU kernel.

Recurrence per head (key-dim i, value-dim j):
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          (w_t data-dependent, per i)

Chunked form computed entirely in VMEM per (batch, head, chunk):
  c_t    = cumsum_t log w            (C, hd)  — within-chunk log decay
  inter  = (r ⊙ exp(c - logw)) @ S   — contribution of the carried state
  intra  = A @ v with A[t,s] = Σ_i r_t[i] k_s[i] e^{c_{t-1,i} - c_{s,i}}
           (s < t; diagonal uses the u bonus) — the (C,C,hd) pairwise tensor
           lives only in VMEM, which is why the chunked form is a *kernel*:
           materializing it in HBM for the whole sequence is impossible.
  S'     = diag(e^{c_C}) S + (k ⊙ e^{c_C - c})^T @ v

The grid's last dim walks chunks sequentially; S is carried in VMEM scratch.
Chunk=32..128 keeps the pairwise tile ≤ (128,128,64) f32 = 4 MiB in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_ref, *,
                chunk: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)        # log decay, negative
    u = u_ref[0].astype(jnp.float32)             # (1, hd)
    S = s_ref[...]                                # (hd_k, hd_v)
    hd = S.shape[0]

    def mm(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # prefix sum over steps as a lower-triangular matmul (Mosaic has no
    # cumsum)
    c = mm(jnp.where(col <= row, 1.0, 0.0), lw)  # (C, hd)
    c_prev = c - lw                              # c_{t-1}

    # inter-chunk: y_inter[t] = (r_t * exp(c_{t-1})) @ S
    y_inter = mm(r * jnp.exp(c_prev), S)

    # intra-chunk pairwise: A[t,s] = sum_i r_t k_s exp(c_{t-1} - c_s), s<t.
    # Log decays are negative, so diff <= 0 wherever s < t; the clamp only
    # keeps the masked-out s >= t entries finite
    diff = c_prev[:, None, :] - c[None, :, :]    # (C, C, hd)
    pair = jnp.exp(jnp.minimum(diff, 0.0))
    A = jnp.sum(r[:, None, :] * k[None, :, :] * pair, axis=2)
    A = jnp.where(col < row, A, 0.0)
    # the diagonal carries the u bonus instead of the decay
    A_diag = jnp.sum(r * k * u, axis=1, keepdims=True)   # (C, 1)
    A = A + jnp.where(col == row, A_diag, 0.0)
    y_intra = mm(A, v)

    y_ref[0, 0] = (y_inter + y_intra).astype(y_ref.dtype)

    # state update: S' = diag(e^{c_C}) S + (k * e^{c_C - c})^T @ v
    c_total = c[chunk - 1:chunk, :]               # (1, hd)
    k_decayed = k * jnp.exp(c_total - c)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1))
    s_ref[...] = (mm(jnp.where(eye, jnp.exp(c_total), 0.0), S)
                  + jax.lax.dot_general(k_decayed, v,
                                        (((0,), (0,)), ((), ())),
                                        precision=jax.lax.Precision.HIGHEST,
                                        preferred_element_type=jnp.float32))


def wkv6(r, k, v, logw, u, *, chunk: int = DEFAULT_CHUNK,
         interpret: bool = False):
    """r,k,v,logw: (B, H, T, hd); u: (H, hd) -> y (B, H, T, hd)."""
    B, H, T, hd = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nt = T // chunk
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    tile = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, t: (b, h, t, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[tile, tile, tile,
                  tile,
                  pl.BlockSpec((1, 1, hd), lambda b, h, t: (h, 0, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u[:, None, :])
