"""Mamba2 SSD chunked Pallas TPU kernel.

Per head (headdim P, state N), scalar decay per step a_t = exp(A*dt_t):
    h_t = a_t h_{t-1} + dt_t x_t B_t^T         h: (P, N)
    y_t = h_t C_t + D x_t                      (D handled by the wrapper)

Chunked dual form per (batch, head, chunk) in VMEM:
  cd_t  = cumsum dt                      (C,)
  L_t   = exp(A cd_t)                    within-chunk decay from chunk start
  inter: y[t] += (L_t h) C_t       ->    (C,N) @ (N,P) with row scaling
  intra: M[t,s] = (C_t . B_s) exp(A (cd_t - cd_s)) dt_s   (s <= t)
         y += M @ x
  carry: h' = exp(A cd_C) h + Σ_s exp(A(cd_C - cd_s)) dt_s x_s B_s^T

Grid last dim walks chunks sequentially; h is VMEM scratch.  The (C,C)
pairwise matrix is per-head scalar-decay — tiny compared to wkv6's (C,C,hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_ref, *,
                chunk: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (1, C) row
    Bm = b_ref[0].astype(jnp.float32)            # (C, N)
    Cm = c_ref[0].astype(jnp.float32)            # (C, N)
    A = a_ref[pl.program_id(1)]                  # f32 scalar (per head), SMEM
    h = h_ref[...]                                # (P, N)

    # row t of the (C, C) masks is step t; column s is step s.  The prefix
    # sums are masked lane reductions (Mosaic has no cumsum)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dts = jnp.broadcast_to(dt, (chunk, chunk))    # [t, s] = dt_s
    cd = jnp.sum(jnp.where(col <= row, dts, 0.0), axis=1,
                 keepdims=True)                   # (C, 1) cumsum dt
    dt_col = jnp.sum(jnp.where(col == row, dts, 0.0), axis=1,
                     keepdims=True)               # (C, 1)
    total = jnp.sum(dt)                           # cd at the chunk's end
    decay = jnp.exp(A * cd)                       # L_t

    # inter-chunk: y[t] = C_t . (L_t * h)  -> (C,P)
    y_inter = decay * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # intra-chunk: dt_s folds into x_s
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (C,C)
    cds = jnp.broadcast_to(cd, (chunk, chunk))    # [t, s] = cd_t
    pair = jnp.exp(A * (cds - cds.T))
    M = jnp.where(col <= row, scores * pair, 0.0)
    xdt = x * dt_col
    y_intra = jax.lax.dot_general(M, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_inter + y_intra).astype(y_ref.dtype)

    # carry
    w = jnp.exp(A * (total - cd))                 # (C, 1)
    h_new = (jnp.exp(A * total) * h
             + jax.lax.dot_general(xdt * w, Bm,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    h_ref[...] = h_new


def ssd_scan(x, dt, B_in, C_in, A, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False):
    """x: (B, H, T, P); dt: (B, H, T); B_in, C_in: (B, T, N); A: (H,)
    -> y (B, H, T, P)."""
    Bsz, H, T, P = x.shape
    N = B_in.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    nt = T // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(Bsz, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, t: (b, h, 0, t)),
            pl.BlockSpec((1, chunk, N), lambda b, h, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, t: (b, t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, t: (b, h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt[:, :, None, :], B_in, C_in, A.astype(jnp.float32))
