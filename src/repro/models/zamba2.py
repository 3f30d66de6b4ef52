"""Zamba2: Mamba2 (SSD) backbone with a single *shared* attention block
applied every ``attn_every`` layers (arXiv:2411.15242).

Mamba2 mixer per layer (multi-head SSD, n_groups=1):
    h_t = exp(A dt_t) h_{t-1} + dt_t * x_t (x) B_t        h: (H, P, N)
    y_t = h_t . C_t + D x_t
with a causal depthwise conv (width 4) on (x, B, C) and a gated RMS-norm
before out-projection.  The model forward is an exact ``lax.scan`` over time.

The shared attention block's weights are reused at every invocation; each
invocation keeps its *own* KV cache (stacked on a leading invocation axis) —
weight sharing is a parameter-count device, not a cache-sharing one.
Mamba state decode is O(1), and the shared-attn KV cache's sequence axis is
shardable over the data mesh axis (SP).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from .layers import (attention, attention_decode, dtype_of, init_attention,
                     init_mlp, init_norm, mlp, norm, shard_hint)

Array = jax.Array


def dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    H = d_inner // cfg.ssm_headdim
    return d_inner, H, cfg.ssm_headdim, cfg.ssm_state


# ------------------------------------------------------------------ init
def init_zamba2(cfg: ModelConfig, rng) -> dict:
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    d_inner, H, P, N = dims(cfg)
    conv_dim = d_inner + 2 * N
    dt = dtype_of(cfg)
    ks = jax.random.split(rng, 10)
    s = 1.0 / math.sqrt(D)

    def mat(k, *shape, scale=s):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    params = {
        "embed": mat(ks[0], V, D, scale=0.02),
        "lm_head": mat(ks[1], D, V),
        "final_norm": init_norm(cfg),
        "blocks": {
            "ln": init_norm(cfg, (L,)),
            # per-component projections (instead of one fused in_proj): x/z
            # are head-sharded (TP over the model axis); B/C/dt are small and
            # replicated — the split keeps TP boundaries on head boundaries.
            "w_z": mat(ks[2], L, D, d_inner),
            "w_x": mat(jax.random.fold_in(ks[2], 1), L, D, d_inner),
            "w_bc": mat(jax.random.fold_in(ks[2], 2), L, D, 2 * N),
            "w_dt": mat(jax.random.fold_in(ks[2], 3), L, D, H),
            "conv_x_w": (jax.random.normal(ks[3], (L, cfg.ssm_conv, d_inner))
                         * 0.1).astype(dt),
            "conv_x_b": jnp.zeros((L, d_inner), dt),
            "conv_bc_w": (jax.random.normal(jax.random.fold_in(ks[3], 1),
                                            (L, cfg.ssm_conv, 2 * N))
                          * 0.1).astype(dt),
            "conv_bc_b": jnp.zeros((L, 2 * N), dt),
            "A_log": jnp.zeros((L, H), jnp.float32),
            "D": jnp.ones((L, H), jnp.float32),
            "dt_bias": jnp.zeros((L, H), jnp.float32),
            "gate_norm": jnp.ones((L, d_inner), dt),
            "out_proj": mat(ks[4], L, d_inner, D,
                            scale=1.0 / math.sqrt(d_inner)),
        },
        # one shared attention+MLP block
        "shared": {
            "ln1": init_norm(cfg),
            "attn": init_attention(cfg, ks[5]),
            "ln2": init_norm(cfg),
            "mlp": init_mlp(cfg, ks[6]),
        },
    }
    return params


# ----------------------------------------------------------------- mamba2
def _causal_conv(x: Array, w: Array, b: Array, conv_state: Array):
    """x: (B,T,C); w: (K,C) depthwise; conv_state: (B,K-1,C) from the left.
    Returns (out (B,T,C), new_conv_state)."""
    K = w.shape[0]
    xx = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    out = sum(xx[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    new_state = xx[:, -(K - 1):, :] if K > 1 else conv_state
    return jax.nn.silu(out + b), new_state


def _ssd_scan(xh, dt_h, B_in, C_in, A, h0):
    """Exact SSD recurrence.
    xh: (B,T,H,P); dt_h: (B,T,H); B_in,C_in: (B,T,N); A: (H,) negative.
    h0: (B,H,P,N).  Returns (y (B,T,H,P), hT)."""

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(A * dt_t)[..., None, None]            # (B,H,1,1)
        upd = (dt_t[..., None, None] * x_t[..., :, None]
               * b_t[:, None, None, :])                       # (B,H,P,N)
        h = decay * h + upd
        y = jnp.einsum("bhpn,bn->bhp", h, c_t)
        return h, y

    xs = (jnp.moveaxis(xh.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt_h, 1, 0),
          jnp.moveaxis(B_in.astype(jnp.float32), 1, 0),
          jnp.moveaxis(C_in.astype(jnp.float32), 1, 0))
    hT, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), hT


def mamba_mixer(x, bp, cfg: ModelConfig, state):
    """state: ((conv_x (B,K-1,d_inner), conv_bc (B,K-1,2N)), ssm (B,H,P,N))."""
    B, T, D = x.shape
    d_inner, H, P, N = dims(cfg)
    (conv_x_state, conv_bc_state), ssm_state = state
    z = x @ bp["w_z"]
    xc = x @ bp["w_x"]
    bc = x @ bp["w_bc"]
    dt_raw = x @ bp["w_dt"]
    xc, new_conv_x = _causal_conv(xc, bp["conv_x_w"], bp["conv_x_b"],
                                  conv_x_state)
    bc, new_conv_bc = _causal_conv(bc, bp["conv_bc_w"], bp["conv_bc_b"],
                                   conv_bc_state)
    B_in, C_in = jnp.split(bc, [N], axis=-1)
    new_conv = (new_conv_x, new_conv_bc)
    dt_h = jax.nn.softplus(dt_raw.astype(jnp.float32) + bp["dt_bias"])
    A = -jnp.exp(bp["A_log"])
    xh = xc.reshape(B, T, H, P)
    y, new_ssm = _ssd_scan(xh, dt_h, B_in, C_in, A, ssm_state)
    y = y + bp["D"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(B, T, d_inner)
    # gated RMS norm, then out-projection
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(var + 1e-6) * bp["gate_norm"]).astype(x.dtype)
    return g @ bp["out_proj"], (new_conv, new_ssm)


# ------------------------------------------------------------------ model
def init_state(cfg: ModelConfig, batch: int, attn_len: int) -> dict:
    d_inner, H, P, N = dims(cfg)
    L = cfg.n_layers
    K = cfg.ssm_conv
    n_inv = L // cfg.attn_every if cfg.attn_every else 0
    dt = dtype_of(cfg)
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "conv_x": jnp.zeros((L, batch, K - 1, d_inner), dt),
        "conv_bc": jnp.zeros((L, batch, K - 1, 2 * N), dt),
        "ssm": jnp.zeros((L, batch, H, P, N), jnp.float32),
        "attn_k": jnp.zeros((n_inv, batch, attn_len, KV, hd), dt),
        "attn_v": jnp.zeros((n_inv, batch, attn_len, KV, hd), dt),
        "len": jnp.zeros((), jnp.int32),
    }


def _shared_attn_full(x, sp, cfg, positions):
    h = norm(x, sp["ln1"], cfg.norm)
    x = x + attention(h, sp["attn"], cfg, positions)
    h = norm(x, sp["ln2"], cfg.norm)
    return x + mlp(h, sp["mlp"], cfg)


def forward(params, tokens, cfg: ModelConfig, remat=False):
    """Training/prefill forward (no cache plumbing): logits."""
    B, T = tokens.shape
    x = shard_hint(jnp.take(params["embed"], tokens, axis=0),
                   "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    sp = params["shared"]
    L = cfg.n_layers
    d_inner, H, P, N = dims(cfg)
    K = cfg.ssm_conv
    conv_x0 = jnp.zeros((L, B, K - 1, d_inner), x.dtype)
    conv_bc0 = jnp.zeros((L, B, K - 1, 2 * N), x.dtype)
    ssm0 = jnp.zeros((L, B, H, P, N), jnp.float32)

    def body(x, xs):
        bp, cx_s, cbc_s, ssm_s, idx = xs
        h = norm(x, bp["ln"], cfg.norm)
        o, _ = mamba_mixer(h, bp, cfg, ((cx_s, cbc_s), ssm_s))
        x = x + o
        if cfg.attn_every:
            x = jax.lax.cond((idx + 1) % cfg.attn_every == 0,
                             lambda v: _shared_attn_full(v, sp, cfg, positions),
                             lambda v: v, x)
        return shard_hint(x, "batch", None, None), None

    if remat:
        body = jax.checkpoint(body)
    idxs = jnp.arange(L, dtype=jnp.int32)
    x, _ = jax.lax.scan(body, x,
                        (params["blocks"], conv_x0, conv_bc0, ssm0, idxs))
    x = norm(x, params["final_norm"], cfg.norm)
    return shard_hint(jnp.einsum("btd,dv->btv", x, params["lm_head"]),
                      "batch", None, "model")


def loss_fn(params, batch, cfg: ModelConfig, remat=True):
    tokens = batch["tokens"]
    logits = forward(params, tokens, cfg,
                     remat=remat and cfg.remat)[:, :-1, :].astype(jnp.float32)
    targets = tokens[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def prefill(params, tokens, cfg: ModelConfig, max_len: int | None = None):
    """Prefill returning decode state (mamba states + per-invocation KV)."""
    B, T = tokens.shape
    max_len = max_len or cfg.max_seq
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    sp = params["shared"]
    state = init_state(cfg, B, max_len)
    L = cfg.n_layers
    KV, hd = cfg.n_kv_heads, cfg.hd

    def body(carry, xs):
        x, ak, av = carry
        bp, cx_s, cbc_s, ssm_s, idx = xs
        h = norm(x, bp["ln"], cfg.norm)
        o, ((cx_n, cbc_n), ssm_n) = mamba_mixer(h, bp, cfg,
                                                ((cx_s, cbc_s), ssm_s))
        x = x + o

        def with_attn(args):
            x, ak, av = args
            from .layers import _project_qkv, _sdpa
            h = norm(x, sp["ln1"], cfg.norm)
            q, k, v = _project_qkv(h, sp["attn"], cfg, positions)
            o = _sdpa(q, k, v, causal=True)
            x = x + jnp.einsum("bsh,hd->bsd", o.reshape(B, T, -1),
                               sp["attn"]["wo"])
            h2 = norm(x, sp["ln2"], cfg.norm)
            x = x + mlp(h2, sp["mlp"], cfg)
            inv = (idx + 1) // cfg.attn_every - 1
            pad = jnp.zeros((B, max_len - T, KV, hd), ak.dtype)
            k_full = jnp.concatenate([k.astype(ak.dtype), pad], axis=1)
            v_full = jnp.concatenate([v.astype(av.dtype), pad], axis=1)
            ak = jax.lax.dynamic_update_slice_in_dim(ak, k_full[None], inv, 0)
            av = jax.lax.dynamic_update_slice_in_dim(av, v_full[None], inv, 0)
            return x, ak, av

        if cfg.attn_every:
            x, ak, av = jax.lax.cond((idx + 1) % cfg.attn_every == 0,
                                     with_attn, lambda a: a, (x, ak, av))
        return (x, ak, av), (cx_n, cbc_n, ssm_n)

    idxs = jnp.arange(L, dtype=jnp.int32)
    (x, ak, av), (cx_f, cbc_f, ssm_f) = jax.lax.scan(
        body, (x, state["attn_k"], state["attn_v"]),
        (params["blocks"], state["conv_x"], state["conv_bc"], state["ssm"],
         idxs))

    x = norm(x, params["final_norm"], cfg.norm)
    logits = jnp.einsum("bd,dv->bv", x[:, -1, :], params["lm_head"])
    return logits, {"conv_x": cx_f, "conv_bc": cbc_f, "ssm": ssm_f,
                    "attn_k": ak, "attn_v": av,
                    "len": jnp.asarray(T, jnp.int32)}


def decode_step(params, cache, tokens, cfg: ModelConfig):
    """One-token decode: O(1) mamba update + cached shared attention."""
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)      # (B,1,D)
    sp = params["shared"]
    pos = cache["len"]
    L = cfg.n_layers

    def body(carry, xs):
        x, ak, av = carry
        bp, cx_s, cbc_s, ssm_s, idx = xs
        h = norm(x, bp["ln"], cfg.norm)
        o, ((cx_n, cbc_n), ssm_n) = mamba_mixer(h, bp, cfg,
                                                ((cx_s, cbc_s), ssm_s))
        x = x + o

        def with_attn(args):
            x, ak, av = args
            inv = (idx + 1) // cfg.attn_every - 1
            kc = jax.lax.dynamic_index_in_dim(ak, inv, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(av, inv, 0, keepdims=False)
            h = norm(x, sp["ln1"], cfg.norm)
            o, new_kv = attention_decode(h, sp["attn"], cfg,
                                         {"k": kc, "v": vc, "len": pos}, pos)
            x = x + o
            h2 = norm(x, sp["ln2"], cfg.norm)
            x = x + mlp(h2, sp["mlp"], cfg)
            ak = jax.lax.dynamic_update_slice_in_dim(ak, new_kv["k"][None], inv, 0)
            av = jax.lax.dynamic_update_slice_in_dim(av, new_kv["v"][None], inv, 0)
            return x, ak, av

        if cfg.attn_every:
            x, ak, av = jax.lax.cond((idx + 1) % cfg.attn_every == 0,
                                     with_attn, lambda a: a, (x, ak, av))
        return (x, ak, av), (cx_n, cbc_n, ssm_n)

    idxs = jnp.arange(L, dtype=jnp.int32)
    (x, ak, av), (cx_f, cbc_f, ssm_f) = jax.lax.scan(
        body, (x, cache["attn_k"], cache["attn_v"]),
        (params["blocks"], cache["conv_x"], cache["conv_bc"], cache["ssm"],
         idxs))

    x = norm(x, params["final_norm"], cfg.norm)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])[:, 0, :]
    return logits, {"conv_x": cx_f, "conv_bc": cbc_f, "ssm": ssm_f,
                    "attn_k": ak, "attn_v": av, "len": pos + 1}
