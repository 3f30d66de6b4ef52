"""RWKV-6 "Finch": attention-free LM with data-dependent per-channel decay
(arXiv:2404.05892).

Time-mix (WKV6) recurrence per head (state S: key-dim x value-dim):

    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T,   w_t = exp(-exp(wr_t))  (data-dep.)

The model forward uses an exact ``lax.scan`` over time (compile-time is
T-independent).  Decode carries (S, token-shift) state — O(1) per token.

Deviations from the paper: token-shift lerp coefficients are static (the
paper's LoRA-produced dynamic lerp is an accuracy refinement orthogonal to the
systems work); decay LoRA is kept because it is the data-dependence itself.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from .layers import dtype_of, init_norm, norm, shard_hint

Array = jax.Array
LORA = 64
DECAY_CLAMP = 8.0     # |log w| <= 8 per step: numerics guard for chunked form


def init_rwkv6(cfg: ModelConfig, rng) -> dict:
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    hd = cfg.rwkv_head_dim
    H = D // hd
    dt = dtype_of(cfg)
    ks = jax.random.split(rng, 16)
    s = 1.0 / math.sqrt(D)

    def mat(k, *shape, scale=s):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    params = {
        "embed": mat(ks[0], V, D, scale=0.02),
        "lm_head": mat(ks[1], D, V),
        "final_norm": init_norm(cfg),
        "blocks": {
            "ln1": init_norm(cfg, (L,)),
            "ln2": init_norm(cfg, (L,)),
            # time-mix
            "mu": jnp.full((L, 5, D), 0.5, dt),          # r,k,v,w,g lerps
            "wr": mat(ks[2], L, D, D), "wk": mat(ks[3], L, D, D),
            "wv": mat(ks[4], L, D, D), "wg": mat(ks[5], L, D, D),
            "wo": mat(ks[6], L, D, D),
            "w_bias": jnp.full((L, D), -2.0, jnp.float32),
            "w_lora_a": mat(ks[7], L, D, LORA),
            "w_lora_b": mat(ks[8], L, LORA, D, scale=1.0 / math.sqrt(LORA)),
            "u": (jax.random.normal(ks[9], (L, H, hd)) * 0.1).astype(jnp.float32),
            "gn_scale": jnp.ones((L, H, hd), dt),        # per-head groupnorm
            # channel-mix
            "mu_c": jnp.full((L, 2, D), 0.5, dt),        # k,r lerps
            "ck": mat(ks[10], L, D, F),
            "cv": mat(ks[11], L, F, D, scale=1.0 / math.sqrt(F)),
            "cr": mat(ks[12], L, D, D),
        },
    }
    return params


def _shift(x: Array, prev: Array) -> Array:
    """Token shift: x_{t-1}; position 0 uses ``prev`` (decode carry)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _decay(xw: Array, bp: dict) -> Array:
    """Data-dependent per-channel log-decay, clamped for chunked numerics."""
    lora = jnp.tanh(xw.astype(jnp.float32) @ bp["w_lora_a"].astype(jnp.float32))
    raw = bp["w_bias"] + lora @ bp["w_lora_b"].astype(jnp.float32)
    return -jnp.clip(jnp.exp(raw), 1e-4, DECAY_CLAMP)      # log w_t  (negative)


def wkv_scan(r, k, v, logw, u, state):
    """Exact recurrence.  r,k,v: (B,T,H,hd); logw: (B,T,H,hd) log-decay;
    u: (H,hd); state: (B,H,hd,hd).  Returns (y (B,T,H,hd), final state)."""
    B, T, H, hd = r.shape

    def step(S, inp):
        r_t, k_t, v_t, lw_t = inp                       # (B,H,hd)
        w_t = jnp.exp(lw_t)
        kv = k_t[..., :, None] * v_t[..., None, :]      # (B,H,hd,hd)
        y = jnp.einsum("bhi,bhij->bhj", r_t,
                       S + u[..., :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, y

    rs, ks_, vs, lws = (jnp.moveaxis(a, 1, 0) for a in (r, k, v, logw))
    state, ys = jax.lax.scan(step, state, (rs.astype(jnp.float32),
                                           ks_.astype(jnp.float32),
                                           vs.astype(jnp.float32), lws))
    return jnp.moveaxis(ys, 0, 1), state


def _time_mix(x, bp, cfg, tm_prev, wkv_state):
    """Returns (out, new_tm_shift, new_wkv_state)."""
    B, T, D = x.shape
    hd = cfg.rwkv_head_dim
    H = D // hd
    xs = _shift(x, tm_prev)
    mu = bp["mu"]
    xr, xk, xv, xw, xg = (x + (xs - x) * mu[i] for i in range(5))
    r = (xr @ bp["wr"]).reshape(B, T, H, hd)
    k = (xk @ bp["wk"]).reshape(B, T, H, hd)
    v = (xv @ bp["wv"]).reshape(B, T, H, hd)
    g = jax.nn.silu(xg @ bp["wg"])
    logw = _decay(xw, bp).reshape(B, T, H, hd)
    y, new_state = wkv_scan(r, k, v, logw, bp["u"], wkv_state)
    # per-head group norm
    yf = y.astype(jnp.float32)
    mu_h = yf.mean(-1, keepdims=True)
    var = yf.var(-1, keepdims=True)
    y = ((yf - mu_h) * jax.lax.rsqrt(var + 1e-5) * bp["gn_scale"]
         ).reshape(B, T, D).astype(x.dtype)
    out = (y * g) @ bp["wo"]
    return out, x[:, -1, :], new_state


def _channel_mix(x, bp, cfg, cm_prev):
    xs = _shift(x, cm_prev)
    mu = bp["mu_c"]
    xk = x + (xs - x) * mu[0]
    xr = x + (xs - x) * mu[1]
    kk = jnp.square(jax.nn.relu(xk @ bp["ck"]))
    out = jax.nn.sigmoid(xr @ bp["cr"]) * (kk @ bp["cv"])
    return out, x[:, -1, :]


def _block(x, bp, cfg, state):
    tm_prev, cm_prev, wkv = state
    h = norm(x, bp["ln1"], cfg.norm)
    o, tm_new, wkv_new = _time_mix(h, bp, cfg, tm_prev, wkv)
    x = x + o
    h = norm(x, bp["ln2"], cfg.norm)
    o, cm_new = _channel_mix(h, bp, cfg, cm_prev)
    return x + o, (tm_new, cm_new, wkv_new)


def init_state(cfg: ModelConfig, batch: int) -> tuple:
    D = cfg.d_model
    hd = cfg.rwkv_head_dim
    H = D // hd
    L = cfg.n_layers
    dt = dtype_of(cfg)
    return (jnp.zeros((L, batch, D), dt),                    # time-mix shift
            jnp.zeros((L, batch, D), dt),                    # channel-mix shift
            jnp.zeros((L, batch, H, hd, hd), jnp.float32))   # wkv state


def forward(params, tokens, cfg: ModelConfig, state=None, remat=False):
    """tokens (B,T) -> (logits, final state)."""
    B, T = tokens.shape
    x = shard_hint(jnp.take(params["embed"], tokens, axis=0),
                   "batch", None, None)
    if state is None:
        state = init_state(cfg, B)
    tm0, cm0, wkv0 = state

    def body(x, xs):
        bp, tm, cm, wkv = xs
        x, (tm2, cm2, wkv2) = _block(x, bp, cfg, (tm, cm, wkv))
        return shard_hint(x, "batch", None, None), (tm2, cm2, wkv2)

    if remat:
        body = jax.checkpoint(body)
    x, (tm, cm, wkv) = jax.lax.scan(body, x, (params["blocks"], tm0, cm0, wkv0))
    x = norm(x, params["final_norm"], cfg.norm)
    logits = shard_hint(jnp.einsum("btd,dv->btv", x, params["lm_head"]),
                        "batch", None, "model")
    return logits, (tm, cm, wkv)


def loss_fn(params, batch, cfg: ModelConfig, remat=True):
    tokens = batch["tokens"]
    logits, _ = forward(params, tokens, cfg, remat=remat and cfg.remat)
    logits = logits[:, :-1, :].astype(jnp.float32)
    targets = tokens[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def prefill(params, tokens, cfg: ModelConfig):
    logits, state = forward(params, tokens, cfg)
    return logits[:, -1, :], {"state": state,
                              "len": jnp.asarray(tokens.shape[1], jnp.int32)}


def decode_step(params, cache, tokens, cfg: ModelConfig):
    """O(1) decode: seq_len only sets the position counter (no KV cache)."""
    logits, state = forward(params, tokens, cfg, state=cache["state"])
    return logits[:, -1, :], {"state": state, "len": cache["len"] + 1}
