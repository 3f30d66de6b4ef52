"""Shared model primitives: norms, rotary, GQA attention (train + cached
decode), gated MLPs, and the capacity-based MoE layer.

All functions are pure; parameters are plain dict pytrees.  Layer stacks store
parameters with a leading layer axis and run under ``jax.lax.scan`` so HLO
size (and 1-core compile time for the 80 dry-run cells) is depth-independent.

Compute dtype is the input dtype (bf16 in production configs); softmax and
norm statistics accumulate in fp32.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import LAYOUT

Array = jax.Array
BIG_NEG = -2.0 ** 30


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------- sharding hints
def shard_hint(x: Array, *axes) -> Array:
    """with_sharding_constraint against the ambient mesh, if any.

    ``axes`` entries: 'batch' (expands to whichever of pod/data exist),
    'model', 'data', or None.  Outside a mesh context (``jax.set_mesh``;
    unit tests, one-chip runs) this is the identity, so model code can hint
    unconditionally.
    """
    m = jax.sharding.get_abstract_mesh()
    if m.empty:
        return x
    names = set(m.axis_names)
    mesh_sizes = dict(m.shape)
    layout = LAYOUT.get()
    fsdp = layout in ("fsdp", "ep")    # no TP on feature dims
    batch_gets_model = layout == "fsdp"
    spec = []
    for i, a in enumerate(axes):
        if a == "batch":
            cand = ("pod", "data", "model") if batch_gets_model \
                else ("pod", "data")
            ba = tuple(n for n in cand if n in names)
            if ba and i < x.ndim:
                total = 1
                for n in ba:
                    total *= mesh_sizes[n]
                while ba and x.shape[i] % total != 0:
                    total //= mesh_sizes[ba[-1]]
                    ba = ba[:-1]
            spec.append(ba if ba else None)
        elif a == "expert":
            # expert-parallel axis: stays on 'model' under EVERY layout
            spec.append("model" if "model" in names else None)
        elif a in names:
            # under fsdp, 'model' belongs to the batch dims — never to
            # feature dims (no tensor parallelism)
            spec.append(None if (fsdp and a == "model") else a)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


# ----------------------------------------------------------------- norms
def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def norm(x: Array, p: dict, kind: str) -> Array:
    if kind == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def init_norm(cfg: ModelConfig, shape_prefix=()) -> dict:
    d = cfg.d_model
    p = {"scale": jnp.ones(shape_prefix + (d,), dtype_of(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(shape_prefix + (d,), dtype_of(cfg))
    return p


# ----------------------------------------------------------------- rotary
def rope_freqs(cfg: ModelConfig, rot_dim: int) -> Array:
    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    return 1.0 / (cfg.rope_theta ** exponent)          # (rot_dim//2,)


def apply_rope(x: Array, positions: Array, cfg: ModelConfig) -> Array:
    """x: (..., S, n_heads, head_dim); positions: (..., S)."""
    hd = x.shape[-1]
    rot = int(hd * cfg.partial_rotary) // 2 * 2
    if rot == 0:
        return x
    freqs = rope_freqs(cfg, rot)                        # (rot/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, rot/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, rot/2)
    sin = jnp.sin(angles)[..., None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# -------------------------------------------------------------- attention
def init_attention(cfg: ModelConfig, key, shape_prefix=()) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(D)
    so = 1.0 / math.sqrt(H * hd)
    dt = dtype_of(cfg)
    p = {
        "wq": (jax.random.normal(k1, (*shape_prefix, D, H * hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (*shape_prefix, D, KV * hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (*shape_prefix, D, KV * hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (*shape_prefix, H * hd, D)) * so).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((*shape_prefix, H * hd), dt)
        p["bk"] = jnp.zeros((*shape_prefix, KV * hd), dt)
        p["bv"] = jnp.zeros((*shape_prefix, KV * hd), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((*shape_prefix, hd), dt)
        p["k_norm"] = jnp.ones((*shape_prefix, hd), dt)
    return p


def _project_qkv(x: Array, p: dict, cfg: ModelConfig, positions: Array):
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    return q, k, v


SDPA_CHUNK_THRESHOLD = 2048          # direct-path limit on max(Sq, Skv)
Q_CHUNK = 512
KV_CHUNK = 1024


def _sdpa_direct(q: Array, k: Array, v: Array, causal: bool,
                 q_offset: int | Array = 0) -> Array:
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (Sq, k.shape[1]), 0) + q_offset
        kpos = jax.lax.broadcasted_iota(jnp.int32, (Sq, k.shape[1]), 1)
        scores = jnp.where(qpos >= kpos, scores, BIG_NEG)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def _sdpa_chunked(q: Array, k: Array, v: Array, causal: bool) -> Array:
    """Flash-style online-softmax attention in jnp: O(S) memory.

    Scans q in blocks of Q_CHUNK; for each, scans kv in blocks of KV_CHUNK
    carrying (running max, running denom, weighted accumulator).  Peak temp
    is one (B,KV,G,Cq,Ckv) tile instead of the full S^2 score matrix.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV

    def _pick(n: int, target: int) -> int:
        c = min(target, n)
        while c > 1 and n % c:
            c //= 2
        return c if n % c == 0 else 1

    Cq = _pick(Sq, Q_CHUNK)
    Ck = _pick(Skv, KV_CHUNK)
    nq, nk = Sq // Cq, Skv // Ck
    scale = 1.0 / math.sqrt(hd)

    qb = q.reshape(B, nq, Cq, KV, G, hd)
    qb = jnp.moveaxis(qb, 1, 0)                       # (nq,B,Cq,KV,G,hd)
    kb = jnp.moveaxis(k.reshape(B, nk, Ck, KV, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, Ck, KV, hd), 1, 0)

    def q_block(qi, qt):
        m0 = jnp.full((B, KV, G, Cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, Cq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, Cq, hd), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, kt, vt = inp
            s = jnp.einsum("bqkgh,bskh->bkgqs", qt, kt).astype(jnp.float32)
            s *= scale
            if causal:
                qpos = qi * Cq + jax.lax.broadcasted_iota(
                    jnp.int32, (Cq, Ck), 0)
                kpos = kj * Ck + jax.lax.broadcasted_iota(
                    jnp.int32, (Cq, Ck), 1)
                s = jnp.where(qpos >= kpos, s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(qt.dtype), vt)
            return (m_new, l, acc), None

        ks = jnp.arange(nk, dtype=jnp.int32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1).reshape(B, Cq, KV * G, hd)

    qi = jnp.arange(nq, dtype=jnp.int32)
    out = jax.lax.map(lambda xs: q_block(*xs), (qi, qb))   # (nq,B,Cq,H,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def _sdpa(q: Array, k: Array, v: Array, causal: bool,
          q_offset: int | Array = 0) -> Array:
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd) -> (B,Sq,H,hd)."""
    Sq, Skv = q.shape[1], k.shape[1]
    if max(Sq, Skv) <= SDPA_CHUNK_THRESHOLD or Sq == 1:
        return _sdpa_direct(q, k, v, causal, q_offset)
    return _sdpa_chunked(q, k, v, causal)


def attention(x: Array, p: dict, cfg: ModelConfig, positions: Array,
              causal: bool = True) -> Array:
    """Full-sequence attention (train / prefill)."""
    B, S, D = x.shape
    q, k, v = _project_qkv(x, p, cfg, positions)
    out = _sdpa(q, k, v, causal)
    return jnp.einsum("bsh,hd->bsd", out.reshape(B, S, -1), p["wo"])


def attention_decode(x: Array, p: dict, cfg: ModelConfig, cache: dict,
                     pos: Array) -> tuple[Array, dict]:
    """One-token decode against a KV cache.

    cache: {'k','v': (B, S_max, KV, hd), 'len': scalar int32 current length}
    x: (B, 1, D); pos broadcasts (B,) or scalar.
    The cache sequence axis may be sharded (SP for long contexts): the
    partial-softmax combine is left to XLA SPMD over the masked full-length
    score vector.
    """
    B, S1, D = x.shape
    positions = jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None]      # (B,1)
    q, k_new, v_new = _project_qkv(x, p, cfg, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), cache["len"], axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), cache["len"], axis=1)
    S = k_cache.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S), 3)
    scores = jnp.where(kpos <= cache["len"], scores, BIG_NEG)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", w, v_cache).reshape(B, 1, H * hd)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return y, {"k": k_cache, "v": v_cache, "len": cache["len"] + 1}


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: Optional[int] = None) -> dict:
    KV, hd = cfg.n_kv_heads, cfg.hd
    L = cfg.n_layers if n_layers is None else n_layers
    dt = dtype_of(cfg)
    return {
        "k": jnp.zeros((L, batch, max_len, KV, hd), dt),
        "v": jnp.zeros((L, batch, max_len, KV, hd), dt),
        "len": jnp.zeros((), jnp.int32),
    }


# ------------------------------------------------------------------- MLPs
def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None,
             shape_prefix=()) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    s_in, s_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "wg": (jax.random.normal(ks[0], (*shape_prefix, D, F)) * s_in).astype(dt),
            "wu": (jax.random.normal(ks[1], (*shape_prefix, D, F)) * s_in).astype(dt),
            "wd": (jax.random.normal(ks[2], (*shape_prefix, F, D)) * s_out).astype(dt),
        }
    return {
        "wu": (jax.random.normal(ks[0], (*shape_prefix, D, F)) * s_in).astype(dt),
        "wd": (jax.random.normal(ks[1], (*shape_prefix, F, D)) * s_out).astype(dt),
    }


def mlp(x: Array, p: dict, cfg: ModelConfig) -> Array:
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wg"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["wu"])
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wu"]))
    return jnp.einsum("bsf,fd->bsd", h, p["wd"])


# -------------------------------------------------------------------- MoE
MOE_GROUP = 512      # tokens per dispatch group (memory/parallelism tradeoff)


def init_moe(cfg: ModelConfig, key, shape_prefix=()) -> dict:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    p = {
        "router": (jax.random.normal(ks[0], (*shape_prefix, D, E)) * s_in
                   ).astype(jnp.float32),
        "wg": (jax.random.normal(ks[1], (*shape_prefix, E, D, F)) * s_in).astype(dt),
        "wu": (jax.random.normal(ks[2], (*shape_prefix, E, D, F)) * s_in).astype(dt),
        "wd": (jax.random.normal(ks[3], (*shape_prefix, E, F, D)) * s_out).astype(dt),
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": (jax.random.normal(kk[0], (*shape_prefix, D, Fs)) * s_in).astype(dt),
            "wu": (jax.random.normal(kk[1], (*shape_prefix, D, Fs)) * s_in).astype(dt),
            "wd": (jax.random.normal(kk[2], (*shape_prefix, Fs, D)) * s_out).astype(dt),
        }
    return p


def moe_ffn(x: Array, p: dict, cfg: ModelConfig) -> tuple[Array, Array]:
    """Top-k capacity-based MoE (GShard-style einsum dispatch).

    x: (B, S, D) -> (B, S, D), plus aux load-balancing loss.
    Tokens are processed in groups of MOE_GROUP so the dispatch one-hots stay
    bounded; groups map onto the data axis, experts onto the model axis (EP).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * S
    G = min(MOE_GROUP, N)
    n_groups = N // G
    assert n_groups * G == N, f"MoE group {G} must divide tokens {N}"
    cap = max(1, int(G * K * cfg.capacity_factor / E))

    xg = x.reshape(n_groups, G, D)
    logits = jnp.einsum("ngd,de->nge", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                    # (n,G,E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)            # (n,G,K)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # position-in-expert bookkeeping, slot by slot (K is small)
    counts = jnp.zeros((n_groups, E), jnp.int32)
    dispatch = jnp.zeros((n_groups, G, E, cap), jnp.bool_)
    combine = jnp.zeros((n_groups, G, E, cap), jnp.float32)
    for slot in range(K):
        oh = jax.nn.one_hot(expert_idx[..., slot], E, dtype=jnp.int32)  # (n,G,E)
        pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None, :]           # (n,G,E)
        keep = (pos < cap) & (oh > 0)
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.bool_) & keep[..., None]
        dispatch = dispatch | pos_oh
        combine = combine + pos_oh * gate_vals[..., slot][..., None, None]
        counts = counts + (oh * keep).sum(axis=1)

    # NOTE (§Perf, qwen3-moe iterations): explicit expert-axis constraints
    # here were tried and REFUTED — GSPMD lowers the n->e reshard to
    # data-axis all-gathers (16x a2a volume) whichever way it is phrased;
    # the proper fix is an explicit shard_map a2a dispatch (future work).
    expert_in = jnp.einsum("ngec,ngd->necd", dispatch.astype(x.dtype), xg)
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("necd,edf->necf", expert_in, p["wg"]))
        h = h * jnp.einsum("necd,edf->necf", expert_in, p["wu"])
    else:
        h = jax.nn.gelu(jnp.einsum("necd,edf->necf", expert_in, p["wu"]))
    expert_out = jnp.einsum("necf,efd->necd", h, p["wd"])
    y = jnp.einsum("ngec,necd->ngd", combine.astype(x.dtype), expert_out)
    y = y.reshape(B, S, D)

    if cfg.n_shared_experts:
        y = y + mlp(x, p["shared"], cfg)

    # aux: Switch-style load-balance loss
    me = probs.mean(axis=1)                                    # (n,E)
    ce = (dispatch.sum(axis=(1, 3)) / G).astype(jnp.float32)   # fraction per e
    aux = (me * ce).sum(axis=-1).mean() * E
    return y, aux
