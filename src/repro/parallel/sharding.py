"""Sharding rules: DP / FSDP / TP / EP / SP over a device mesh.

Mesh axes: ``(data, model)``, or ``(pod, data, model)`` across pods.
Recipe (MaxText-style FSDP+TP):

  * batch        -> ('pod','data')           data parallel (hierarchical)
  * TP           -> 'model' on head/ff/vocab dims (Megatron split)
  * FSDP         -> 'data' on the non-TP large dim of every weight
                    (all-gather per layer under scan, overlappable)
  * EP           -> experts over 'model'
  * SP           -> long-context KV cache sequence over 'data' (and over
                    'model' when kv-heads don't divide) — flash-decode style
                    partial-softmax combine is generated by SPMD
  * optimizer    -> same sharding as its param (ZeRO-1-equivalent: fp32
                    master+m+v bytes / devices)

Every rule degrades to replication when a dim doesn't divide the axis.
"""
from __future__ import annotations

import contextvars
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# Layout selector:
#   'tp'   — Megatron TP over 'model' + FSDP over 'data'  (baseline)
#   'fsdp' — batch + weight sharding over BOTH axes, no tensor parallelism
#            (except MoE experts, which stay EP over 'model').  Right for
#            models whose per-layer matmuls are too small to amortize
#            activation all-reduces at TP=16.
#   'dp'   — weights fully replicated (tiny models: gathering costs more
#            than storing), batch over pod/data only.
#   'ep'   — MoE recipe: batch over pod/data ONLY (leaves 'model' free for
#            experts), non-expert weights FSDP over 'data', experts EP over
#            'model' with explicit token redistribution.
LAYOUT: contextvars.ContextVar[str] = contextvars.ContextVar("layout",
                                                             default="tp")


def set_layout(layout: str):
    return LAYOUT.set(layout)


def batch_axes(mesh: Mesh, global_batch: Optional[int] = None):
    axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if LAYOUT.get() == "fsdp":
        full = axes + ("model",)
        if global_batch is None:
            return full
        total = 1
        for a in full:
            total *= mesh.shape[a]
        if global_batch % total == 0:
            return full
    return axes


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _maybe(mesh: Mesh, axis: Optional[str], dim: int):
    """axis if dim divides the mesh axis size, else None (replicate)."""
    if axis is None:
        return None
    if isinstance(axis, tuple):
        total = 1
        for a in axis:
            total *= _axis_size(mesh, a)
        return axis if dim % total == 0 else None
    return axis if dim % _axis_size(mesh, axis) == 0 else None


# ---------------------------------------------------------------- params
# rules keyed by parameter leaf name: (in_axis_rule, out_axis_rule) applied to
# the last two dims; leading (layer-stack) dims replicate.
_COL = {"wq", "wk", "wv", "wg", "wu", "w_z", "w_x", "wr", "ck", "cr",
        "w_lora_a"}                       # (.., D, out): FSDP in, TP out
_ROW = {"wo", "wd", "cv", "out_proj", "w_lora_b"}   # (.., in, D): TP in, FSDP out
_REP_IN = {"w_bc", "w_dt", "router"}      # (.., D, small): FSDP in only


def param_pspec(path: tuple, arr, mesh: Mesh) -> P:
    name = None
    for p in reversed(path):
        if hasattr(p, "key"):
            name = p.key
            break
        if isinstance(p, str):
            name = p
            break
    shape = arr.shape
    nd = len(shape)
    in_moe = any(getattr(p, "key", p) == "moe" for p in path)

    def spec(*last_axes):
        return P(*([None] * (nd - len(last_axes)) + list(last_axes)))

    layout = LAYOUT.get()
    fsdp = layout == "fsdp"
    if layout == "dp" and not in_moe:
        return P()

    if layout == "ep" and not in_moe:
        # FSDP over 'data' only; 'model' reserved for experts
        if nd >= 2:
            big_axis = -1 if shape[-1] >= shape[-2] else -2
            out = [None, None]
            out[big_axis] = _maybe(mesh, "data", shape[big_axis])
            return spec(*out)
        return spec(_maybe(mesh, "data", shape[-1]))

    if in_moe and name in ("wg", "wu"):        # (L, E, D, F): EP + FSDP
        return spec(_maybe(mesh, "model", shape[-3]),
                    _maybe(mesh, "data", shape[-2]), None)
    if in_moe and name == "wd":                # (L, E, F, D)
        return spec(_maybe(mesh, "model", shape[-3]), None,
                    _maybe(mesh, "data", shape[-1]))

    if fsdp:
        # storage-only sharding: spread the largest dim over both axes when
        # divisible, else over 'data'; no tensor parallelism
        if nd >= 2:
            d_last, d_prev = shape[-1], shape[-2]
            big_axis = -1 if d_last >= d_prev else -2
            dim = shape[big_axis]
            sh = (_maybe(mesh, ("data", "model"), dim)
                  or _maybe(mesh, "data", dim)
                  or _maybe(mesh, "model", dim))
            out = [None, None]
            out[big_axis] = sh
            return spec(*out)
        return spec(_maybe(mesh, "data", shape[-1]))

    if name == "embed":
        return spec(_maybe(mesh, "model", shape[-2]),
                    _maybe(mesh, "data", shape[-1]))
    if name == "lm_head":
        return spec(_maybe(mesh, "data", shape[-2]),
                    _maybe(mesh, "model", shape[-1]))
    if name in _COL and nd >= 2:
        return spec(_maybe(mesh, "data", shape[-2]),
                    _maybe(mesh, "model", shape[-1]))
    if name in _ROW and nd >= 2:
        return spec(_maybe(mesh, "model", shape[-2]),
                    _maybe(mesh, "data", shape[-1]))
    if name in _REP_IN and nd >= 2:
        return spec(_maybe(mesh, "data", shape[-2]), None)
    if name in ("bq", "bk", "bv"):
        return spec(_maybe(mesh, "model", shape[-1]))
    if name in ("gate_norm",):
        return spec(_maybe(mesh, "model", shape[-1]))
    if name in ("conv_x_w", "conv_x_b"):
        return spec(_maybe(mesh, "model", shape[-1]))
    # norms, biases, mu, decay params, u, gn_scale, A_log, D, dt_bias ...
    return P()


def params_shardings(params_shape: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, a: NamedSharding(mesh, param_pspec(path, a, mesh)),
        params_shape)


def opt_shardings(opt_shape: Any, mesh: Mesh, param_sh: Any) -> Any:
    """master/m/v follow the params; scalars replicate."""
    rep = NamedSharding(mesh, P())
    return {
        "step": rep,
        "master": jax.tree.map(lambda s: s, param_sh),
        "m": jax.tree.map(lambda s: s, param_sh),
        "v": jax.tree.map(lambda s: s, param_sh),
    }


# ---------------------------------------------------------------- batches
def batch_pspec(cfg: ModelConfig, mesh: Mesh, global_batch: int) -> dict:
    ba = _maybe(mesh, batch_axes(mesh, global_batch), global_batch)
    out = {"tokens": P(ba, None)}
    if cfg.family == "vlm":
        out["patches"] = P(ba, None, None)
    elif cfg.family == "audio":
        out["frames"] = P(ba, None, None)
    return out


# ----------------------------------------------------------------- caches
def cache_pspecs(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int) -> Any:
    """PartitionSpec tree matching api.init_cache's structure."""
    b = _maybe(mesh, "data", batch)
    kv = _maybe(mesh, "model", cfg.n_kv_heads)
    # SP: if kv heads don't divide TP, shard cache sequence over 'model';
    # when batch=1 (long context) shard sequence over 'data' too.
    if b is None:
        if kv is None:
            seq_axis = _maybe(mesh, ("data", "model"), max_len) \
                or _maybe(mesh, "data", max_len)
        else:
            seq_axis = _maybe(mesh, "data", max_len)
    else:
        seq_axis = None if kv is not None else _maybe(mesh, "model", max_len)

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return {
            "k": P(None, b, seq_axis, kv, None),
            "v": P(None, b, seq_axis, kv, None),
            "len": P(),
        }
    if fam == "ssm":
        d_sh = _maybe(mesh, "model", cfg.d_model)
        hd_sh = _maybe(mesh, "model", cfg.rwkv_head_dim)
        return {
            "state": (P(None, b, d_sh), P(None, b, d_sh),
                      P(None, b, None, None, hd_sh)),
            "len": P(),
        }
    if fam == "hybrid":
        from repro.models.zamba2 import dims
        d_inner, H, Ph, N = dims(cfg)
        h_sh = _maybe(mesh, "model", H)
        di_sh = _maybe(mesh, "model", d_inner)
        attn_seq = None if kv is not None else _maybe(mesh, "model", max_len)
        if b is None:
            attn_seq = _maybe(mesh, "data", max_len)
        return {
            "conv_x": P(None, b, None, di_sh),
            "conv_bc": P(None, b, None, None),
            "ssm": P(None, b, h_sh, None, None),
            "attn_k": P(None, b, attn_seq, kv, None),
            "attn_v": P(None, b, attn_seq, kv, None),
            "len": P(),
        }
    if fam == "audio":
        seq = None if kv is not None else _maybe(mesh, "model", max_len)
        return {
            "k": P(None, b, seq, kv, None),
            "v": P(None, b, seq, kv, None),
            "cross_k": P(None, b, None, kv, None),
            "cross_v": P(None, b, None, kv, None),
            "len": P(),
        }
    raise ValueError(fam)


def to_named(tree_pspec: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p), tree_pspec,
        is_leaf=lambda x: isinstance(x, P))
