"""Step builders: the jit-able train / prefill / decode entry points with
their input ShapeDtypeStruct specs + shardings, for lowering and compiling a
step on a mesh without allocating it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.optim import AdamWConfig, apply_updates, init_opt_state
from repro.parallel.sharding import (batch_pspec, cache_pspecs,
                                     params_shardings, opt_shardings,
                                     to_named)


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def batch_structs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                  seq: Optional[int] = None) -> dict:
    B = shape.global_batch
    S = seq if seq is not None else shape.seq_len
    specs = batch_pspec(cfg, mesh, B)
    dt = jnp.dtype(cfg.dtype)
    out = {"tokens": _sds((B, S), jnp.int32,
                          NamedSharding(mesh, specs["tokens"]))}
    if cfg.family == "vlm":
        out["patches"] = _sds((B, cfg.n_patches, cfg.d_model), dt,
                              NamedSharding(mesh, specs["patches"]))
    elif cfg.family == "audio":
        out["frames"] = _sds((B, cfg.enc_ctx, cfg.d_model), dt,
                             NamedSharding(mesh, specs["frames"]))
    return out


@dataclass
class LoweringSpec:
    """Everything needed to .lower() one (arch x shape x mesh) cell."""
    fn: Callable
    args: tuple
    donate: tuple = ()


def _sharded_struct_tree(shape_tree, shardings):
    return jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), shape_tree, shardings)


def make_train_spec(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                    opt_cfg: Optional[AdamWConfig] = None) -> LoweringSpec:
    api = build_model(cfg)
    opt_cfg = opt_cfg or AdamWConfig()
    p_shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    p_sh = params_shardings(p_shapes, mesh)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(api.loss)(params, batch)
        # grads adopt the optimizer-state sharding.  Intended to lower the
        # data-parallel reduction to reduce-scatter (1x traffic) instead of
        # all-reduce (2x); measured NO-OP on this XLA version — the
        # partitioner emits AR+slice anyway.  Kept because it is
        # semantically correct and future partitioners (Shardy) fuse it.
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, p_sh)
        new_params, new_opt, metrics = apply_updates(params, grads,
                                                     opt_state, opt_cfg)
        return new_params, new_opt, {"loss": loss, **metrics}
    o_shapes = jax.eval_shape(init_opt_state, p_shapes)
    o_sh = opt_shardings(o_shapes, mesh, p_sh)
    params_in = _sharded_struct_tree(p_shapes, p_sh)
    opt_in = _sharded_struct_tree(o_shapes, o_sh)
    batch_in = batch_structs(cfg, shape, mesh)
    return LoweringSpec(fn=train_step, args=(params_in, opt_in, batch_in))


def make_prefill_spec(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh
                      ) -> LoweringSpec:
    api = build_model(cfg)

    def prefill_step(params, batch):
        return api.prefill(params, batch)

    p_shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    p_sh = params_shardings(p_shapes, mesh)
    params_in = _sharded_struct_tree(p_shapes, p_sh)
    batch_in = batch_structs(cfg, shape, mesh)
    return LoweringSpec(fn=prefill_step, args=(params_in, batch_in))


def make_decode_spec(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh
                     ) -> LoweringSpec:
    """serve_step: ONE new token against a seq_len KV cache."""
    api = build_model(cfg)
    B = shape.global_batch
    max_len = shape.seq_len

    def decode_fn(params, cache, tokens):
        return api.decode(params, cache, tokens)

    p_shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    p_sh = params_shardings(p_shapes, mesh)
    params_in = _sharded_struct_tree(p_shapes, p_sh)
    c_shapes = jax.eval_shape(lambda: api.init_cache(B, max_len))
    c_sh = to_named(cache_pspecs(cfg, mesh, B, max_len), mesh)
    cache_in = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                            c_shapes, c_sh)
    tok_spec = batch_pspec(cfg, mesh, B)["tokens"]
    tokens_in = _sds((B, 1), jnp.int32, NamedSharding(mesh, tok_spec))
    return LoweringSpec(fn=decode_fn, args=(params_in, cache_in, tokens_in))


def make_spec(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> LoweringSpec:
    if shape.kind == "train":
        return make_train_spec(cfg, shape, mesh)
    if shape.kind == "prefill":
        return make_prefill_spec(cfg, shape, mesh)
    return make_decode_spec(cfg, shape, mesh)
