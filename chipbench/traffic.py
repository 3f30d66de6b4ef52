"""What a run feeds the system, made from ``--seed`` alone.

- ``seed_key``: a PRNG key from any whole number the driver passes (its seeds
  go past 32 bits), split into two 31-bit words.
- ``make_state``: weights and optimizer state for a training configuration,
  made on the device in one jitted call, in the dtype they are trained in.
  The plain reference is given the same numbers, so neither side takes
  anything the other made.  A configuration's optional ``init`` map (a
  leaf-name suffix → ``"zeros"``, ``"ones"`` or ``{"normal": std}``) sets
  the leaves it names; the rules in ``_rule`` set the rest.
- ``make_feed``: the batch of step ``idx`` as a pure function of
  ``(seed, idx)``, jitted once; a resumed run replays exactly the batches
  it crashed on.

Everything else a traffic mix sets (cadence, crash point, window) is data
in ``chipbench/mixes/<name>.json``, read by ``run.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _rule(name: str, shape, init: dict):
    """How leaf ``name`` starts: the ``init`` entry of the longest suffix
    that ends ``name`` at a ``/``, else the rules every configuration
    shares."""
    hits = [k for k in init if name == k or name.endswith("/" + k)]
    if hits:
        return init[max(hits, key=len)]
    last = name.rsplit("/", 1)[-1]
    if last == "scale":                              # norm gains
        return "ones"
    if last in ("bias", "bq", "bk", "bv"):           # norm shifts, QKV bias
        return "zeros"
    if last == "embed" or name == "embed":
        return {"normal": 0.02}
    if len(shape) < 2:
        raise ValueError(f"leaf {name!r} of shape {shape} has no rule: "
                         "name it in the configuration's init map")
    return {"normal": 1.0 / math.sqrt(shape[-2])}   # (..., fan_in, fan_out)


def _init_leaf(key, name: str, shape, dtype, init: dict):
    rule = _rule(name, shape, init)
    if rule == "ones":
        return jnp.ones(shape, dtype)
    if rule == "zeros":
        return jnp.zeros(shape, dtype)
    if not (isinstance(rule, dict) and set(rule) == {"normal"}):
        raise ValueError(f"init entry for leaf {name!r} is {rule!r}; "
                         'give "zeros", "ones" or {"normal": std}')
    std = rule["normal"]
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(seed: int, shapes, init: dict | None = None):
    """Weights shaped like ``shapes`` (a pytree of ShapeDtypeStruct), made
    on the device in one jitted call; ``init`` is the configuration's map."""
    named = jax.tree_util.tree_flatten_with_path(shapes)
    leaves, treedef = named
    spec = [(leaf_name(p), tuple(s.shape), s.dtype) for p, s in leaves]
    init = init or {}

    def build(key):
        key = jax.random.fold_in(key, 0)
        out = [_init_leaf(jax.random.fold_in(key, i), n, sh, dt, init)
               for i, (n, sh, dt) in enumerate(spec)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))


def make_state(seed: int, shapes, init: dict | None = None):
    """``{"params", "opt"}`` as the training step takes it: parameters in
    their training dtype, fp32 master copy, zero moments, step 0."""
    def build(params):
        f32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return {"params": params,
                "opt": {"step": jnp.zeros((), jnp.int32), "master": f32,
                        "m": jax.tree.map(jnp.zeros_like, f32),
                        "v": jax.tree.map(jnp.zeros_like, f32)}}
    return jax.jit(build)(make_params(seed, shapes, init))


def make_feed(seed: int, job: dict, vocab: int, d_model: int,
              dtype: str = "bfloat16"):
    """``batch_at(idx)``: tokens ``(batch, seq)`` drawn uniformly from the
    vocabulary (every row differs), and for an encoder-decoder job the
    encoder's input frames ``(batch, frames, d_model)``."""
    key = jax.random.fold_in(seed_key(seed), 1)
    b, s, t = job["batch"], job["seq"], job.get("frames", 0)

    @jax.jit
    def gen(idx):
        k = jax.random.fold_in(key, idx)
        k1, k2 = jax.random.split(k)
        out = {"tokens": jax.random.randint(k1, (b, s), 0, vocab, jnp.int32)}
        if t:
            out["frames"] = jax.random.normal(
                k2, (b, t, d_model), jnp.float32).astype(dtype)
        return out

    def batch_at(idx: int):
        return gen(jnp.int32(idx))

    return batch_at
