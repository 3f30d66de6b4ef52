"""Plain reference for training cells, and the comparison that decides
``correct``.

The reference imports nothing of the program.  It computes in float32 with
every matrix product at HIGHEST precision (three bf16 passes are not
enough: a TPU runs float32 products in bf16 unless told otherwise).  Each
configuration's forward pass and loss sit beside its JSON file
(``configs/<name>.py``, a ``loss(params, batch, cfg, mm)``); this file holds
what they share: norms, rotary positions, attention, AdamW, the step driven
in blocks of rows so that it fits beside nothing, and the numbers compared.

``mm`` is the one place precision enters.  ``MM_F32`` is the reference;
``MM_FP8`` is the control: the same products with both operands rounded to
float8 e4m3 under a per-tensor scale, and the cotangent of the backward
products to float8 e5m2.  That is the lower precision a later change would
be tempted by, and ``correct`` has to come out false under it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NUMBERS = ("loss_gap", "grad_gap", "grad_diff", "update_gap")
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ products
def mm_f32(eq: str, a, b):
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _round(x, dtype, top: float):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    scale = jax.lax.stop_gradient(scale)
    return (x / scale).astype(dtype).astype(F32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def mm_fp8(eq: str, a, b):
    return mm_f32(eq, _round(a, jnp.float8_e4m3fn, 448.0),
                  _round(b, jnp.float8_e4m3fn, 448.0))


def _mm_fp8_fwd(eq, a, b):
    qa = _round(a, jnp.float8_e4m3fn, 448.0)
    qb = _round(b, jnp.float8_e4m3fn, 448.0)
    return mm_f32(eq, qa, qb), (qa, qb)


def _mm_fp8_bwd(eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: mm_f32(eq, x, y), qa, qb)
    return vjp(_round(g, jnp.float8_e5m2, 57344.0))


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)

MM = {"f32": mm_f32, "fp8": mm_fp8}


# ---------------------------------------------------------- primitives
def layer_norm(x, p, eps: float):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def rms_norm(x, p, eps: float):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1 + jnp.exp(-x))


def rope(x, theta: float):
    """Rotate-half rotary positions over the whole head: x (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]     # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(xq, xkv, p, mm, *, heads: int, kv_heads: int, causal: bool,
              theta: float | None):
    """Multi-head attention with grouped K/V heads; ``theta`` None means no
    rotary positions.  Biases are added where ``p`` has them."""
    b, sq, _ = xq.shape
    skv = xkv.shape[1]
    q = mm("bsd,dh->bsh", xq, p["wq"])
    k = mm("bsd,dh->bsh", xkv, p["wk"])
    v = mm("bsd,dh->bsh", xkv, p["wv"])
    if "bq" in p:
        q, k, v = (q + p["bq"].astype(F32), k + p["bk"].astype(F32),
                   v + p["bv"].astype(F32))
    hd = q.shape[-1] // heads
    q = q.reshape(b, sq, heads, hd)
    k = k.reshape(b, skv, kv_heads, hd)
    v = v.reshape(b, skv, kv_heads, hd)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    rep = heads // kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, skv), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", w, v).reshape(b, sq, heads * hd)
    return mm("bsh,hd->bsd", o, p["wo"])


def cross_entropy(logits, tokens):
    """Mean next-token loss: position t predicts token t+1."""
    lg = logits[:, :-1]
    tgt = tokens[:, 1:]
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


# --------------------------------------------------------------- AdamW
def adamw(params, grads, opt, hp: dict):
    """AdamW (decoupled weight decay) after global-norm clipping, with
    linear warm-up and cosine decay.  Returns (params, opt, clipped
    grads)."""
    step = opt["step"] + 1
    stepf = step.astype(F32)
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, hp["clip_norm"] / (gnorm + 1e-9))
    warm = stepf / max(1.0, hp["warmup_steps"])
    t = jnp.clip((stepf - hp["warmup_steps"])
                 / max(1.0, hp["total_steps"] - hp["warmup_steps"]), 0.0, 1.0)
    cos = hp["min_lr_frac"] + (1 - hp["min_lr_frac"]) * 0.5 \
        * (1 + jnp.cos(jnp.pi * t))
    lr = hp["lr"] * jnp.where(stepf < hp["warmup_steps"], warm, cos)
    b1, b2 = hp["betas"]
    g = jax.tree.map(lambda x: x * clip, grads)
    m = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, opt["m"], g)
    v = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, opt["v"], g)
    bc1, bc2 = 1 - b1 ** stepf, 1 - b2 ** stepf
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + hp["eps"])
                                  + hp["weight_decay"] * p),
        params, m, v)
    return new, {"step": step, "m": m, "v": v}, g


def diff_norms(a, b) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)
                                                  - y.astype(F32))))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


# ------------------------------------------------------- reference run
def run_steps(loss_fn, cfg: dict, params0, batch_at, n_steps: int = 3,
              mm: str = "f32", rows=None, row_block: int = 1) -> dict:
    """Train ``n_steps`` from ``params0`` on ``batch_at(0..n_steps-1)``.

    The gradient of the batch mean is the mean of the gradients of blocks
    of ``row_block`` rows (every row has as many loss positions), so the
    step fits beside nothing else.  ``rows`` keeps only the first so many
    rows of each batch (a fault: half the batch left out).  Returns the
    readings ``compare`` takes, as numpy arrays."""
    mmf = MM[mm]
    hp = cfg["optimizer"]

    @jax.jit
    def row_grad(params, rb):
        return jax.value_and_grad(lambda p: loss_fn(p, rb, cfg, mmf))(params)

    @jax.jit
    def acc(total, part):
        return jax.tree.map(jnp.add, total, part)

    @jax.jit
    def update(params, grads, opt, n):
        grads = jax.tree.map(lambda g: g / n, grads)
        return adamw(params, grads, opt, hp)

    params = jax.tree.map(lambda x: x.astype(F32), params0)
    p0 = params
    opt = {"step": jnp.zeros((), jnp.int32),
           "m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params)}
    losses, g1 = [], None
    for s in range(n_steps):
        batch = batch_at(s)
        n = rows or batch["tokens"].shape[0]
        total_l, total_g = 0.0, None
        for r in range(0, n, row_block):
            rb = {k: v[r:r + row_block] for k, v in batch.items()}
            l, g = row_grad(params, rb)
            total_l = total_l + l
            total_g = g if total_g is None else acc(total_g, g)
        nb = n // row_block
        params, opt, g = update(params, total_g, opt, jnp.float32(nb))
        losses.append(total_l / nb)
        if s == 0:
            g1 = [np.asarray(x) for x in jax.tree.leaves(g)]
    return {"loss": np.asarray(jnp.stack(losses), np.float64),
            "grad1": np.array([np.linalg.norm(x) for x in g1], np.float64),
            "grad1_leaves": g1,
            "update": np.asarray(diff_norms(params, p0), np.float64)}


# ---------------------------------------------------------- comparison
def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray,
                   against: np.ndarray | None = None) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf (or of ``against``) or
    of the median leaf, whichever is larger."""
    base = (ref if against is None else against)[keep]
    floor = float(np.median(base))
    gap = np.abs(prog[keep] - ref[keep])
    return float(np.max(gap / np.maximum(base, floor)))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared for a training cell.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone (a key's bias under softmax) and are left out.

    ``grad_diff`` is the one number not of the training form: the norm of
    the difference of the first gradients, by the worst leaf.  Rounding
    that is random moves a norm only in the second order, so float8 reads
    barely above bfloat16 on ``grad_gap``; on the difference it reads in
    the first order."""
    g = ref["grad1"]
    keep = g >= 1e-3 * np.median(g)
    diff = np.array([np.linalg.norm(a.astype(np.float32)
                                    - b.astype(np.float32))
                     for a, b in zip(prog["grad1_leaves"],
                                     ref["grad1_leaves"])])
    return {
        "loss_gap": float(np.max(np.abs(prog["loss"] - ref["loss"])
                                 / np.abs(ref["loss"]))),
        "grad_gap": worst_leaf_gap(prog["grad1"], g, keep),
        "grad_diff": worst_leaf_gap(diff, np.zeros_like(diff), keep,
                                    against=g),
        "update_gap": worst_leaf_gap(prog["update"], ref["update"], keep),
    }
