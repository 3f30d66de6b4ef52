"""Batched serving driver: prefill a request batch, decode greedily.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --preset smoke --batch 4 --prompt-len 32 --gen 16

Each decode step is one token against a seq_len KV cache, run here at CPU
scale.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import preset_config
from repro.models import build_model, make_batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "30m", "100m", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()

    cfg = preset_config(get_config(args.arch), args.preset)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, args.batch, args.prompt_len,
                       jax.random.PRNGKey(7))

    t0 = time.time()
    logits, cache = jax.jit(api.prefill)(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    print(f"prefill: batch={args.batch} len={args.prompt_len} "
          f"in {t_prefill*1e3:.1f} ms")

    decode = jax.jit(api.decode)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    generated = [tok]
    t0 = time.time()
    for _ in range(args.gen):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        generated.append(tok)
    tok.block_until_ready()
    dt = time.time() - t0
    out = jnp.concatenate(generated, axis=1)
    print(f"decode: {args.gen} steps x batch {args.batch} in {dt*1e3:.1f} ms "
          f"({args.gen * args.batch / dt:.1f} tok/s)")
    for b in range(min(2, args.batch)):
        print(f"  request {b}: {out[b].tolist()}")


if __name__ == "__main__":
    main()
