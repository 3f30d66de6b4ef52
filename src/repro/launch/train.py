"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --preset 30m --steps 60 --crash-at 35

Presets scale the assigned architecture's family to CPU-runnable sizes
(--preset full uses the assigned geometry).  The loop is wired to the
logical-recovery state store:
per-step heartbeats, incremental chunk transactions, RSSP checkpoints; with
--crash-at it hard-crashes mid-run and then restores + replays, verifying the
resumed state matches exactly.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import time
from collections import defaultdict

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import TokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.optim import AdamWConfig, apply_updates, init_opt_state
from repro.state_store import (TrainWAL, WALConfig, n_state_records,
                               resume_from_crash, train_with_recovery)


def preset_config(cfg, preset: str):
    if preset == "full":
        return cfg
    if preset == "smoke":
        return cfg.reduced()
    if preset == "30m":
        return dataclasses.replace(
            cfg, name=cfg.name + "-30m", n_layers=6, d_model=384, n_heads=6,
            n_kv_heads=max(1, min(6, cfg.n_kv_heads)), d_ff=1152,
            head_dim=64, vocab_size=16384,
            n_experts=min(cfg.n_experts, 8), top_k=min(cfg.top_k, 2),
            moe_d_ff=192 if cfg.n_experts else 0,
            ssm_state=min(cfg.ssm_state, 32),
            attn_every=3 if cfg.attn_every else 0,
            n_enc_layers=4 if cfg.n_enc_layers else 0, enc_ctx=64,
            n_patches=16 if cfg.n_patches else 0, max_seq=2048)
    if preset == "100m":
        return dataclasses.replace(
            cfg, name=cfg.name + "-100m", n_layers=12, d_model=512,
            n_heads=8, n_kv_heads=max(1, min(8, cfg.n_kv_heads)), d_ff=2048,
            head_dim=64, vocab_size=50_304,
            n_experts=min(cfg.n_experts, 16), top_k=min(cfg.top_k, 4),
            moe_d_ff=512 if cfg.n_experts else 0,
            ssm_state=min(cfg.ssm_state, 64),
            attn_every=4 if cfg.attn_every else 0,
            n_enc_layers=6 if cfg.n_enc_layers else 0, enc_ctx=128,
            n_patches=32 if cfg.n_patches else 0, max_seq=2048)
    raise ValueError(preset)


def make_train_step(api, opt_cfg: AdamWConfig):
    """The jitted step: loss + grads, then the AdamW update."""
    @jax.jit
    def train_step(state, batch):
        loss, grads = jax.value_and_grad(api.loss)(state["params"], batch)
        new_p, new_opt, m = apply_updates(state["params"], grads,
                                          state["opt"], opt_cfg)
        return {"params": new_p, "opt": new_opt}, {"loss": loss, **m}

    return train_step


def build_trainer(cfg, batch: int, seq: int, opt_cfg: AdamWConfig):
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    state0 = {"params": params, "opt": init_opt_state(params)}
    pipe = TokenPipeline(cfg, batch, seq, seed=1234)
    return api, state0, make_train_step(api, opt_cfg), pipe


def run(*, arch: str, preset: str, steps: int, batch: int, seq: int,
        crash_at: int = 0, chunk_interval: int = 10, ckpt_interval: int = 25,
        log_every: int = 10) -> None:
    """Train ``steps`` steps with every step logged to a ``TrainWAL``; with
    ``crash_at`` hard-crash after that step, recover, replay the tail, check
    the restored state bit for bit, and train on to ``steps``.

    Prints the host wall of every phase.  Each step blocks until its outputs
    are ready, so a step's wall is its own.  Raises if the restored state is
    not the pre-crash state or a loss is not finite."""
    cfg = preset_config(get_config(arch), preset)
    opt_cfg = AdamWConfig(lr=3e-4, warmup_steps=10, total_steps=steps)
    api, state0, train_step, pipe = build_trainer(cfg, batch, seq, opt_cfg)
    n_params = sum(x.size for x in jax.tree.leaves(state0["params"]))
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state0))
    print(f"arch={cfg.name} params={n_params} state_bytes={state_bytes} "
          f"batch={batch} seq={seq}")

    t0 = time.perf_counter()
    compiled = train_step.lower(state0, pipe.batch_at(0)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(f"compile: {compile_s:.3f} s host wall; train step argument="
          f"{mem.argument_size_in_bytes} output={mem.output_size_in_bytes} "
          f"temp={mem.temp_size_in_bytes} bytes")

    walls: dict[str, list[float]] = defaultdict(list)
    phase = "train"
    step_end = 0.0

    def step_fn(state, batch):
        nonlocal step_end
        t = time.perf_counter()
        out = jax.block_until_ready(compiled(state, batch))
        step_end = time.perf_counter()
        walls[phase].append(step_end - t)
        return out

    def after_step(step, state, metrics):
        # the loop logged the step (and maybe checkpointed) since step_fn
        kind = "save" if (step + 1) % chunk_interval == 0 else "heartbeat"
        if (step + 1) % ckpt_interval == 0:
            kind += "+checkpoint"
        walls[kind].append(time.perf_counter() - step_end)
        loss = float(metrics["loss"])
        if not math.isfinite(loss):
            raise FloatingPointError(f"step {step + 1}: loss={loss}")

    # every state chunk's page stays resident: a leaf page holds at least
    # one chunk and the index adds far less than a page per chunk
    chunk_elems = WALConfig.chunk_elems
    wal_cfg = WALConfig(chunk_interval=chunk_interval,
                        ckpt_interval=ckpt_interval, bg_flush_pages=32,
                        cache_pages=2 * n_state_records(state0, chunk_elems))
    wal = TrainWAL(wal_cfg)
    t0 = time.perf_counter()
    wal.log_state(0, 0, state0)
    walls["save"].append(time.perf_counter() - t0)
    template = jax.eval_shape(lambda: state0)

    batch_at = pipe.batch_at
    t0 = time.perf_counter()
    end = crash_at if 0 < crash_at < steps else steps
    state = train_with_recovery(train_step=step_fn, init_state=state0,
                                batch_at=batch_at, n_steps=end, wal=wal,
                                log_every=log_every, on_step=after_step)
    del state0                      # device memory for the states below
    if end < steps:
        image = wal.crash()
        print(f"--- CRASH at step {crash_at} "
              f"(log={len(image.log)} recs, stable pages={len(image.store)})")
        phase = "replay"
        t1 = time.perf_counter()
        wal, restored, step, stats = resume_from_crash(
            image, template, train_step=step_fn, batch_at=batch_at,
            wal_cfg=wal_cfg)
        recover_s = time.perf_counter() - t1 - sum(walls["replay"])
        print(f"--- RECOVERED to step {step} in {recover_s:.3f} s host wall "
              f"(redo: {stats.redo.submitted} ops submitted, "
              f"{stats.redo.redone} redone, {stats.redo.skipped_dpt} DPT-"
              f"pruned, {stats.io.sync_reads} page fetches, "
              f"DPT={stats.dpt_size})")
        if step != crash_at:
            raise RuntimeError(f"recovered to step {step}, crashed at "
                               f"{crash_at}")
        leaves = zip(jax.tree.leaves(restored), jax.tree.leaves(state))
        if not all(bool(jnp.array_equal(a, b)) for a, b in leaves):
            raise RuntimeError("restored state diverged from the pre-crash "
                               "state")
        print("--- restored state == pre-crash state (bit-exact)")
        del state
        phase = "train"
        train_with_recovery(train_step=step_fn, init_state=restored,
                            batch_at=batch_at, n_steps=steps, wal=wal,
                            start_step=step, log_every=log_every,
                            on_step=after_step)
    dt = time.perf_counter() - t0
    print(f"done: {steps} steps in {dt:.1f}s host wall")
    for kind, ws in walls.items():
        print(f"host wall {kind}: " + " ".join(f"{w:.3f}" for w in ws) + " s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--preset", default="30m",
                    choices=["smoke", "30m", "100m", "full"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--crash-at", type=int, default=0,
                    help="crash after this step, then restore + verify")
    ap.add_argument("--chunk-interval", type=int, default=10)
    ap.add_argument("--ckpt-interval", type=int, default=25)
    args = ap.parse_args()
    use_compile_cache()
    run(arch=args.arch, preset=args.preset, steps=args.steps,
        batch=args.batch, seq=args.seq, crash_at=args.crash_at,
        chunk_interval=args.chunk_interval, ckpt_interval=args.ckpt_interval)


if __name__ == "__main__":
    main()
