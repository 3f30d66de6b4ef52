"""Run one benchmark cell once, on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``chipbench/configs/
<name>.json``, its plain reference beside it as ``<name>.py``) and a traffic
mix (``chipbench/mixes/<name>.json``).  Per-layer metrics are readers in
``chipbench/layer_metrics/<metric>.py``.  All are found by name.

The system under test is the program's fault-tolerant training loop:
``make_train_step`` compiled once, driven by ``train_with_recovery`` with a
``TrainWAL``, or restored by ``resume_from_crash``.  Weights, optimizer
state and batches come from ``--seed`` (``traffic.py``).

- Set-up: weights, compile (the persistent cache makes later runs load),
  the first, insert-only save, and the mix's first steps, through the
  window's own calls.  ``setup_s`` is process start to window start.
- Window, ``periods``: whole periods of steps until ``--seconds`` have
  passed; ``train_tokens_per_s`` is every token over all of the window.
  It blocks only on its final state, as the loop itself does.
- Window, ``resume``: ``resume_from_crash`` from one crash image and one
  new step, repeated until ``--seconds`` have passed, at least once;
  ``resume_s`` is their mean.
- ``--trace 1``: one period or one resume under the profiler, every call
  the harness makes or passes in wrapped in a ``TraceAnnotation``; prints
  the per-layer metrics and a breakdown instead.
- Then ``correct``: the first steps against the plain reference
  (``reflib.compare``), the store read back against the state of the last
  save, a resume against the state before the crash.  Each number is
  printed beside its limit, last on standard error and last in the result.

The last line of standard output is one JSON object.  Without an
accelerator, or with fewer chips than the cell asks for, it exits 2 and
prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import ctypes            # noqa: E402
import dataclasses       # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import resource          # noqa: E402
import struct            # noqa: E402
import sys               # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
HERE = CHECKOUT / "chipbench"
CACHE_DIR = CHECKOUT / ".jax_cache"
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

ANNOTATIONS = ("train_step", "wal.heartbeat", "wal.save", "wal.checkpoint",
               "restore", "replay")
STEP_PROGRAM = "jit_train_step"


class NoChip(SystemExit):
    def __init__(self, why: str):
        print(f"chipbench: {why}", file=sys.stderr)
        super().__init__(2)


# ------------------------------------------------------------- finding
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(entry: dict) -> dict:
    """A configuration's file, with the path of its plain reference: the
    ``.py`` beside it, or the one its ``reference`` key names."""
    cfg = load_json(CHECKOUT / entry["file"])
    ref = cfg.get("reference") or str(Path(entry["file"]).with_suffix(".py"))
    cfg["_reference"] = str(CHECKOUT / ref)
    return cfg


def find_cell(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, mix file) for ``workload``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg = load_config({c["name"]: c for c in bench["configs"]}[cell["config"]])
    mix = load_json(HERE / "mixes" / f"{cell['traffic']}.json")
    return cell, cfg, mix


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list[dict]:
    """The metrics this cell prints: its end-to-end ones untraced, its
    per-layer ones traced."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            or ("workloads" not in m and m["moves"] in names)]


def read_layer_metric(name: str, run) -> float | None:
    mod = load_module(HERE / "layer_metrics" / f"{name}.py",
                      f"chipbench_metric_{name.replace('.', '_')}")
    return mod.read(run)


# --------------------------------------------------------------- chips
def require_chips(n: int):
    import jax
    from chipbench.peaks import peaks
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("needs an accelerator; JAX found only the CPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs[:n], peaks(devs[0].device_kind)


def use_cache() -> None:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache``,
    whatever the environment names: the run keeps its caches inside its
    checkout, and the program takes the directory JAX is given."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------------- program
@dataclasses.dataclass
class Trainer:
    """The program's compiled step with its state, made from the seed."""
    cfg: dict
    program_cfg: object
    shapes: object
    state: object
    step: object
    feed: object
    tokens_per_step: int


def build_trainer(cfg: dict, seed: int) -> Trainer:
    import jax
    from repro.configs import get_config
    from repro.launch.train import make_train_step
    from repro.models import build_model
    from repro.optim import AdamWConfig

    from chipbench import traffic
    prog = cfg["program"]
    pc = dataclasses.replace(get_config(prog["arch"]),
                             **{f: cfg[k] for f, k in prog["fields"].items()})
    job = cfg["job"]
    if pc.dtype != job["dtype"]:
        raise ValueError(f"program dtype {pc.dtype} != job {job['dtype']}")
    api = build_model(pc)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    state = traffic.make_state(seed, shapes, cfg.get("init"))
    hp = dict(cfg["optimizer"], betas=tuple(cfg["optimizer"]["betas"]))
    feed = traffic.make_feed(seed, job, pc.vocab_size, pc.d_model, pc.dtype)
    step = make_train_step(api, AdamWConfig(**hp))
    compiled = step.lower(state, feed(0)).compile()
    return Trainer(cfg, pc, shapes, state, compiled, feed,
                   job["batch"] * job["seq"])


class Spans:
    """Host spans around the calls the harness makes or passes in; on the
    profiler's clock too when tracing.  Off, ``wrap`` returns the call
    itself: the untraced window runs the bare loop."""

    def __init__(self, on: bool):
        self.on = on
        self.t: dict[str, list[float]] = defaultdict(list)

    def wrap(self, name, fn, keep=None):
        if not self.on:
            return fn
        import jax

        def timed(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                t0 = time.perf_counter()
                out = fn(*a, **k)
                dt = time.perf_counter() - t0
            if keep is None or keep(out):
                self.t[name].append(dt)
            return out
        return timed

    def wrap_wal(self, wal) -> None:
        if not self.on:
            return
        wal.log_state = self.wrap("wal.save", wal.log_state)
        wal.log_step_meta = self.wrap("wal.heartbeat", wal.log_step_meta)
        wal.maybe_checkpoint = self.wrap("wal.checkpoint",
                                         wal.maybe_checkpoint, keep=bool)


def new_wal(mix: dict, state):
    from repro.state_store import TrainWAL, WALConfig, n_state_records
    cfg = WALConfig(chunk_interval=mix["save_every"],
                    ckpt_interval=mix["checkpoint_every"],
                    bg_flush_pages=mix["bg_flush_pages"])
    cfg.cache_pages = mix["pool_pages_per_record"] * n_state_records(
        state, cfg.chunk_elems)
    return TrainWAL(cfg), cfg


def first_steps(tr: Trainer, mix: dict, wal):
    """Set-up: the insert-only save, then the mix's first steps through the
    window's own call and feed.  Returns the state and the program's
    readings of steps 1-3."""
    import jax
    import numpy as np

    from chipbench.reflib import diff_norms
    b1 = tr.cfg["optimizer"]["betas"][0]
    losses, grad1 = [], []

    def on_step(step, state, metrics):
        losses.append(metrics["loss"])
        if step == 0:       # the gradient as the optimizer got it
            grad1.extend(x / (1 - b1) for x in
                         jax.tree.leaves(state["opt"]["m"]))

    state0 = tr.state
    tr.state = None
    if wal is not None:
        wal.log_state(0, 0, state0)
    state = loop(tr, wal, state0, 0, 3, on_step)
    update = jax.jit(diff_norms)(state["opt"]["master"],
                                 state0["opt"]["master"])
    del state0
    state = loop(tr, wal, state, 3, mix["first_steps"], on_step)
    g1 = [np.asarray(x) for x in grad1]
    readings = {"loss": np.asarray(losses[:3], np.float64),
                "grad1": np.array([np.linalg.norm(x) for x in g1]),
                "grad1_leaves": g1,
                "update": np.asarray(update, np.float64)}
    return state, readings


def loop(tr: Trainer, wal, state, start: int, end: int, on_step,
         step_fn=None):
    """Steps ``[start, end)``: the program's loop with a ``TrainWAL``, or a
    plain loop without one."""
    from repro.state_store import train_with_recovery
    step_fn = step_fn or tr.step
    if wal is not None:
        return train_with_recovery(train_step=step_fn, init_state=state,
                                   batch_at=tr.feed, n_steps=end, wal=wal,
                                   start_step=start, on_step=on_step)
    for s in range(start, end):
        state, metrics = step_fn(state, tr.feed(s))
        on_step(s, state, metrics)
    return state


# ---------------------------------------------------------------- runs
class Run:
    """What a per-layer reader reads."""

    def __init__(self, mix, spans, trace, recovery, flops, peak):
        self.mix = mix
        self.spans = dict(spans.t)
        self.trace = trace
        self.recovery = recovery
        self.flops_per_step = flops
        self.peak = peak
        self.step_program = STEP_PROGRAM


def start_trace(out: Path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)


def run_periods(tr, mix, seconds, trace_dir, spans, count):
    """The ``periods`` window.  Returns (final state, wal, window seconds,
    tokens, losses of the window)."""
    import jax
    wal = new_wal(mix, tr.state)[0] if mix["log"] else None
    state, count["readings"] = first_steps(tr, mix, wal)
    if wal is not None:
        spans.wrap_wal(wal)
    jax.block_until_ready(state)
    count["setup_s"] = time.perf_counter() - T_START
    note("set-up done")
    losses = []

    def on_step(step, st, metrics):
        losses.append(metrics["loss"])

    step_fn = spans.wrap("train_step", tr.step)
    step, period = mix["first_steps"], mix["period_steps"]
    if trace_dir is not None:
        start_trace(trace_dir)
    t0 = time.perf_counter()
    ends = []
    with annotate(trace_dir, "window"):
        while True:
            state = loop(tr, wal, state, step, step + period, on_step, step_fn)
            if wal is None:
                # no save waits on the steps: without this the host would
                # queue steps for the whole window before the device ran
                jax.block_until_ready(state)
            step += period
            ends.append(time.perf_counter() - t0)
            if trace_dir is not None or ends[-1] >= seconds:
                break
        jax.block_until_ready(state)
    window = time.perf_counter() - t0
    # host time of each period's loop (its last save blocks on its steps)
    count["period_s"] = [b - a for a, b in zip([0.0] + ends, ends)]
    if trace_dir is not None:
        jax.profiler.stop_trace()
    count["steps"] = step - mix["first_steps"]
    count["final_step"] = step
    return state, wal, window, count["steps"] * tr.tokens_per_step, losses


def annotate(trace_dir, name):
    import contextlib

    import jax
    if trace_dir is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


def run_resume(tr, mix, seconds, trace_dir, spans, count):
    """The ``resume`` window.  Returns (per-resume seconds, recovery stats of
    the last, number of resumes whose state differed, losses)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.state_store import resume_from_crash

    wal, wal_cfg = new_wal(mix, tr.state)
    template = jax.eval_shape(lambda: tr.state)
    state, count["readings"] = first_steps(tr, mix, wal)
    state = loop(tr, wal, state, mix["first_steps"], mix["crash_after"],
                 lambda *a: None)
    image = wal.crash()
    del wal
    before = jax.block_until_ready(state)
    del state
    # the leaves come back through eager reshapes; compile them here
    jax.block_until_ready([jnp.zeros(x.size, x.dtype).reshape(x.shape)
                           for x in jax.tree.leaves(template)])
    neq_fn = jax.jit(lambda a, b: sum(
        jnp.any(x != y).astype(jnp.int32)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))
    jax.block_until_ready(neq_fn(before, before))
    gc.collect()
    count["setup_s"] = time.perf_counter() - T_START
    note("set-up done")

    replay_fn = spans.wrap("replay", tr.step)
    step_fn = spans.wrap("train_step", tr.step)
    resume = spans.wrap("restore", resume_from_crash)
    times, neq, steps_ok, losses, stats = [], [], [], [], None
    if trace_dir is not None:
        start_trace(trace_dir)
    t_window = time.perf_counter()
    while True:
        with annotate(trace_dir, "window"):
            t0 = time.perf_counter()
            wal2, restored, step, stats = resume(
                image, template, train_step=replay_fn, batch_at=tr.feed,
                wal_cfg=wal_cfg)
            spans.wrap_wal(wal2)
            new = loop(tr, wal2, restored, step, step + 1,
                       lambda s, st, m: losses.append(m["loss"]), step_fn)
            jax.block_until_ready(new)
            times.append(time.perf_counter() - t0)
        count["resume_each_s"] = times
        neq.append(neq_fn(restored, before))
        steps_ok.append(step == mix["crash_after"])
        del wal2, restored, new
        gc.collect()
        if trace_dir is not None or time.perf_counter() - t_window >= seconds:
            break
    if trace_dir is not None:
        jax.profiler.stop_trace()
    count["resumes"] = len(times)
    differed = sum(int(n) > 0 or not ok for n, ok in zip(neq, steps_ok))
    recovery = {k: getattr(stats, k) for k in
                ("analysis_ms", "redo_wall_ms", "total_wall_ms")}
    return times, recovery, differed, losses


def store_mismatches(wal, state, step: int) -> int:
    """Leaves of ``state`` that the store does not give back bit for bit,
    plus one if its latest record names another state step."""
    import jax
    import jax.numpy as jnp
    from repro.core.dc import make_key
    from repro.state_store import records_to_tree
    from repro.state_store.train_wal import META_TABLE, STATE_TABLE
    prefix = make_key(STATE_TABLE, b"")
    records = {k[len(prefix):]: v for k, v in wal.db.scan_all()
               if k.startswith(prefix)}
    back = records_to_tree(jax.eval_shape(lambda: state), records,
                           wal.cfg.chunk_elems)
    bad = sum(not bool(jnp.array_equal(a, b)) for a, b in
              zip(jax.tree.leaves(back), jax.tree.leaves(state)))
    meta = wal.db.dc.read(META_TABLE, b"latest")
    state_step = struct.unpack_from("<qqq", meta)[2] if meta else -1
    return bad + int(state_step != step)


def reference_readings(tr: Trainer, seed: int, mm: str = "f32",
                       rows=None) -> dict:
    """The plain reference's first three steps from the same seed."""
    from chipbench import reflib, traffic
    ref = load_module(Path(tr.cfg["_reference"]),
                      f"chipbench_ref_{tr.cfg['name']}")
    params0 = traffic.make_params(seed, tr.shapes, tr.cfg.get("init"))
    return reflib.run_steps(ref.loss, tr.cfg, params0, tr.feed, 3, mm=mm,
                            rows=rows)


def rss_now() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def release_host_memory() -> None:
    """Hand the freed store back to the system before the reference
    compiles and runs: glibc keeps freed blocks mapped otherwise, and the
    store's log and pages are most of the host's memory."""
    gc.collect()
    ctypes.CDLL("libc.so.6").malloc_trim(0)


def note(what: str) -> None:
    print(f"chipbench: {what}: host RSS {rss_now()} bytes, "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr,
          flush=True)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, chips) -> dict:
    """One run of one cell; returns the result line as a dict.  ``chips``
    is ``(devices, peak row)`` as ``require_chips`` gives them."""
    from chipbench import flops, reflib, trace_reduce
    cell, cfg, mix = find_cell(bench, workload)
    devices, peak = chips
    tr = build_trainer(cfg, seed)
    spans = Spans(trace)
    trace_dir = None
    if trace:
        trace_dir = CHECKOUT / "chipbench" / "out" / f"trace_{workload}_{seed}"
    count: dict = {}
    attempted = failed = 0
    recovery = None
    metrics: dict[str, float] = {}
    if mix["kind"] == "periods":
        state, wal, window, tokens, losses = run_periods(
            tr, mix, seconds, trace_dir, spans, count)
        metrics["train_tokens_per_s"] = tokens / window
        attempted += count["steps"]
        if wal is not None:
            saves = count["steps"] // mix["save_every"]
            attempted += saves
            peak_hbm = memory_peak(devices)
            bad_store = store_mismatches(wal, state, count["final_step"])
            failed += min(saves, bad_store)
        else:
            peak_hbm = memory_peak(devices)
            bad_store = None
        del state, wal
    elif mix["kind"] == "resume":
        times, recovery, differed, losses = run_resume(
            tr, mix, seconds, trace_dir, spans, count)
        metrics["resume_s"] = sum(times) / len(times)
        attempted += 2 * len(times)          # each resume and its new step
        failed += differed
        peak_hbm = memory_peak(devices)
        bad_store = None
    else:
        raise ValueError(f"unknown mix kind {mix['kind']!r}")
    metrics["setup_s"] = count["setup_s"]
    note("window and checks done")
    bad_loss = sum(not math.isfinite(float(x)) for x in losses)
    failed += bad_loss
    tr.state = None
    release_host_memory()
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    print(f"peak HBM {peak_hbm} bytes; peak host RSS {rss} bytes",
          file=sys.stderr)

    ref = reference_readings(tr, seed)
    note("reference done")
    nums = reflib.compare(count["readings"], ref)
    limits = cfg["limits"]
    checks = {k: (nums[k], limits[k]) for k in reflib.NUMBERS}
    checks["nonfinite_losses"] = (bad_loss, 0)
    if bad_store is not None:
        checks["store_mismatches"] = (bad_store, 0)
    if mix["kind"] == "resume":
        checks["resumes_not_exact"] = (differed, 0)
    # a limit of None: a number with no upper reading, shown, not compared
    correct = all(lim is None or v <= lim for v, lim in checks.values())

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak_hbm}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    chosen = cell_metrics(bench, cell, trace)
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(str(trace_dir)),
                                  ANNOTATIONS)
        run = Run(mix, spans, red, recovery,
                  flops.train_step_flops(cfg, cfg["job"]), peak)
        vals = {m["name"]: read_layer_metric(m["name"], run) for m in chosen}
        out["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                      "unit": m["unit"]}
                          for m in chosen if vals[m["name"]] is not None}
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["device"] = dev
        out["breakdown"] = {"device_ops": [list(x) for x in red["device_ops"]],
                            "idle_gaps": [list(x) for x in red["idle_gaps"]]}
    else:
        out["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                      "unit": m["unit"]} for m in chosen}
        out["device"] = dev
    out["counts"] = {k: v for k, v in count.items() if k != "readings"}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    return out


def memory_peak(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    cell, _, _ = find_cell(bench, args.workload)
    chips = require_chips(cell["chips"])
    use_cache()
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), chips=chips)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
