"""Readings that the limits of ``correct`` are set from, for one
configuration, in one process.

    python chipbench/calibrate.py --config whisper_base --seeds 12 --faults 3

For each seed: the program's first three steps (the compiled step and feed
the cells time, in a plain loop) against the plain reference.  For the
first ``--faults`` seeds also the control, the reference in float8 in the
program's place, and each fault a training cell can have: half of the
batch left out (planted in the reference), and a step that returns its
state unchanged (planted in the program).  Prints one JSON object: every
reading, the lower reading of each number (the largest over the program's
seeds) and the upper candidates (the smallest over the control's and each
fault's seeds).  Needs a chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run as R  # noqa: E402

from chipbench.reflib import NUMBERS  # noqa: E402


def program_readings(tr, seed: int, step_fn=None) -> dict:
    from chipbench import traffic
    tr.state = traffic.make_state(seed, tr.shapes, tr.cfg.get("init"))
    tr.feed = traffic.make_feed(seed, tr.cfg["job"], tr.program_cfg.vocab_size,
                                tr.program_cfg.d_model, tr.program_cfg.dtype)
    step = tr.step
    if step_fn is not None:
        tr.step = step_fn(step)
    try:
        _, readings = R.first_steps(tr, {"first_steps": 3}, None)
    finally:
        tr.step = step
    return readings


def unchanged(step):
    """A step that returns its state unchanged."""
    def broken(state, batch):
        return state, step(state, batch)[1]
    return broken


def calibrate(cfg: dict, seeds: list[int], n_faults: int) -> dict:
    from chipbench import reflib
    tr = R.build_trainer(cfg, seeds[0])
    half = cfg["job"]["batch"] // 2
    rows = {"program": [], "control": [], "half_batch": [], "unchanged": []}
    for i, seed in enumerate(seeds):
        prog = program_readings(tr, seed)
        ref = R.reference_readings(tr, seed)
        rows["program"].append(dict(seed=seed, **reflib.compare(prog, ref)))
        if i >= n_faults:
            continue
        ctl = R.reference_readings(tr, seed, mm="fp8")
        rows["control"].append(dict(seed=seed, **reflib.compare(ctl, ref)))
        hb = R.reference_readings(tr, seed, rows=half)
        rows["half_batch"].append(dict(seed=seed, **reflib.compare(hb, ref)))
        un = program_readings(tr, seed, unchanged)
        rows["unchanged"].append(dict(seed=seed, **reflib.compare(un, ref)))
        print(json.dumps({"seed": seed, **{k: v[-1] for k, v in rows.items()}}),
              file=sys.stderr, flush=True)
    summary = {"lower": {n: max(r[n] for r in rows["program"])
                         for n in NUMBERS}}
    for kind in ("control", "half_batch", "unchanged"):
        if rows[kind]:
            summary[kind] = {n: min(r[n] for r in rows[kind])
                             for n in NUMBERS}
    return {"config": cfg["name"], "readings": rows, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = R.load_json(R.CHECKOUT / "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    cfg = R.load_config(entry)
    R.require_chips(1)
    R.use_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = calibrate(cfg, seeds, args.faults)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
