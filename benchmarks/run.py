"""Benchmark entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV lines per benchmark row, writes
full JSON to artifacts/bench/, and appends one machine-readable
``artifacts/bench_<n>.json`` summary per run (monotonic ``n``) so the
perf trajectory across commits is diffable without parsing stdout.
Per-module metric snapshots land in ``artifacts/bench/
metrics_timeseries.jsonl`` and the final registry state in Prometheus
text form at ``artifacts/bench/metrics.prom``.
--full uses the paper-scaled setup (slower); the default "fast" mode
keeps the whole suite under ~3 minutes.

Failure discipline: each module runs to completion independently (one
broken table must not hide the others' numbers), but any failure — an
oracle assertion inside a sub-benchmark most importantly — makes the
runner exit non-zero, so CI cannot greenlight a diverging benchmark.
"""
from __future__ import annotations

import json
import re
import sys
import time
import traceback
from pathlib import Path

ART_ROOT = Path(__file__).resolve().parents[1] / "artifacts"
ART = ART_ROOT / "bench"


def _next_run_index() -> int:
    """Next monotonic run index.  A truncated/corrupt bench_<n>.json still
    claims its index (so we never overwrite evidence of the torn write)
    but is warned about loudly — bench-diff will skip it, and a silent
    skip here would leave the perf trajectory with an unexplained hole."""
    mx = 0
    for p in ART_ROOT.glob("bench_*.json"):
        m = re.fullmatch(r"bench_(\d+)\.json", p.name)
        if not m:
            continue
        mx = max(mx, int(m.group(1)))
        try:
            json.loads(p.read_text())
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as exc:
            print(f"bench: WARNING: existing artifact {p.name} is "
                  f"unreadable ({type(exc).__name__}: {exc}) — keeping "
                  "its run index, bench-diff will not use it as a "
                  "baseline", file=sys.stderr)
    return mx + 1


def write_summary(results: list[dict], failures: list[str],
                  fast: bool) -> Path:
    """One flat, machine-readable record of this run: every row's key
    metrics plus per-module status — the perf-trajectory unit.  The
    process-wide metrics snapshot rides along so each artifact carries the
    telemetry (cache hit rates, windows, shipped records, ...) that
    explains its numbers."""
    from repro.obs import metrics as obs_metrics
    summary = {
        "run": _next_run_index(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "mode": "fast" if fast else "full",
        "modules": [
            {"name": out["name"], "rows": len(out["rows"]), "ok": True}
            for out in results
        ] + [{"name": name, "rows": 0, "ok": False} for name in failures],
        "failures": failures,
        "rows": [
            {"module": out["name"], **{
                key: row[key] for key in
                ("name", "us_per_call", "derived", "speedup",
                 "speedup_vs_log1", "ratio", "recs_per_s",
                 "bytes_per_record", "p50", "p95", "p99",
                 "window_p50", "window_p95", "window_p99",
                 "flight_frac")
                if key in row}}
            for out in results for row in out["rows"]
        ],
        "metrics": obs_metrics.snapshot(),
    }
    path = ART_ROOT / f"bench_{summary['run']}.json"
    path.write_text(json.dumps(summary, indent=1))
    return path


def main() -> None:
    fast = "--full" not in sys.argv
    from . import (appendix_d_variants, archive_bench, faults_bench,
                   fig2_cache_sweep, fig3_ckpt_interval, media_bench,
                   pagepack_bench, parallel_apply_bench, recovery_bench,
                   replication_bench, trainstore_bench)
    from repro.obs.export import Sampler, prometheus_text
    ART.mkdir(parents=True, exist_ok=True)
    failures: list[str] = []
    results: list[dict] = []
    # per-module metric snapshots: one JSONL row after each module, so a
    # regression shows *which table* moved a counter, not just that the
    # end-of-run total moved
    sampler = Sampler(ART / "metrics_timeseries.jsonl", period_ms=0.0)
    print("name,us_per_call,derived")
    for mod in (fig2_cache_sweep, fig3_ckpt_interval, appendix_d_variants,
                recovery_bench, pagepack_bench, replication_bench,
                parallel_apply_bench, archive_bench, media_bench,
                faults_bench, trainstore_bench):
        try:
            out = mod.run(fast=fast)
        except Exception:
            failures.append(mod.__name__)
            print(f"# FAILED {mod.__name__}:", file=sys.stderr)
            traceback.print_exc()
            sampler.tick(force=True, note=f"{mod.__name__} FAILED")
            continue
        results.append(out)
        sampler.tick(force=True, note=out["name"])
        (ART / f"{out['name']}.json").write_text(json.dumps(out, indent=1))
        for row in out["rows"]:
            if "us_per_call" in row:
                print(f"{row['name']},{row['us_per_call']:.1f},"
                      f"\"{row.get('derived','')}\"")
            elif "strategy" in row:
                label = out["name"]
                key = row.get("cache_pages") or row.get(
                    "ckpt_interval_updates") or ""
                us = row.get("modeled_ms", 0.0) * 1e3
                derived = (f"dpt={row.get('dpt_size','')} "
                           f"fetch={row.get('fetches','')} "
                           f"ok={row.get('correct','')}")
                print(f"{label}/{row['strategy']}@{key},{us:.0f},\"{derived}\"")
            elif "touched_frac" in row:
                print(f"trainstore/touch={row['touched_frac']},"
                      f"{row['log1_modeled_ms']*1e3:.0f},"
                      f"\"log0={row['log0_modeled_ms']}ms "
                      f"speedup={row['speedup_log1_vs_log0']}x "
                      f"dpt={row['log1_dpt']}\"")
            elif "delta_mode" in row:
                print(f"appendix_d/{row['delta_mode']},"
                      f"{row['log1_modeled_ms']*1e3:.0f},"
                      f"\"dpt={row['log1_dpt']} "
                      f"payload={row['delta_payload_bytes']}B\"")
            else:
                print(f"{out['name']}/{row.get('arch','')}__"
                      f"{row.get('shape','')},"
                      f"{row.get('compute_s', 0)*1e6:.0f},"
                      f"\"dom={row.get('dominant','')}\"")
    sampler.close()
    (ART / "metrics.prom").write_text(prometheus_text())
    summary_path = write_summary(results, failures, fast)
    print(f"# full JSON written to artifacts/bench/; run summary at "
          f"{summary_path.relative_to(ART_ROOT.parent)}", file=sys.stderr)
    if failures:
        print(f"# {len(failures)} benchmark module(s) FAILED: "
              f"{', '.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
