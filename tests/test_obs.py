"""Observability layer: registry/tracer units, traced-recovery acceptance,
Log2 pacing parity, decode-cache counters, shard gauges, bench-diff gate."""
import dataclasses
import json
import random
import sys
from pathlib import Path

import pytest

from repro import obs
from repro.archive import Archiver, LogArchive, SnapshotStore
from repro.core import (Database, RecoveryStats, Strategy,
                        committed_state_oracle, make_key, recover,
                        recovered_state)
from repro.core.storage import issue_schedule, prefetch_overlap
from repro.replication import LogShipper, ShardedApplier

import repl_workload

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks import diff as bench_diff  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off and a clear trace;
    metrics are reset per-prefix inside tests that assert on them (the
    registry is process-wide by design)."""
    obs.disable()
    obs.TRACER.clear()
    yield
    obs.disable()
    obs.TRACER.clear()


# ------------------------------------------------------------------ registry
def test_registry_counters_gauges_histograms():
    obs.REGISTRY.reset("test_reg")
    c = obs.counter("test_reg.hits")
    c.inc()
    c.inc(4)
    assert obs.value("test_reg.hits") == 5
    g = obs.gauge("test_reg.depth")
    g.set(7)
    g.inc(-2)
    assert obs.value("test_reg.depth") == 5
    h = obs.histogram("test_reg.window")
    for v in (10, 20, 30):
        h.observe(v)
    s = obs.value("test_reg.window")
    assert s == {"count": 3, "sum": 60.0, "min": 10, "max": 30, "avg": 20.0,
                 "p50": 20, "p95": 30, "p99": 30}
    # untouched metrics read as 0, and re-requesting returns the same object
    assert obs.value("test_reg.never") == 0
    assert obs.counter("test_reg.hits") is c


def test_registry_labels_flatten_sorted_and_isolate():
    obs.REGISTRY.reset("test_lbl")
    obs.gauge("test_lbl.lag", shard=1, replica="r1").set(10)
    obs.gauge("test_lbl.lag", replica="r1", shard=2).set(20)
    snap = obs.snapshot("test_lbl")
    # labels sort alphabetically regardless of kwargs order
    assert snap == {"test_lbl.lag{replica=r1,shard=1}": 10,
                    "test_lbl.lag{replica=r1,shard=2}": 20}
    assert obs.value("test_lbl.lag", shard=1, replica="r1") == 10


def test_registry_reset_zeroes_in_place():
    """Call sites cache Counter references at import; reset must zero the
    object, never replace it."""
    obs.REGISTRY.reset("test_rst")
    c = obs.counter("test_rst.n")
    c.inc(9)
    obs.REGISTRY.reset("test_rst")
    assert obs.value("test_rst.n") == 0
    c.inc()                      # the cached reference still feeds the key
    assert obs.value("test_rst.n") == 1
    assert obs.counter("test_rst.n") is c


def test_registry_kind_conflict_is_loud():
    obs.REGISTRY.reset("test_kind")
    obs.counter("test_kind.x")
    with pytest.raises(TypeError, match="already registered"):
        obs.gauge("test_kind.x")


def test_publish_and_load_dataclass_round_trip():
    obs.REGISTRY.reset("recovery")
    st = RecoveryStats(strategy="Log1", log_records=123, batched=True,
                       redo_wall_ms=4.5)
    st.redo.redone = 77
    st.io.sync_reads = 9
    st.publish()
    assert obs.value("recovery.log_records") == 123
    assert obs.value("recovery.batched") == 1
    assert obs.value("recovery.redo.redone") == 77
    assert obs.value("recovery.io.sync_reads") == 9
    view = RecoveryStats.from_registry()
    assert view.log_records == 123 and view.batched is True
    assert view.redo_wall_ms == 4.5
    assert view.redo.redone == 77 and view.io.sync_reads == 9
    assert view.strategy == ""          # non-numeric fields stay default


# -------------------------------------------------------------------- tracer
def test_tracer_disabled_is_silent_and_shared():
    sp1 = obs.TRACER.span("a", k=1)
    sp2 = obs.TRACER.span("b")
    assert sp1 is sp2                   # the shared null span
    with sp1 as s:
        s.set(more=2)
    obs.TRACER.event("never")
    assert obs.TRACER.events == []


def test_tracer_nesting_events_and_jsonl(tmp_path):
    obs.enable()
    with obs.span("outer", tag="t") as o:
        with obs.span("inner"):
            obs.event("leaf", n=3)
        o.set(late=1)
    obs.disable()
    ev = obs.TRACER.events
    kinds = [(e["type"], e["name"]) for e in ev]
    assert kinds == [("begin", "outer"), ("begin", "inner"),
                     ("event", "leaf"), ("end", "inner"), ("end", "outer")]
    outer_id = ev[0]["span"]
    inner_id = ev[1]["span"]
    assert ev[0]["parent"] == 0 and ev[1]["parent"] == outer_id
    assert ev[2]["parent"] == inner_id
    assert ev[4]["attrs"] == {"tag": "t", "late": 1}    # set() rides the end
    assert ev[0]["wall"] > 0 and ev[3]["dur_ms"] >= 0
    path = obs.trace.export_jsonl(tmp_path / "t.jsonl")
    assert obs.load_jsonl(path) == ev
    obs.TRACER.clear()
    assert obs.TRACER.events == [] and obs.TRACER._stack == []


def test_timeline_renders_tree_and_aggregates():
    obs.enable()
    with obs.span("recover"):
        with obs.span("redo"):
            for _ in range(5):
                obs.event("io.demand", stall_ms=2.0)
    obs.disable()
    out = obs.render_timeline()
    assert "recover" in out and "└─ redo" in out
    assert "5x io.demand" in out and "stall_ms=10.0" in out


# ------------------------------------------------- traced recovery acceptance
def _crash_image(n_rows=4000, n_txns=250, seed=11):
    rng = random.Random(seed)
    db = Database(cache_pages=512, tracker_interval=50, bg_flush_per_txn=2)
    rows = [(f"k{i:06d}".encode(), rng.randbytes(40)) for i in range(n_rows)]
    db.load_table("t", rows)
    base = {make_key("t", k): v for k, v in rows}

    def drive(n):
        for _ in range(n):
            db.run_txn([("update", "t",
                         f"k{rng.randrange(n_rows):06d}".encode(),
                         rng.randbytes(40)) for _ in range(6)])

    drive(n_txns // 2)
    db.checkpoint()
    drive(n_txns // 2)
    return db.crash(), base


def test_traced_batched_recovery_timeline_and_registry_view():
    """The PR's acceptance run: one traced recover(batched=True) produces
    phase spans whose walls match the stats, window spans that sum to
    log_records, and a registry view consistent with RecoveryStats."""
    image, base = _crash_image()
    oracle = committed_state_oracle(image, base)
    obs.REGISTRY.reset("recovery")
    obs.enable()
    db, stats = recover(image, Strategy.LOG1, batched=True,
                        batch_window=256)
    obs.disable()
    assert recovered_state(db) == oracle

    ev = obs.TRACER.events
    # Python's collections are spans too, wherever they fall
    roots = [r for r in obs.build_tree(ev) if r.name != "gc"]
    assert [r.name for r in roots] == ["recover"]
    phases = [c.name for c in roots[0].children if c.name != "gc"]
    assert phases == ["analysis", "redo", "undo", "checkpoint"]
    redo, = [c for c in roots[0].children if c.name == "redo"]
    windows = [c for c in redo.children if c.name == "redo.window"]
    assert len(windows) == stats.windows >= 2
    assert sum(w.attrs["records"] for w in windows) == stats.log_records
    # span walls and stats timers measure the same regions
    analysis, = [c for c in roots[0].children if c.name == "analysis"]
    assert analysis.attrs["analysis_ms"] == round(stats.analysis_ms, 3)
    assert redo.attrs["redo_wall_ms"] == round(stats.redo_wall_ms, 3)
    assert abs(redo.dur_ms - stats.redo_wall_ms) < 5.0

    # the legacy dataclass is a view over the registry
    view = RecoveryStats.from_registry()
    for f in dataclasses.fields(RecoveryStats):
        got, want = getattr(view, f.name), getattr(stats, f.name)
        if isinstance(want, (bool, int, float)):
            assert got == want, f"registry view diverged on {f.name}"
    assert view.redo == stats.redo and view.io == stats.io

    out = obs.render_timeline(snapshot=obs.snapshot())
    for needle in ("recover", "analysis", "redo.window", "undo",
                   "checkpoint", "cache: pagestore decode cache"):
        assert needle in out, f"timeline missing {needle!r}"


# ------------------------------------------------------- Log2 pacing parity
def test_log2_batched_pacing_matches_per_record_schedule():
    """The iosim fix: batched Log2 must issue the PF-list on the exact
    per-record schedule (same pid groups, same order), with issues spread
    across the window's work — not collapsed onto the window start, which
    was the window-granular bug that overstated prefetch overlap."""
    image, base = _crash_image(seed=13)
    oracle = committed_state_oracle(image, base)

    def traced(**kw):
        obs.TRACER.clear()
        # small lookahead so the pacer actually gates issues at this scale
        # (the default would swallow the whole small pf_list in one burst)
        db, st = recover(image, Strategy.LOG2, lookahead=16, **kw)
        assert recovered_state(db) == oracle
        return list(obs.TRACER.events)

    obs.enable()
    ev_per = traced()
    ev_bat = traced(batched=True, batch_window=256)
    obs.disable()

    sched_per, sched_bat = issue_schedule(ev_per), issue_schedule(ev_bat)
    assert sched_per, "Log2 issued no PF-list prefetches"
    assert sched_bat == sched_per

    # batched issues spread across work positions (distinct modeled
    # clocks), except the initial lookahead burst
    clocks = [e["attrs"]["clock"] for e in ev_bat
              if e.get("name") == "io.prefetch.issue"]
    assert len(set(clocks)) > len(clocks) // 2

    ov_per, ov_bat = prefetch_overlap(ev_per), prefetch_overlap(ev_bat)
    assert ov_per["issued"] == ov_bat["issued"]
    assert ov_per["consumed"] > 0 and ov_bat["consumed"] > 0
    # batched demand reads land at the window end, after more work has
    # overlapped — so prefetching must absorb (fully or partially) at
    # least as many demands, and never pay more cold random reads.  A
    # single hit-vs-partial flip is modeled-clock luck (page-layout
    # changes move split points and hence prefetch run grouping), so the
    # full-hit fraction is not asserted ordinal on its own.
    assert (ov_bat["hits"] + ov_bat["partials"]
            >= ov_per["hits"] + ov_per["partials"])
    assert ov_bat["syncs"] <= ov_per["syncs"]


# ------------------------------------------------------ decode-cache counters
def test_pagestore_decode_cache_cold_then_warm_via_registry():
    image, base = _crash_image(n_rows=1500, n_txns=80, seed=17)
    obs.REGISTRY.reset("pagestore")
    recover(image, Strategy.LOG1)
    cold = obs.snapshot("pagestore")
    assert cold["pagestore.decode_misses"] > 0
    # same image again: the content-keyed cache is shared across clones,
    # so the second recovery decodes (almost) nothing new
    recover(image, Strategy.LOG1)
    warm = obs.snapshot("pagestore")
    new_hits = warm["pagestore.decode_hits"] - cold["pagestore.decode_hits"]
    new_misses = (warm["pagestore.decode_misses"]
                  - cold["pagestore.decode_misses"])
    assert new_hits > new_misses
    assert new_hits >= cold["pagestore.decode_misses"] // 2
    # reset path: keys zero but the cached module counters keep feeding
    obs.REGISTRY.reset("pagestore")
    assert obs.snapshot("pagestore")["pagestore.decode_hits"] == 0
    recover(image, Strategy.LOG1)
    assert obs.value("pagestore.decode_hits") > 0


def test_archive_lru_cold_then_warm_via_registry():
    rng = random.Random(23)
    db = Database(cache_pages=256, tracker_interval=50, bg_flush_per_txn=2)
    rows = [(f"k{i:05d}".encode(), rng.randbytes(40)) for i in range(800)]
    db.load_table("t", rows)
    base = {make_key("t", k): v for k, v in rows}
    arch = Archiver(db, archive=LogArchive(segment_records=256,
                                           cache_segments=64),
                    snapshots=SnapshotStore())

    def drive(n):
        for _ in range(n):
            db.run_txn([("update", "t",
                         f"k{rng.randrange(800):05d}".encode(),
                         rng.randbytes(40)) for _ in range(5)])

    drive(60)
    arch.snapshots.take(db)
    drive(60)
    arch.run_once()
    store = arch.snapshots          # Archiver attached the archive to it
    target = arch.archive.archived_upto
    oracle = committed_state_oracle(db.crash(), base, upto_lsn=target)

    obs.REGISTRY.reset("archive")
    db1, _ = store.restore(target)               # cold: decodes segments
    assert dict(db1.scan_all()) == oracle
    cold = obs.snapshot("archive")
    assert cold["archive.segment_decodes"] > 0
    db2, _ = store.restore(target)               # warm: served by the LRU
    assert dict(db2.scan_all()) == oracle
    warm = obs.snapshot("archive")
    assert warm["archive.segment_decodes"] == cold["archive.segment_decodes"]
    assert warm["archive.cache_hits"] > cold["archive.cache_hits"]
    # counter reset leaves the instance tallies (the per-archive API) alone
    decodes_inst = arch.archive.segment_decodes
    obs.REGISTRY.reset("archive")
    assert obs.value("archive.segment_decodes") == 0
    assert arch.archive.segment_decodes == decodes_inst


# ----------------------------------------------------------- shard imbalance
def _dispatch(primary, rep):
    shipper = LogShipper(primary)
    shipper.subscribe(rep.replica_id, from_lsn=rep.resume_lsn)
    shipper.drain(rep.replica_id, rep.apply_batch)


def test_dispatch_imbalance_gauge_moves_under_skew():
    rng = random.Random(31)
    n_rows, val = 400, 24

    def run(rid, hot_key):
        primary, rows, _ = repl_workload.make_primary(rng, n_rows=n_rows,
                                                      val=val)
        rep = ShardedApplier(rid, page_size=4096, cache_pages=512,
                             tracker_interval=25, bg_flush_per_txn=2,
                             seed_tables={"t": rows}, n_shards=4,
                             epoch_txns=8)
        for _ in range(40):
            if hot_key:
                ops = [("update", "t", b"k00042", rng.randbytes(val))
                       for _ in range(4)]
            else:
                ops = [("update", "t",
                        f"k{rng.randrange(n_rows):05d}".encode(),
                        rng.randbytes(val)) for _ in range(4)]
            primary.run_txn(ops)
        _dispatch(primary, rep)
        return rep

    uniform = run("u1", hot_key=False)
    skewed = run("s1", hot_key=True)

    g_uniform = obs.value("repl.dispatch_imbalance", replica="u1")
    g_skewed = obs.value("repl.dispatch_imbalance", replica="s1")
    assert g_uniform == round(uniform.imbalance(), 4)
    assert g_skewed == round(skewed.imbalance(), 4)
    # one hot key lands every op on one shard: imbalance == n_shards
    assert g_skewed == 4.0
    assert g_uniform < 2.0 < g_skewed

    # per-shard gauges are live and account for every dispatched op
    dispatched = [obs.value("repl.shard.dispatched_ops",
                            replica="s1", shard=i) for i in range(4)]
    assert sum(dispatched) == sum(s.dispatched_ops for s in skewed.shards)
    assert sorted(dispatched)[:3] == [0, 0, 0]   # cold shards
    for i in range(4):
        assert obs.value("repl.shard.lag", replica="s1", shard=i) == 0
        assert obs.value("repl.shard.watermark",
                         replica="s1", shard=i) == skewed.shard_watermark(i)


def test_shard_gauges_show_lag_with_manual_pump():
    rng = random.Random(37)
    primary, rows, _ = repl_workload.make_primary(rng, n_rows=200, val=24)
    rep = ShardedApplier("m1", page_size=4096, cache_pages=512,
                         tracker_interval=25, bg_flush_per_txn=2,
                         seed_tables={"t": rows}, n_shards=2,
                         partitioner=lambda t, k: k[-1] % 2,
                         epoch_txns=10_000, auto_pump=False)
    for i in range(12):
        primary.run_txn([("update", "t", f"k{i % 200:05d}".encode(),
                          rng.randbytes(24))])
    _dispatch(primary, rep)
    rep.pump(shard=0)                  # shard 1 still queued
    rep.publish_metrics()
    lag0 = obs.value("repl.shard.lag", replica="m1", shard=0)
    lag1 = obs.value("repl.shard.lag", replica="m1", shard=1)
    assert lag0 == 0 and lag1 > 0
    rep.pump()
    rep.barrier()
    rep.publish_metrics()
    assert obs.value("repl.shard.lag", replica="m1", shard=1) == 0


# --------------------------------------------------------------- bench-diff
def _summary(mode, rows):
    return {"run": 1, "mode": mode,
            "rows": [{"module": m, "name": n, "us_per_call": us}
                     for m, n, us in rows]}


def test_bench_diff_flags_guarded_regressions_only():
    old = _summary("fast", [
        ("recovery_pipeline", "recovery_redo/Log1", 100.0),
        ("recovery_pipeline", "recovery_redo/Log0", 100.0),
        ("fig2_cache_sweep", "fig2/cache", 100.0),   # not oracle-guarded
        ("media", "media/tiny", 10.0),               # below the noise floor
    ])
    new = _summary("fast", [
        ("recovery_pipeline", "recovery_redo/Log1", 250.0),   # 2.5x: flag
        ("recovery_pipeline", "recovery_redo/Log0", 150.0),   # 1.5x: ok
        ("fig2_cache_sweep", "fig2/cache", 900.0),             # unguarded
        ("media", "media/tiny", 45.0),                        # noise floor
    ])
    regressions = bench_diff.compare_runs(old, new)
    assert len(regressions) == 1
    assert "recovery_redo/Log1" in regressions[0]
    assert "2.50x" in regressions[0]
    assert bench_diff.compare_runs(old, old) == []


def test_bench_diff_gate_is_graceful_without_history(tmp_path,
                                                     monkeypatch):
    monkeypatch.setattr(bench_diff, "ART_ROOT", tmp_path)
    assert bench_diff.main() == 0            # no artifacts at all
    (tmp_path / "bench_1.json").write_text(
        '{"run": 1, "mode": "fast", "rows": []}')
    assert bench_diff.main() == 0            # nothing to compare against
    (tmp_path / "bench_2.json").write_text(
        '{"run": 2, "mode": "full", "rows": []}')
    assert bench_diff.main() == 0            # different mode: still no pair


def test_bench_diff_warns_on_unreadable_artifact(tmp_path, monkeypatch,
                                                 capsys):
    monkeypatch.setattr(bench_diff, "ART_ROOT", tmp_path)
    (tmp_path / "bench_1.json").write_text('{"run": 1, "mo')   # torn write
    (tmp_path / "bench_2.json").write_text(
        '{"run": 2, "mode": "fast", "rows": []}')
    assert bench_diff.main() == 0
    err = capsys.readouterr().err
    assert "WARNING" in err and "bench_1.json" in err       # loud, by name


def test_bench_diff_newest_unreadable_is_loud_noop_pass(tmp_path,
                                                        monkeypatch,
                                                        capsys):
    monkeypatch.setattr(bench_diff, "ART_ROOT", tmp_path)
    (tmp_path / "bench_1.json").write_text(
        '{"run": 1, "mode": "fast", "rows": []}')
    (tmp_path / "bench_2.json").write_text('{"run": 2,')       # truncated
    assert bench_diff.main() == 0            # no-op pass, never a crash
    out = capsys.readouterr()
    assert "bench_2.json" in out.err         # the culprit is named
    assert "unreadable" in out.out           # and the verdict says why


def test_bench_diff_json_verdict(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(bench_diff, "ART_ROOT", tmp_path)
    rows = ('[{"module": "media", "name": "blob", "us_per_call": %s}]')
    (tmp_path / "bench_1.json").write_text(
        '{"run": 1, "mode": "fast", "rows": %s}' % (rows % "100.0"))
    (tmp_path / "bench_2.json").write_text(
        '{"run": 2, "mode": "fast", "rows": %s}' % (rows % "300.0"))
    assert bench_diff.main(["--json"]) == 1
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["ok"] is False and verdict["status"] == "regressions"
    assert verdict["old_run"] == 1 and verdict["new_run"] == 2
    assert len(verdict["regressions"]) == 1
    assert "media/blob" in verdict["regressions"][0]
