"""The tracer's profiler sink: spans on the JAX profiler's clock, the spans
inside ``TrainWAL``'s save, heartbeat and restore, the ``gc`` spans, and the
log-bytes counter they report."""
import contextlib
import gc
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import Database
from repro.core.dc import make_key
from repro.core.records import UpdateRec
from repro.obs import trace
from repro.state_store import (TrainWAL, WALConfig, n_state_records,
                               tree_to_records)
from repro.state_store.chunking import encode_chunk
from repro.state_store.train_wal import STATE_TABLE

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.TRACER.clear()
    yield
    obs.disable()
    obs.TRACER.clear()


@contextlib.contextmanager
def profiled(log_dir):
    """A CPU profiler session, as the benchmark harness opens one."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(log_dir) -> list[tuple[str, int, int, dict]]:
    """(name, start ns, end ns, stats) of every ``/host:CPU`` event."""
    path, = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats}))
    return out


def children(node, skip=("gc",)):
    return [c for c in node.children if c.name not in skip]


# ---------------------------------------------------------------- the bridge
def test_span_under_profiler_lands_on_host_plane(tmp_path):
    assert not obs.TRACER.enabled
    with profiled(tmp_path):
        with obs.span("outer", tag="t") as o:
            with obs.span("inner", n=1) as i:
                i.set(done=2)
            o.set(late=3)
    ev = {e[0]: e for e in host_events(tmp_path)}
    (_, o0, o1, ostats), (_, i0, i1, istats) = ev["outer"], ev["inner"]
    assert o0 <= i0 and i1 <= o1                  # nested on one clock
    assert ostats == {"tag": "t", "late": 3}
    assert istats == {"n": 1, "done": 2}
    # the same spans are in TRACER.events, with the end attributes
    roots = obs.build_tree(obs.TRACER.events)
    assert [r.name for r in roots] == ["outer"]
    assert [c.name for c in children(roots[0])] == ["inner"]
    assert roots[0].attrs == {"tag": "t", "late": 3}


def test_no_sink_gives_the_null_span_and_records_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    sp = obs.span("a", k=1)
    assert sp is trace._NULL_SPAN
    with sp as s:
        s.set(more=2)
    gc.collect()
    assert obs.TRACER.events == []


def test_enabled_without_profiler_records_without_traceme():
    obs.enable()
    with obs.span("a") as sp:
        assert sp._me is None
    assert [e["type"] for e in obs.TRACER.events] == ["begin", "end"]


def test_attribute_the_profiler_cannot_take_is_shown_as_text(tmp_path):
    with profiled(tmp_path):
        with obs.span("odd", none=None) as sp:
            sp.set(also=None, pids=[1, 2])
    (_, _, _, stats), = [e for e in host_events(tmp_path) if e[0] == "odd"]
    assert stats["none"] == "None" and stats["also"] == "None"
    end = obs.TRACER.events[-1]
    assert end["attrs"] == {"none": None, "also": None, "pids": [1, 2]}


def test_import_obs_does_not_import_jax():
    code = ("import sys, repro.obs, repro.core\n"
            "with repro.obs.span('x'): pass\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"PYTHONPATH": str(ROOT / "src"),
                        "PATH": "/usr/bin:/bin"})


def test_gc_collection_under_profiler_is_a_span(tmp_path):
    with profiled(tmp_path):
        gc.collect()
    spans = [e for e in obs.TRACER.events if e["name"] == "gc"]
    gen2 = [e for e in spans if e["type"] == "begin"
            and e["attrs"]["generation"] == 2]
    assert gen2
    ends = {e["span"]: e for e in spans if e["type"] == "end"}
    assert isinstance(ends[gen2[-1]["span"]]["attrs"]["collected"], int)
    host = [e for e in host_events(tmp_path) if e[0] == "gc"]
    assert any(stats.get("generation") == 2 for *_, stats in host)


def test_gc_outside_any_sink_records_nothing():
    gc.collect()
    assert trace._gc_span is None and obs.TRACER.events == []


# -------------------------------------------------------- log bytes counter
def test_log_bytes_counter_counts_before_and_after_images():
    db = Database(cache_pages=64)
    db.bootstrap_empty()
    c0 = obs.value("log.bytes_appended")
    db.run_txn([("insert", "t", b"k1", b"x" * 10)])
    assert obs.value("log.bytes_appended") - c0 == 10
    db.run_txn([("update", "t", b"k1", b"y" * 7)])
    assert obs.value("log.bytes_appended") - c0 == 10 + 10 + 7


# ------------------------------------------------------ TrainWAL span tree
CHUNK = 4096


def _state(sign):
    return {"w": sign * jnp.arange(3 * CHUNK + 5, dtype=jnp.float32),
            "b": (sign * jnp.arange(CHUNK + 9, dtype=jnp.float32) / 7
                  ).astype(jnp.bfloat16),
            "s": jnp.asarray(3 * sign, jnp.int32)}


def _update_bytes(log, lo, hi) -> int:
    total = 0
    for lsn in range(lo + 1, hi + 1):
        rec = log.record(lsn)
        if isinstance(rec, UpdateRec):
            total += len(rec.before or b"") + len(rec.after or b"")
    return total


@pytest.fixture(scope="module")
def wal_run(tmp_path_factory):
    """Insert save, update save, heartbeat, checkpoint, crash and restore
    of a tiny state, under a profiler session with ``TRACER`` off."""
    log_dir = tmp_path_factory.mktemp("wal_trace")
    obs.disable()
    obs.TRACER.clear()
    cfg = WALConfig(chunk_interval=1, ckpt_interval=2, bg_flush_pages=4,
                    cache_pages=256, chunk_elems=CHUNK, tracker_interval=3)
    wal = TrainWAL(cfg)
    state0, state1 = _state(1), _state(-1)
    ends = [wal.db.log.end_lsn]
    with profiled(log_dir):
        wal.log_state(0, 0, state0)
        ends.append(wal.db.log.end_lsn)
        wal.log_state(1, 1, state1)
        ends.append(wal.db.log.end_lsn)
        # read before the checkpoint discards the saves' records
        log_bytes = [_update_bytes(wal.db.log, a, b)
                     for a, b in zip(ends, ends[1:])]
        wal.log_step_meta(2, 2, 1)
        assert wal.maybe_checkpoint(2)
        image = wal.crash()
        out = TrainWAL.restore(image, jax.eval_shape(lambda: state0), cfg)
    events = list(obs.TRACER.events)
    obs.TRACER.clear()
    return dict(events=events, host=host_events(log_dir), wal=wal, out=out,
                states=(state0, state1), log_bytes=log_bytes, cfg=cfg)


def _roots(wal_run):
    return [r for r in obs.build_tree(wal_run["events"]) if r.name != "gc"]


def test_wal_roots_in_order(wal_run):
    assert [r.name for r in _roots(wal_run)] == [
        "wal.log_state", "wal.log_state", "wal.log_step_meta",
        "wal.log.discard", "wal.restore"]


def test_wal_checkpoint_discards_the_saves(wal_run):
    """The checkpoint's discard drops both saves and the heartbeat, each
    record's before- and after-image; each save reports the image bytes
    the log holds at its commit."""
    insert, update, _, discard = _roots(wal_run)[:4]
    assert insert.attrs["log_live_bytes"] == wal_run["log_bytes"][0]
    assert update.attrs["log_live_bytes"] == sum(wal_run["log_bytes"])
    heartbeat = 2 * 24                  # the meta record, before and after
    assert discard.attrs["bytes"] == sum(wal_run["log_bytes"]) + heartbeat
    assert discard.attrs["records"] > 2 * n_state_records(
        wal_run["states"][0], CHUNK)
    assert wal_run["wal"].db.log.live_image_bytes == 0


def test_wal_save_children(wal_run):
    n_leaves = len(jax.tree.leaves(wal_run["states"][0]))
    for save in _roots(wal_run)[:2]:
        names = [c.name for c in children(save)]
        assert names[0] == "wal.save.wait"
        assert names[1:-2] == ["wal.save.pull", "wal.save.records"] * n_leaves
        assert names[-2:] == ["wal.save.commit", "pool.bg_flush"]


@pytest.mark.parametrize("i", [0, 1], ids=["insert", "update"])
def test_wal_save_counts(wal_run, i):
    save = _roots(wal_run)[i]
    state = wal_run["states"][i]
    n = n_state_records(state, CHUNK)
    assert save.attrs["chunks_written"] == n
    assert save.attrs["chunks_skipped"] == 0
    assert save.attrs["state_bytes"] == sum(
        len(v) for _, v in tree_to_records(state, CHUNK))
    assert save.attrs["log_bytes"] == wal_run["log_bytes"][i]
    # before-image and after-image of every chunk on the update
    want = (1 if i == 0 else 2) * save.attrs["state_bytes"]
    assert 0 < save.attrs["log_bytes"] - want <= 2 * 24      # + the meta


def test_wal_save_skips_unchanged_chunks():
    wal = TrainWAL(WALConfig(chunk_elems=CHUNK, cache_pages=256))
    state = _state(1)
    obs.enable()
    wal.log_state(0, 0, state)
    wal.log_state(1, 1, state)
    save = [r for r in obs.build_tree(obs.TRACER.events)
            if r.name != "gc"][-1]
    assert save.name == "wal.log_state"
    assert save.attrs["chunks_written"] == 0
    assert save.attrs["chunks_skipped"] == n_state_records(state, CHUNK)


@pytest.mark.parametrize("on_host", [False, True], ids=["jax", "numpy"])
def test_wal_save_counts_leaves_prefetched(on_host):
    state = _state(1)
    if on_host:
        state = jax.tree.map(np.asarray, state)
    wal = TrainWAL(WALConfig(chunk_elems=CHUNK, cache_pages=256))
    obs.enable()
    wal.log_state(0, 0, state)
    save, = [r for r in obs.build_tree(obs.TRACER.events) if r.name != "gc"]
    assert save.attrs["leaves_prefetched"] == (
        0 if on_host else len(jax.tree.leaves(state)))


def test_every_copy_to_host_starts_before_the_first_record(monkeypatch):
    array_cls = type(jnp.zeros(()))
    started = []
    copy = array_cls.copy_to_host_async

    def record(self):
        started.append(id(self))
        return copy(self)
    monkeypatch.setattr(array_cls, "copy_to_host_async", record)
    state = _state(1)
    next(tree_to_records(state, CHUNK))
    assert started == [id(leaf) for leaf in jax.tree.leaves(state)]


def test_wal_heartbeat_and_pool_flush(wal_run):
    beat = _roots(wal_run)[2]
    assert beat.attrs["step"] == 2
    flush, = children(beat)
    assert flush.name == "pool.bg_flush"
    assert flush.attrs["scanned"] > 0
    assert 0 <= flush.attrs["flushed"] <= min(wal_run["cfg"].bg_flush_pages,
                                              flush.attrs["scanned"])


def test_wal_restore_children(wal_run):
    restore = _roots(wal_run)[4]
    names = [c.name for c in children(restore)]
    n_leaves = len(jax.tree.leaves(wal_run["states"][0]))
    assert names == (["recover", "wal.restore.scan"]
                     + ["wal.restore.decode", "wal.restore.put"] * n_leaves)
    recover = children(restore)[0]
    assert [c.name for c in children(recover)] == [
        "analysis", "redo", "undo", "checkpoint"]


def test_wal_restore_round_trips_bit_for_bit(wal_run):
    wal2, restored, step, cursor, state_step, _ = wal_run["out"]
    state1 = wal_run["states"][1]
    assert (step, cursor, state_step) == (2, 2, 1)
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(state1)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    prefix = make_key(STATE_TABLE, b"")
    stored = {k: v for k, v in wal2.db.scan_all() if k.startswith(prefix)}
    assert {k[len(prefix):]: v for k, v in stored.items()} == dict(
        tree_to_records(state1, CHUNK))


def test_records_keep_their_keys_and_bytes(tmp_path):
    """The spans inside ``tree_to_records`` change no key and no byte:
    checked against a plain re-statement of the record format, under a
    profiler session."""
    state = _state(1)
    want = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        name = "/".join(str(p.key) for p in path)
        arr = np.asarray(leaf)
        if leaf.dtype == jnp.bfloat16:
            arr = arr.view(np.uint16)
        flat = arr.reshape(-1)
        for c in range(max(1, -(-flat.size // CHUNK))):
            part = flat[c * CHUNK:(c + 1) * CHUNK]
            want.append((f"{name}#{c:06d}".encode(),
                         encode_chunk(part.tobytes(), str(leaf.dtype),
                                      part.size)))
    with profiled(tmp_path):
        got = list(tree_to_records(state, CHUNK))
    assert got == want
    assert Counter(e["name"] for e in obs.TRACER.events
                   if e["type"] == "begin")["wal.save.pull"] == len(
                       jax.tree.leaves(state))


def test_wal_spans_on_the_host_plane(wal_run):
    names = Counter(e[0] for e in wal_run["host"])
    for name, n in [("wal.log_state", 2), ("wal.save.commit", 2),
                    ("wal.log_step_meta", 1), ("pool.bg_flush", 3),
                    ("wal.restore", 1), ("recover", 1), ("checkpoint", 1),
                    ("wal.restore.scan", 1), ("wal.log.discard", 1)]:
        assert names[name] == n, name
    save = [e for e in wal_run["host"] if e[0] == "wal.log_state"][1]
    assert save[3]["chunks_written"] == n_state_records(
        wal_run["states"][1], CHUNK)
    assert save[3]["log_bytes"] == wal_run["log_bytes"][1]


def test_program_spans_are_not_harness_annotations():
    """The benchmark names its idle gaps by its own annotations; a program
    span of the same name would change what ``breakdown`` means."""
    sys.path.insert(0, str(ROOT))
    from chipbench import run
    names = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        names |= set(re.findall(r"\.span\(\s*\"([^\"]+)\"",
                                path.read_text()))
    assert {"wal.log_state", "pool.bg_flush", "gc", "recover"} <= names
    assert not names & (set(run.ANNOTATIONS) | {"window"})
