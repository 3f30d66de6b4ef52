"""The readers of the program's own spans: on a hand-built
``TRACER.events`` list, and on the spans a traced tiny window records on
the CPU."""
import pytest

from chipbench import program_spans
from chipbench import run as R
from repro import obs
from repro.obs.trace import TRACER

SAVE_METRICS = ("save.pull_s", "save.records_s",
                "save.log_bytes_per_state_byte", "pool.bg_flush_ms",
                "gc_s.train")
RESUME_METRICS = ("recovery.checkpoint_s", "restore.scan_s",
                  "restore.decode_s", "restore.put_s", "gc_s.resume")


class Events:
    """Builds begin/end events as the tracer writes them."""

    def __init__(self):
        self.events = []
        self.next = 1

    def span(self, name, t0, t1, parent=0, **attrs):
        sid = self.next
        self.next += 1
        self.events.append({"type": "begin", "span": sid, "parent": parent,
                            "name": name, "t_ms": t0, "wall": 0.0,
                            "attrs": {}})
        self.events.append({"type": "end", "span": sid, "name": name,
                            "t_ms": t1, "dur_ms": t1 - t0, "attrs": attrs})
        return sid


@pytest.fixture
def events(monkeypatch):
    ev = Events()
    monkeypatch.setattr(TRACER, "events", ev.events)
    return ev


def read(name):
    return R.read_layer_metric(name, None)


def test_readers_find_nothing_without_program_spans(events):
    for name in SAVE_METRICS + RESUME_METRICS:
        assert read(name) is None, name


def test_save_readers(events):
    t = 0.0
    for _ in range(2):                       # two saves, 2 leaves each
        save = events.span("wal.log_state", t, t + 1000.0,
                           log_bytes=2000, state_bytes=1000)
        events.span("wal.save.wait", t, t + 100.0, parent=save)
        for k in range(2):
            events.span("wal.save.pull", t + 100 + 400 * k,
                        t + 150 + 400 * k, parent=save)
            events.span("wal.save.records", t + 150 + 400 * k,
                        t + 500 + 400 * k, parent=save)
        events.span("pool.bg_flush", t + 950.0, t + 954.0, parent=save)
        events.span("gc", t + 200.0, t + 210.0, parent=save, generation=0)
        beat = events.span("wal.log_step_meta", t + 1000.0, t + 1010.0)
        events.span("pool.bg_flush", t + 1001.0, t + 1007.0, parent=beat)
        t += 2000.0
    assert read("save.pull_s") == pytest.approx(0.1)       # 2 × 50 ms
    assert read("save.records_s") == pytest.approx(0.7)    # 2 × 350 ms
    assert read("save.log_bytes_per_state_byte") == pytest.approx(2.0)
    assert read("pool.bg_flush_ms") == pytest.approx(5.0)  # 4, 6, 4, 6
    assert read("gc_s.train") == pytest.approx(0.02)


def test_gc_reads_zero_when_spans_but_no_collection(events):
    events.span("wal.log_state", 0.0, 10.0, log_bytes=1, state_bytes=1)
    assert read("gc_s.train") == 0.0


def test_resume_readers(events):
    events.span("gc", 0.0, 500.0, generation=2)          # before: left out
    restore = events.span("wal.restore", 1000.0, 5000.0)
    rec = events.span("recover", 1000.0, 4000.0, parent=restore)
    events.span("analysis", 1000.0, 1050.0, parent=rec)
    events.span("checkpoint", 3400.0, 4000.0, parent=rec)
    events.span("gc", 3500.0, 3600.0, parent=rec, generation=2)
    events.span("wal.restore.scan", 4000.0, 4300.0, parent=restore)
    for k in range(3):
        events.span("wal.restore.decode", 4300 + 200 * k, 4400 + 200 * k,
                    parent=restore)
        events.span("wal.restore.put", 4400 + 200 * k, 4500 + 200 * k,
                    parent=restore)
    events.span("checkpoint", 6000.0, 6100.0)             # not recovery's
    beat = events.span("wal.log_step_meta", 6200.0, 6210.0)
    events.span("pool.bg_flush", 6201.0, 6209.0, parent=beat)
    events.span("gc", 6300.0, 7300.0, generation=2)       # after: left out
    assert read("recovery.checkpoint_s") == pytest.approx(0.6)
    assert read("restore.scan_s") == pytest.approx(0.3)
    assert read("restore.decode_s") == pytest.approx(0.3)
    assert read("restore.put_s") == pytest.approx(0.3)
    assert read("gc_s.resume") == pytest.approx(0.1)


def test_open_span_is_not_read(events):
    events.span("wal.restore", 0.0, 10.0)
    events.events.append({"type": "begin", "span": 99, "parent": 0,
                          "name": "wal.restore.scan", "t_ms": 1.0,
                          "wall": 0.0, "attrs": {}})
    assert [s.name for s in program_spans.spans()] == ["wal.restore"]
    assert read("restore.scan_s") == 0.0


@pytest.mark.parametrize("workload,names", [
    ("tiny_whisper.save10", SAVE_METRICS),
    ("tiny_whisper.resume", RESUME_METRICS)])
def test_readers_on_a_traced_tiny_window(bench, cpu, tmp_path, workload,
                                         names):
    """The window a ``--trace 1`` run profiles, on the CPU: the program's
    spans land in ``TRACER.events`` with the tracer itself off, and every
    reader of the cell finds them."""
    cell, cfg, mix = R.find_cell(bench, workload)
    assert {m["name"] for m in R.cell_metrics(bench, cell, True)} >= set(names)
    tr = R.build_trainer(cfg, 3)
    obs.disable()
    TRACER.clear()
    count = {}
    try:
        if mix["kind"] == "periods":
            R.run_periods(tr, mix, 0.0, tmp_path, R.Spans(True), count)
        else:
            R.run_resume(tr, mix, 0.0, tmp_path, R.Spans(True), count)
        vals = {n: read(n) for n in names}
    finally:
        TRACER.clear()
    assert all(v is not None and v >= 0 for v in vals.values()), vals
    if mix["kind"] == "periods":
        assert 1.5 < vals["save.log_bytes_per_state_byte"] < 2.1
        assert vals["save.records_s"] > 0 and vals["pool.bg_flush_ms"] > 0
    else:
        assert vals["restore.scan_s"] > 0 and vals["restore.put_s"] > 0
