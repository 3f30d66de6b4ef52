"""The reader of ``log.live_bytes_per_state_byte``: on hand-built spans,
and on the spans a traced tiny ``save10`` window records on the CPU,
where the log is cut at each checkpoint."""
import pytest

from chipbench import run as R
from repro import obs
from repro.obs.trace import TRACER

NAME = "log.live_bytes_per_state_byte"


def _save(events, t, **attrs):
    sid = events.next
    events.next += 1
    events.events.append({"type": "begin", "span": sid, "parent": 0,
                          "name": "wal.log_state", "t_ms": t, "wall": 0.0,
                          "attrs": {}})
    events.events.append({"type": "end", "span": sid,
                          "name": "wal.log_state", "t_ms": t + 1.0,
                          "dur_ms": 1.0, "attrs": attrs})


class _Events:
    def __init__(self):
        self.events, self.next = [], 1


@pytest.fixture
def events(monkeypatch):
    ev = _Events()
    monkeypatch.setattr(TRACER, "events", ev.events)
    return ev


def test_reads_the_largest_save(events):
    _save(events, 0.0, state_bytes=1000, log_bytes=2000,
          log_live_bytes=2100)
    _save(events, 10.0, state_bytes=1000, log_bytes=2000,
          log_live_bytes=4100)
    assert R.read_layer_metric(NAME, None) == pytest.approx(4.1)


def test_a_program_without_the_count_reads_none(events):
    assert R.read_layer_metric(NAME, None) is None
    _save(events, 0.0, state_bytes=1000, log_bytes=2000)
    assert R.read_layer_metric(NAME, None) is None


def test_traced_tiny_window_holds_two_saves(bench, cpu, tmp_path):
    """The traced period's saves at steps 30 and 40 follow the step-20
    checkpoint's cut: the log holds at most their two before- and
    after-images, and never the set-up's saves."""
    cell, cfg, mix = R.find_cell(bench, "tiny_whisper.save10")
    assert NAME in {m["name"] for m in R.cell_metrics(bench, cell, True)}
    tr = R.build_trainer(cfg, 3)
    obs.disable()
    TRACER.clear()
    try:
        R.run_periods(tr, mix, 0.0, tmp_path, R.Spans(True), {})
        value = R.read_layer_metric(NAME, None)
    finally:
        TRACER.clear()
    assert 3.9 < value <= 4.1
