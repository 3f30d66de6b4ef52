"""Unified observability layer: metrics registry + span/event tracer +
recovery-timeline renderer + always-on flight recorder.

Quick tour::

    from repro import obs

    obs.enable()                         # tracing on (metrics are always on)
    db, stats = recover(image, Strategy.LOG1, batched=True)
    obs.disable()

    print(obs.render_timeline(snapshot=obs.snapshot()))
    obs.trace.export_jsonl("artifacts/recovery_trace.jsonl")

    obs.snapshot("recovery")             # {'recovery.redo_wall_ms': ..., ...}
    obs.reset()                          # zero metrics + drop trace events

Metrics (counters/gauges/histograms) are always on — a probe costs one
attribute increment, same as the ``self.x += 1`` counters it unifies.
Tracing is off by default.  A span is live while ``obs.enable()`` is in
force or a JAX profiler session is capturing; a live span records into
``TRACER.events`` and, under the profiler, also opens a ``TraceMe`` on the
profiler's clock (``obs.trace``).  Otherwise every span is a shared null
span and every point event sits behind an ``if TRACER.enabled`` guard.
``benchmarks/recovery_bench.bench_probe_overhead`` bounds that cost; it is
a CPU benchmark, not part of the tier-1 tests.  Importing ``obs`` never
imports ``jax``.

The flight recorder (``obs.flightrec``) is the third tier: always on like
metrics, event-shaped like the tracer, bounded like neither needs to be —
a ring of compact tuples dumped as a versioned black-box blob when the
engine crashes, rendered post hoc by ``obs.postmortem``.  Live progress
(``obs.progress``) and registry export (``obs.export``) round out the
production story.
"""
from . import metrics, timeline, trace
from . import export, flightrec, postmortem, progress
from .export import Sampler, prometheus_text
from .flightrec import FLIGHT, FlightRecorder, auto_dump, decode_dump
from .metrics import (REGISTRY, counter, gauge, histogram, load_dataclass,
                      publish_dataclass, snapshot, value)
from .postmortem import interrupted_phase, load_dump, render_postmortem
from .progress import ProgressObserver
from .timeline import build_tree, load_jsonl, render_timeline
from .trace import TRACER, event, span

__all__ = [
    "metrics", "trace", "timeline",
    "export", "flightrec", "postmortem", "progress",
    "REGISTRY", "counter", "gauge", "histogram", "value", "snapshot",
    "publish_dataclass", "load_dataclass",
    "TRACER", "span", "event",
    "render_timeline", "build_tree", "load_jsonl",
    "FLIGHT", "FlightRecorder", "auto_dump", "decode_dump",
    "load_dump", "render_postmortem", "interrupted_phase",
    "ProgressObserver", "Sampler", "prometheus_text",
    "enable", "disable", "reset",
]


def enable() -> None:
    """Turn tracing on (metrics need no enabling)."""
    trace.TRACER.enabled = True


def disable() -> None:
    trace.TRACER.enabled = False


def reset() -> None:
    """Zero every metric in place, drop all trace events, and clear the
    flight-recorder ring (re-anchoring its baseline)."""
    metrics.REGISTRY.reset()
    trace.TRACER.clear()
    flightrec.FLIGHT.clear()
