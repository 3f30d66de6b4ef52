"""Structured span/event tracer with a no-op fast path, and a bridge to the
JAX profiler's clock.

A span is *live* when ``TRACER.enabled`` is set or a JAX profiler session
is capturing (``TraceMe.is_enabled()``).  A live span

- records its begin/end events in ``TRACER.events`` (below);
- under a capturing profiler, also opens a ``TraceMe`` with its name and
  attributes, so it lands on the trace's ``/host:CPU`` plane on the same
  clock as the device's events; ``span.set(...)`` is forwarded to
  ``set_metadata`` and appears as that event's stats.

A span that is not live is the shared null span: it records nothing.
Point events (``Tracer.event``) stay in memory, and only while
``TRACER.enabled`` is set: they run per page read, so hot paths guard the
*call* with ``if TRACER.enabled`` and the kwargs dict is never built.
``benchmarks/recovery_bench.bench_probe_overhead`` bounds the cost of the
probes when off; it is a CPU benchmark, not part of the tier-1 tests.

The store is pure Python: this module never imports ``jax``.  It looks
for ``TraceMe`` only once ``jax`` is in ``sys.modules`` — a process that
never imported JAX cannot be under its profiler.

Python's garbage collections are spans too (``gc``, with ``generation``
and ``collected``), opened and closed by a ``gc.callbacks`` hook while
spans are live.

Event model — a flat list of dicts, one per line in the JSONL export:

  {"type": "begin", "span": 7, "parent": 3, "name": "redo.window",
   "t_ms": 12.301, "wall": 1754550000.123, "attrs": {...}}
  {"type": "end",   "span": 7, "name": "redo.window",
   "t_ms": 14.875, "dur_ms": 2.574, "attrs": {...}}
  {"type": "event", "parent": 7, "name": "io.demand",
   "t_ms": 13.002, "attrs": {"pid": 91, "outcome": "sync"}}

``t_ms`` is monotonic (``perf_counter`` relative to the tracer epoch — the
construction or last ``clear()``); ``wall`` on begin events anchors the
trace to wall-clock time.  Span ids are per-tracer-epoch; ``parent`` is the
innermost open span at emit time (0 = root).  Attributes set *during* a
span (``span.set(...)``) appear on its end event.  Events accumulate for as
long as spans are live; ``clear()`` drops them.

``TRACER`` is a process-wide singleton; toggle ``TRACER.enabled`` (or
``obs.enable()``/``obs.disable()``) — never rebind the name, call sites
capture the object at import.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path
from types import TracebackType
from typing import Any, Dict, List, Optional, Type, Union

#: ``jax.profiler.TraceAnnotation`` (a ``TraceMe``) once ``jax`` is loaded
_TRACEME: Any = None


def _traceme() -> Any:
    """The profiler's ``TraceMe`` type, or None while ``jax`` is not
    imported (this module never imports it)."""
    global _TRACEME
    if _TRACEME is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is not None:
            _TRACEME = profiler.TraceAnnotation
    return _TRACEME


def _stats(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """``attrs`` as ``TraceMe`` takes them for event stats."""
    for v in attrs.values():
        if not isinstance(v, (int, float, str)):
            return {k: v if isinstance(v, (int, float, str)) else str(v)
                    for k, v in attrs.items()}
    return attrs


class _NullSpan:
    """Shared no-op span returned while tracing is disabled."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type: Optional[Type[BaseException]],
                 exc: Optional[BaseException],
                 tb: Optional[TracebackType]) -> bool:
        return False

    def set(self, **attrs: object) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tr", "name", "attrs", "span_id", "_t0", "_me")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Dict[str, Any], traceme: Any = None):
        self._tr = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = 0
        self._t0 = 0.0
        self._me = None if traceme is None else traceme(name, **_stats(attrs))

    def __enter__(self) -> "_Span":
        tr = self._tr
        self.span_id = tr._next_id
        tr._next_id += 1
        if self._me is not None:
            self._me.__enter__()
        self._t0 = tr.now_ms()
        tr.events.append({
            "type": "begin", "span": self.span_id,
            "parent": tr._stack[-1] if tr._stack else 0,
            "name": self.name, "t_ms": round(self._t0, 3),
            "wall": time.time(), "attrs": dict(self.attrs)})
        tr._stack.append(self.span_id)
        return self

    def set(self, **attrs: object) -> "_Span":
        """Attach/refresh attributes; they ride on the end event (and on
        the profiler's event, as its stats)."""
        self.attrs.update(attrs)
        if self._me is not None:
            self._me.set_metadata(**_stats(attrs))
        return self

    def __exit__(self, exc_type: Optional[Type[BaseException]],
                 exc: Optional[BaseException],
                 tb: Optional[TracebackType]) -> bool:
        tr = self._tr
        t1 = tr.now_ms()
        if self._me is not None:
            self._me.__exit__(exc_type, exc, tb)
        if tr._stack and tr._stack[-1] == self.span_id:
            tr._stack.pop()
        ev: Dict[str, Any] = {
            "type": "end", "span": self.span_id, "name": self.name,
              "t_ms": round(t1, 3), "dur_ms": round(t1 - self._t0, 3),
              "attrs": self.attrs}
        if exc_type is not None:
            ev["error"] = exc_type.__name__
        tr.events.append(ev)
        return False


class Tracer:
    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.events: List[Dict[str, Any]] = []
        self._stack: List[int] = []
        self._next_id = 1
        self._epoch = time.perf_counter()

    def now_ms(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e3

    # ------------------------------------------------------------- emission
    def span(self, name: str,
             **attrs: object) -> Union[_Span, _NullSpan]:
        """Context manager for a nested span; the null span unless live
        (enabled, or a profiler session capturing)."""
        tm = _traceme()
        capturing = tm is not None and tm.is_enabled()
        if not (self.enabled or capturing):
            return _NULL_SPAN
        return _Span(self, name, attrs, tm if capturing else None)

    def event(self, name: str, **attrs: object) -> None:
        """Point event, parented to the innermost open span.  Hot paths
        must guard the *call* with ``if TRACER.enabled`` so the kwargs
        dict is never built when tracing is off."""
        if not self.enabled:
            return
        self.events.append({
            "type": "event", "parent": self._stack[-1] if self._stack else 0,
            "name": name, "t_ms": round(self.now_ms(), 3), "attrs": attrs})

    # ------------------------------------------------------------ lifecycle
    def clear(self) -> None:
        """Drop all events and start a new epoch (span ids restart, t_ms
        rebases to now)."""
        self.events.clear()
        self._stack.clear()
        self._next_id = 1
        self._epoch = time.perf_counter()

    def export_jsonl(self, path: Union[str, Path]) -> Path:
        """One event per line; returns the path written."""
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        with open(p, "w", encoding="utf-8") as f:
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")
        return p


#: the process-wide tracer; import-site convenience shims below
TRACER = Tracer()


def span(name: str, **attrs: object) -> Union[_Span, _NullSpan]:
    return TRACER.span(name, **attrs)


def event(name: str, **attrs: object) -> None:
    # reprolint: allow(tracer-guard) — the module-level convenience shim IS the unguarded form; hot paths import TRACER and guard at the call site
    TRACER.event(name, **attrs)


def export_jsonl(path: Union[str, Path]) -> Path:
    return TRACER.export_jsonl(path)


# ------------------------------------------------------ garbage collection
_gc_span: Optional[_Span] = None


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` hook: one ``gc`` span per collection while spans
    are live; one check per collection otherwise."""
    global _gc_span
    if phase == "start":
        sp = TRACER.span("gc", generation=info["generation"])
        if isinstance(sp, _Span):
            _gc_span = sp.__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.set(collected=info["collected"])
        sp.__exit__(None, None, None)


gc.callbacks.append(_on_gc)
