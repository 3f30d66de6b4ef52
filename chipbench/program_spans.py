"""The program's own spans, as ``repro.obs.trace.TRACER`` recorded them in
this process.

A program span is live, and recorded in ``TRACER.events``, while a
profiler session captures.  After a ``--trace 1`` window the list holds
the spans of the traced period or resume, and nothing from set-up or from
the checks, which run with the profiler off.  A program that records no
such spans leaves the list empty, and every reader of it returns None.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Span:
    name: str
    span: int
    parent: int
    t0_ms: float
    t1_ms: float
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.t1_ms - self.t0_ms) / 1e3


def events() -> list[dict]:
    try:
        from repro.obs.trace import TRACER
    except ImportError:
        return []
    return TRACER.events


def spans() -> list[Span]:
    """Every closed span, in the order they opened; its attributes are
    those it opened with and those set before it closed."""
    opened: dict[int, dict] = {}
    out = []
    for e in events():
        if e.get("type") == "begin":
            opened[e["span"]] = e
        elif e.get("type") == "end" and e.get("span") in opened:
            b = opened.pop(e["span"])
            out.append(Span(b["name"], b["span"], b["parent"], b["t_ms"],
                            e["t_ms"], {**b["attrs"], **e["attrs"]}))
    out.sort(key=lambda s: s.t0_ms)
    return out


def named(name: str, found: list[Span] | None = None) -> list[Span]:
    return [s for s in (spans() if found is None else found)
            if s.name == name]


def per_span(name: str, per: str) -> float | None:
    """Seconds in spans ``name`` for each span ``per`` (a save, a
    restore), or None without any span ``per``."""
    found = spans()
    n = len(named(per, found))
    if not n:
        return None
    return sum(s.seconds for s in named(name, found)) / n


def gc_seconds(start: str | None = None) -> float | None:
    """Seconds in ``gc`` spans, or None when the program recorded no span
    at all.  With ``start``, only the collections from the first span
    ``start`` opens to the last span other than ``gc`` closes."""
    found = spans()
    if not found:
        return None
    gcs = named("gc", found)
    if start is not None:
        firsts = named(start, found)
        if not firsts:
            return None
        lo = firsts[0].t0_ms
        hi = max(s.t1_ms for s in found if s.name != "gc")
        gcs = [s for s in gcs if lo <= s.t0_ms <= hi]
    return sum(s.seconds for s in gcs)
