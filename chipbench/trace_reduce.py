"""From a profiler trace to device busy and idle time, per-op device time,
and idle gaps named by what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Device planes are named ``/device:<KIND>:<n>``;
their ``XLA Ops`` line holds one event per operation run, and their ``XLA
Modules`` line one event per program run.  The host plane ``/host:CPU``
holds the ``jax.profiler.TraceAnnotation`` spans the harness opens.  All
events share one clock in nanoseconds.

- The window is the span of the annotation named ``window``.
- Busy is the union of the device's operation intervals inside the window,
  averaged over the devices traced; idle is the rest of the window.
- A program run counts when its midpoint lies inside the window, with its
  whole device time.
- Each idle gap is cut where a host annotation opens or closes, and each
  piece is named by the innermost annotation open over it, or
  ``"(none)"``; neighbouring pieces of one name join.

The device's clock can read a millisecond or two early against the host's
(1.5 ms in the recorded trace the tests use); at the length of a window
that moves nothing.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:[A-Z_]+:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NONE = "(none)"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    import jax
    if os.path.isdir(path):
        path = find_xplane(path)
    return jax.profiler.ProfileData.from_file(path)


def op_name(name: str) -> str:
    """``fusion.12`` from an op event's HLO text
    (``%fusion.12 = bf16[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") \
        else name


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield op_name(e.name), e.start_ns, e.start_ns + e.duration_ns


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def host_spans(pd, names) -> list[tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def _pieces(gap, spans) -> list[tuple[str, float]]:
    """The gap cut at annotation edges; each piece named by the innermost
    annotation open over it."""
    lo, hi = gap
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out: list[list] = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [(e - s, name) for name, s, e in spans if s <= mid < e]
        name = min(open_)[1] if open_ else NONE
        if out and out[-1][0] == name:
            out[-1][1] += b - a
        else:
            out.append([name, b - a])
    return [(name, secs) for name, secs in out]


def reduce(pd, annotations, window: str = "window", top: int = 10) -> dict:
    """Busy and window seconds, per-op and per-program device seconds, and
    the ``top`` longest idle gaps with their host annotation."""
    spans = host_spans(pd, set(annotations) | {window})
    wins = [(s, e) for n, s, e in spans if n == window]
    if not wins:
        raise ValueError(f"no {window!r} annotation in the trace")
    lo, hi = wins[0]
    inner = [x for x in spans if x[0] != window]
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("no device plane in the trace")
    busy_total = 0.0
    ops: dict[str, float] = defaultdict(float)
    modules: dict[str, list[float]] = defaultdict(list)
    gaps = []
    for i, plane in enumerate(devices):
        ivs = []
        for name, s, e in _events(plane, OPS_LINE):
            s, e = _clip(s, e, lo, hi)
            if e > s:
                ivs.append((s, e))
                ops[name] += (e - s) / 1e9
        for name, s, e in _events(plane, MODULES_LINE):
            if lo <= (s + e) / 2 <= hi:
                modules[name].append((e - s) / 1e9)
        busy = _union(ivs)
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps += [(n, d / 1e9) for n, d in _pieces((s, e), inner)]
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy_total / len(devices) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "modules": {k: {"count": len(v), "seconds": sum(v)}
                    for k, v in modules.items()},
        "idle_gaps": gaps[:top],
    }
