"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The run writes JAX's profiler trace (an ``.xplane.pb``) of its window. In
it, every TPU device plane (``/device:TPU:<n>``) has an ``XLA Ops`` line,
one event per executed operation, and an ``XLA Modules`` line, one event
per executed program, named ``<jit name>(<fingerprint>)``. The host plane
(``/host:CPU``) carries the benchmark's own spans (``bench.*``) and the
program's (``fleet.*``, ``src/repro/core/trace.py``, with their counters
as the events' stats), both written with ``jax.profiler.TraceAnnotation``,
on the same clock.

* busy: the union of the operation intervals inside the window, averaged
  over the devices used;
* per-module time: the summed durations of a program's executions;
* idle gaps: the stretches of the window with no operation on a device,
  each named by the innermost benchmark span it falls in (by its middle);
* host time: a span's length less the device-busy time inside it;
* per ``fleet.`` name: how many spans, their summed length and host time,
  their counters summed, and each ``*_max`` counter as its maximum.

Idle gaps are named by the ``bench.*`` spans alone.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict

import numpy as np

SPAN_PREFIX = "bench."
FLEET_PREFIX = "fleet."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[int, int, str]]]       # device -> (start, end, name)
    modules: dict[str, list[tuple[int, int, str]]]   # device -> (start, end, name)
    spans: list[tuple[int, int, str]]                # host (start, end, name)
    # the program's spans: host (start, end, name, counters)
    fleet: list[tuple[int, int, str, dict]] = dataclasses.field(
        default_factory=list)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: dict = {}
    modules: dict = {}
    spans, fleet = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(int(e.start_ns), int(e.end_ns), e.name)
                                       for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        (int(e.start_ns), int(e.end_ns), e.name)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((int(e.start_ns), int(e.end_ns), e.name))
                    elif e.name.startswith(FLEET_PREFIX):
                        fleet.append((int(e.start_ns), int(e.end_ns), e.name,
                                      dict(e.stats)))
    return Trace(ops=ops, modules=modules, spans=sorted(spans),
                 fleet=sorted(fleet, key=lambda f: f[:3]))


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged, clipped to [lo, hi], sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Busy:
    """Merged busy intervals with a prefix sum: the busy time inside any
    [lo, hi] in O(log n)."""

    def __init__(self, merged: list[tuple[int, int]]) -> None:
        self.starts = np.array([s for s, _ in merged], np.int64)
        self.ends = np.array([e for _, e in merged], np.int64)
        self.before = np.concatenate(
            [[0], np.cumsum(self.ends - self.starts)])

    def upto(self, t: int) -> int:
        """Busy time before t."""
        i = int(np.searchsorted(self.starts, t, side="right"))
        if i == 0:
            return 0
        return int(self.before[i - 1]) + min(t, int(self.ends[i - 1])) \
            - int(self.starts[i - 1])

    def within(self, lo: int, hi: int) -> int:
        return self.upto(hi) - self.upto(lo)


def module_base(name: str) -> str:
    return name.split("(", 1)[0]


def op_head(name: str) -> str:
    """``%fusion.17 = (...) fusion(...)`` -> ``fusion.17``."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_names(ops, modules) -> list[str]:
    """``<program>:<op>`` for each op, the program being the module
    execution the op lies in (both lists sorted by start)."""
    out, i, cur = [], 0, "?"
    mods = sorted(modules)
    for s, e, name in ops:
        while i < len(mods) and mods[i][0] <= s:
            cur = module_base(mods[i][2]) if mods[i][1] >= s else "?"
            i += 1
        if i and mods[i - 1][1] < s:
            cur = "?"
        out.append(f"{cur}:{op_head(name)}")
    return out


def innermost(spans, points) -> list[str]:
    """Name of the innermost span containing each point. ``spans`` are
    sorted by start and nest (they come from one thread); a point outside
    every span is ``host:untraced``."""
    names = [""] * len(points)
    stack: list[tuple[int, int, str]] = []
    i = 0
    for j in sorted(range(len(points)), key=points.__getitem__):
        t = points[j]
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        names[j] = stack[-1][2] if stack else "host:untraced"
    return names


def reduce(trace: Trace, *, host_spans: tuple[str, ...] = ()) -> dict:
    """Numbers of the traced window (the ``bench.window`` span)."""
    win = [(s, e) for s, e, n in trace.spans if n == WINDOW_SPAN]
    if not win or not trace.ops:
        return {}
    lo, hi = win[0]
    window_ns = hi - lo
    devices = sorted(trace.ops)
    merged = {d: union(trace.ops[d], lo, hi) for d in devices}
    busy_ns = [sum(e - s for s, e in merged[d]) for d in devices]

    module_ns: dict[str, int] = defaultdict(int)
    for d in devices:
        for s, e, name in trace.modules.get(d, ()):
            if s >= lo and e <= hi:
                module_ns[module_base(name)] += e - s
    op_ns: dict[str, int] = defaultdict(int)
    for d in devices:
        ops = sorted(trace.ops[d])
        for (s, e, _), name in zip(ops, op_names(ops, trace.modules.get(d, ()))):
            if s >= lo and e <= hi:
                op_ns[name] += e - s

    inner = [sp for sp in trace.spans
             if sp[2] != WINDOW_SPAN and lo <= sp[0] and sp[1] <= hi]
    gap_ns: dict[str, int] = defaultdict(int)
    for d in devices:
        edges = [lo] + [x for iv in merged[d] for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        names = innermost(inner, [(a + b) // 2 for a, b in gaps])
        for (a, b), name in zip(gaps, names):
            gap_ns[name] += b - a
    busy = [Busy(merged[d]) for d in devices]

    def host(s: int, e: int) -> float:
        return (e - s) - float(np.mean([b.within(s, e) for b in busy]))

    host_ns = sum(host(s, e) for s, e, name in inner if name in host_spans)
    n_dev = len(devices)
    out = {
        "window_s": window_ns / 1e9,
        "busy_s": float(np.mean(busy_ns)) / 1e9,
        "module_s": {k: v / 1e9 / n_dev for k, v in module_ns.items()},
        "op_s": {k: v / 1e9 / n_dev for k, v in op_ns.items()},
        "gap_s": {k: v / 1e9 / n_dev for k, v in gap_ns.items()},
        "host_s": host_ns / 1e9,
        "spans": len(inner),
    }
    fleet = fleet_blocks([f for f in trace.fleet if lo <= f[0] and f[1] <= hi],
                         host)
    if fleet:
        out["fleet"] = fleet
    return out


def fleet_blocks(events, host) -> dict:
    """One block per ``fleet.`` name: ``count``, ``span_s`` (summed
    length), ``host_s`` (summed length less the device busy inside each,
    by ``host(start, end)`` in ns) and ``counters``: summed, but a
    ``*_max`` counter keeps its maximum."""
    out: dict[str, dict] = {}
    for s, e, name, stats in events:
        b = out.setdefault(name, {"count": 0, "span_s": 0.0, "host_s": 0.0,
                                  "counters": {}})
        b["count"] += 1
        b["span_s"] += (e - s) / 1e9
        b["host_s"] += host(s, e) / 1e9
        c = b["counters"]
        for key, v in stats.items():
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            if key.endswith("_max"):
                c[key] = max(c.get(key, v), v)
            else:
                c[key] = c.get(key, 0) + v
    return out


def breakdown(red: dict, top: int = 10) -> dict:
    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": ranked(red["op_s"]),
            "idle_gaps": ranked(red["gap_s"])}
