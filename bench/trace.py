"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The run writes JAX's profiler trace (an ``.xplane.pb``) of its window. In
it, every TPU device plane (``/device:TPU:<n>``) has an ``XLA Ops`` line,
one event per executed operation, and an ``XLA Modules`` line, one event
per executed program, named ``<jit name>(<fingerprint>)``. The host plane
(``/host:CPU``) carries the benchmark's own spans (``bench.*``), written
with ``jax.profiler.TraceAnnotation``, on the same clock.

* busy: the union of the operation intervals inside the window, averaged
  over the devices used;
* per-module time: the summed durations of a program's executions;
* idle gaps: the stretches of the window with no operation on a device,
  each named by the innermost benchmark span it falls in (by its middle);
* host time: a span's length less the device-busy time inside it.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict

import numpy as np

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[int, int, str]]]       # device -> (start, end, name)
    modules: dict[str, list[tuple[int, int, str]]]   # device -> (start, end, name)
    spans: list[tuple[int, int, str]]                # host (start, end, name)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: dict = {}
    modules: dict = {}
    spans = []
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
                spans.extend((int(e.start_ns), int(e.end_ns), e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=sorted(spans))


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
    host_ns = 0.0
    for s, e, name in inner:
        if name in host_spans:
            host_ns += (e - s) - float(np.mean([b.within(s, e) for b in busy]))
    n_dev = len(devices)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": float(np.mean(busy_ns)) / 1e9,
        "module_s": {k: v / 1e9 / n_dev for k, v in module_ns.items()},
        "op_s": {k: v / 1e9 / n_dev for k, v in op_ns.items()},
        "gap_s": {k: v / 1e9 / n_dev for k, v in gap_ns.items()},
        "host_s": host_ns / 1e9,
        "spans": len(inner),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": ranked(red["op_s"]),
            "idle_gaps": ranked(red["gap_s"])}
