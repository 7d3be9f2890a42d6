"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device operations are the events of each device plane's ``XLA Ops`` line;
host spans are the benchmark's own ``TraceAnnotation`` events, named
``bench/<phase>/<query>``, on the host plane.  Both share the trace's clock.
Busy time is the union of a device's operation intervals; with several
devices it is averaged over them.

The device's clock runs up to about a millisecond ahead of the host's in a
v5e trace: a plan's execution (``XLA Modules`` line) can appear to start
before the host span that dispatched it.  ``load`` shifts each device's
events by the least amount that puts every execution after the start of
its query's ``call`` span (the i-th execution belongs to the i-th query:
each query runs one plan).
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # seconds on the trace's clock

SPAN_PREFIX = "bench/"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
#: ops that hold other ops (a loop and its body are both on the ops line):
#: left out of the top list, which would count their bodies twice
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Span:
    phase: str
    query: str
    start: float
    end: float


@dataclass
class Trace:
    """Host spans and, per device, its operations ``(name, start, end)``."""

    spans: List[Span]
    ops: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)

    def __post_init__(self) -> None:
        self._merged = {d: union([(s, e) for _, s, e in evs])
                        for d, evs in self.ops.items()}
        self._starts = {d: [a for a, _ in m] for d, m in self._merged.items()}
        self._before = {}  # busy seconds before each merged interval
        for d, m in self._merged.items():
            acc, pre = 0.0, []
            for a, b in m:
                pre.append(acc)
                acc += b - a
            self._before[d] = pre + [acc]

    def queries(self) -> List[Span]:
        return [s for s in self.spans if s.phase == "query"]

    def window(self) -> Interval:
        q = self.queries()
        return (q[0].start, q[-1].end) if q else (0.0, 0.0)

    def busy(self, lo: float, hi: float) -> float:
        """Device-busy seconds in ``[lo, hi]``, averaged over the devices."""
        if not self.ops or hi <= lo:
            return 0.0
        total = 0.0
        for d, m in self._merged.items():
            i = max(0, bisect.bisect_right(self._starts[d], lo) - 1)
            j = bisect.bisect_left(self._starts[d], hi)
            if i >= j:
                continue
            seconds = self._before[d][j] - self._before[d][i]
            a, b = m[i]
            seconds -= max(0.0, min(b, lo) - a)   # the part before lo
            a, b = m[j - 1]
            seconds -= max(0.0, b - max(a, hi))   # the part after hi
            total += seconds
        return total / len(self._merged)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, ops, modules = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            ops[plane.name] = [(op_name(ev.name), ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                               for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ())]
            modules[plane.name] = sorted(ev.start_ns * 1e-9 for ev in (
                lines[MODULES_LINE].events if MODULES_LINE in lines else ()))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        _, phase, query = ev.name.split("/", 2)
                        spans.append(Span(phase, query, ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9))
    spans.sort(key=lambda s: s.start)
    calls = [s.start for s in spans if s.phase == "call"]
    for device, starts in modules.items():
        lead = max([c - m for c, m in zip(calls, starts)] + [0.0])
        ops[device] = [(n, s + lead, e + lead) for n, s, e in ops[device]]
    return Trace(spans, ops)


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


class SpanIndex:
    """Finds the innermost host span open at a time.  Query spans follow
    one another and each holds its phase spans, so the search walks back
    from the last span that started before the time."""

    def __init__(self, spans: Sequence[Span]) -> None:
        self.spans = sorted(spans, key=lambda s: s.start)
        self.starts = [s.start for s in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            s = self.spans[i]
            if s.end >= t:
                return f"{s.query}/{s.phase}"
            if s.phase == "query":
                break
            i -= 1
        return "outside"


def breakdown(trace: Trace) -> Dict[str, list]:
    """The device operations that took most time and the longest idle gaps
    of the window, each named by the host span open at the time."""
    lo, hi = trace.window()
    index = SpanIndex(trace.spans)
    by_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for evs in trace.ops.values():
        for name, s, e in evs:
            if lo <= s <= hi and name.split(".")[0] not in CONTAINERS:
                by_op[f"{index.at(s)}/{name}"] += e - s
        merged = union([(s, e) for _, s, e in evs if e >= lo and s <= hi])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((index.at((a + b) / 2), b - a))
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in sorted(gaps, key=lambda g: -g[1])[:TOP]]}
