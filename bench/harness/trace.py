"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: the device's busy seconds (the union of the intervals in which an
operation ran, averaged over the chips), busy within given intervals, the
operations that took most time, and the longest idle gaps, each named by the
innermost harness annotation (``jax.profiler.TraceAnnotation`` named
``bench.<what>``) that was open on the host at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_OP_LINES = ("XLA Ops", "XLA Modules")
# control flow whose body's operations are listed on their own
_CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Trace:
    """Events in nanoseconds on one clock: device ops per device plane, and
    the harness's host annotations as ``(name, start, end)``."""
    ops: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    annotations: List[Tuple[str, float, float]] = field(default_factory=list)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops, notes = {}, []
        for plane in data.planes:
            if _DEVICE_PLANE.match(plane.name):
                lines = {line.name: line for line in plane.lines}
                line = next((lines[n] for n in _OP_LINES if n in lines), None)
                if line is not None:
                    ops[plane.name] = [(op_name(e.name), e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PREFIX):
                            notes.append((e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
        return cls(ops, sorted(notes, key=lambda n: n[1]))

    @classmethod
    def from_dir(cls, directory: str) -> "Trace":
        found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        return cls.from_file(max(found, key=os.path.getmtime))

    # ----------------------------------------------------------------- reads
    def window(self) -> Optional[Interval]:
        """From the first harness annotation's start to the last's end."""
        if not self.annotations:
            return None
        return (min(a for _, a, _ in self.annotations),
                max(b for _, _, b in self.annotations))

    def intervals(self, name: str) -> List[Interval]:
        return merge([(a, b) for n, a, b in self.annotations if n == name])

    def busy(self, within: Sequence[Interval]) -> Optional[float]:
        """Busy nanoseconds inside ``within`` (merged), averaged over the
        device planes; None when the trace has no device operation."""
        if not self.ops or not any(self.ops.values()):
            return None
        within = merge(within)
        return sum(overlap(merge([(a, b) for _, a, b in evs]), within)
                   for evs in self.ops.values()) / len(self.ops)

    def top_ops(self, within: Interval, k: int = 10) -> List[list]:
        """The ``k`` operation names with the most device seconds inside
        ``within`` (summed over the devices, divided by their number);
        loops and calls are left out, their bodies' operations count."""
        lo, hi = within
        total: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for name, a, b in evs:
                if name.split(".")[0] in _CONTAINERS:
                    continue
                if b > lo and a < hi:
                    total[name] += min(b, hi) - max(a, lo)
        n = max(len(self.ops), 1)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n / 1e9] for name, ns in ranked]

    def idle_gaps(self, within: Interval, k: int = 10) -> List[list]:
        """The ``k`` longest idle gaps of the first device inside
        ``within``, each named by the innermost harness annotation open at
        its midpoint (``none`` outside them)."""
        if not self.ops:
            return []
        evs = self.ops[sorted(self.ops)[0]]
        lo, hi = within
        busy = merge([(max(a, lo), min(b, hi)) for _, a, b in evs
                      if b > lo and a < hi])
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) / 2
            open_ = [(e - s, n) for n, s, e in self.annotations if s <= mid <= e]
            label = min(open_)[1][len(PREFIX):] if open_ else "none"
            out.append([label, (b - a) / 1e9])
        return out
