"""Reduction of a profiler trace to per-layer numbers.

A trace here is what ``jax.profiler`` writes (``*.xplane.pb``): one plane
per device with its operations on the "XLA Ops" line, and the host
plane whose threads carry the benchmark's own spans
(``jax.profiler.TraceAnnotation`` names starting ``chipbench.``). Times
are converted to seconds on the trace's clock.

The functions below take plain lists of (start, end) intervals, so they
can be checked on constructed traces without a chip:

* ``busy``: seconds in which some operation ran (union of intervals);
* ``idle_gaps``: the complement of that union inside a window;
* ``kernel_time``: summed durations of operations whose text matches;
* ``self_times``: each operation's time less that of the operations
  nested inside it (a loop's body runs inside the loop's own event);
* ``exposed``: collective time during which no compute ran (counting
  only leaf ops: a loop's event spans its body, collectives included);
* ``attribute``: each idle gap named by the innermost host span open at
  its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# Names XLA gives collective operations (HLO opcodes and their fusions).
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter", re.IGNORECASE)
SPAN_PREFIX = "chipbench."


@dataclasses.dataclass
class Op:
    name: str       # the HLO instruction's name, e.g. "fusion.12"
    start: float
    end: float
    text: str = ""  # its whole HLO text (shapes, custom-call target)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]          # plane name -> ops, by start
    spans: List[Tuple[str, float, float]]  # benchmark host spans

    def window(self, span: str) -> Optional[Interval]:
        """The extent of every host span of this name."""
        hits = [(s, e) for n, s, e in self.spans if n == span]
        if not hits:
            return None
        return min(s for s, _ in hits), max(e for _, e in hits)


# --------------------------------------------------------------------------- #
# Interval arithmetic.
# --------------------------------------------------------------------------- #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if e > lo and s < hi]


def busy(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] covered by at least one interval."""
    return sum(e - s for s, e in _clip(union(intervals), lo, hi))


def idle_gaps(intervals: Iterable[Interval], lo: float,
              hi: float) -> List[Interval]:
    """Maximal sub-intervals of [lo, hi] covered by no interval."""
    gaps, t = [], lo
    for s, e in _clip(union(intervals), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def intersection(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def kernel_time(ops: Iterable[Op], pattern: str, lo: float = -1e300,
                hi: float = 1e300) -> Tuple[float, int]:
    """(summed seconds, count) of ops whose name or text matches
    ``pattern`` and that start inside [lo, hi]."""
    rx = re.compile(pattern)
    t = n = 0
    for op in ops:
        if lo <= op.start <= hi and (rx.search(op.name)
                                     or rx.search(op.text)):
            t += op.end - op.start
            n += 1
    return t, n


def leaves(ops: Iterable[Op]) -> List[Op]:
    """The ops with no other op nested inside them."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    parent = [False] * len(ops)
    open_: List[int] = []
    for i, op in enumerate(ops):
        while open_ and ops[open_[-1]].end <= op.start:
            open_.pop()
        if open_ and op.end <= ops[open_[-1]].end:
            parent[open_[-1]] = True
        open_.append(i)
    return [op for op, p in zip(ops, parent) if not p]


def exposed(ops: Iterable[Op], lo: float, hi: float) -> Tuple[float, float]:
    """(collective seconds, of which no compute ran) within [lo, hi],
    over the leaf ops."""
    coll, comp = [], []
    for op in leaves(ops):
        target = coll if COLLECTIVE.search(op.name) else comp
        target.append((op.start, op.end))
    c = _clip(union(coll), lo, hi)
    total = sum(e - s for s, e in c)
    return total, total - intersection(c, _clip(union(comp), lo, hi))


def attribute(gaps: Iterable[Interval],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle seconds by the innermost host span open at each gap's
    midpoint ("none" where no span is open)."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        name, best = "none", None
        for k in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            n, ss, se = spans[k]
            if se >= mid and (best is None or ss > best):
                name, best = n, ss
                break
        out[name] += e - s
    return dict(out)


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def self_times(ops: Iterable[Op], lo: float, hi: float) -> Dict[str, float]:
    """Seconds by op name of the ops starting inside [lo, hi], each less
    the time of the ops nested directly inside it."""
    out: Dict[str, float] = defaultdict(float)
    open_: List[Tuple[float, str]] = []  # (end, name) of enclosing ops
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while open_ and open_[-1][0] <= op.start:
            open_.pop()
        d = op.end - op.start
        inside = bool(open_) and op.end <= open_[-1][0]
        if lo <= op.start <= hi:
            out[op.name] += d
            if inside:
                out[open_[-1][1]] -= d
        if inside or not open_:
            open_.append((op.end, op.name))
    return dict(out)


# --------------------------------------------------------------------------- #
# Loading.
# --------------------------------------------------------------------------- #
def find(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return paths[-1]


def _short(text: str) -> str:
    """"%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Trace:
    """Device ops ("XLA Ops" lines of the ``/device:`` planes) and the
    benchmark's host spans from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops: List[Op] = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append(Op(_short(ev.name), s,
                                  s + ev.duration_ns * 1e-9, ev.name))
            if ops:
                ops.sort(key=lambda o: o.start)
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return Trace(devices, spans)
