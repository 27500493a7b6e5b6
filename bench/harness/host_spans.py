"""Interval arithmetic over the program's spans and the device's idle time,
for the readers of the host-work metrics.

Intervals are ``(start, end)`` pairs on the host clock; ``union`` returns
them sorted and disjoint, which ``intersect`` and ``length`` expect.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out: List[Interval] = []
    i = j = 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return intersect(intervals, [(lo, hi)]) if hi > lo else []


def device_idle(trace) -> List[Interval]:
    """The traced window's intervals in which no device operation ran."""
    out: List[Interval] = []
    t = trace.start
    for a, b in trace.busy_intervals():
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if trace.stop > t:
        out.append((t, trace.stop))
    return out


def spans_of(spans: Iterable[dict], names) -> List[Interval]:
    """Union of the intervals of the spans named in ``names``."""
    return union((r["t0"], r["t1"]) for r in spans if r["name"] in names)


def host_work_names() -> Optional[tuple]:
    """The program's list of host-work span names, or None for a program
    that does not mark host work."""
    try:
        from repro.obs import HOST_WORK_SPANS
    except ImportError:
        return None
    return tuple(HOST_WORK_SPANS)
