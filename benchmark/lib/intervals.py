"""Arithmetic on lists of half-open intervals ``(start, end)``."""

from __future__ import annotations

from typing import Iterable, List, Tuple

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    """Length covered (overlaps counted once)."""
    return sum(e - s for s, e in union(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]
             ) -> List[Interval]:
    """Points of ``a`` not in ``b`` (both are unioned first)."""
    out: List[Interval] = []
    bs = union(b)
    j = 0
    for s, e in union(a):
        while j < len(bs) and bs[j][1] <= s:
            j += 1
        k = j
        cur = s
        while k < len(bs) and bs[k][0] < e:
            if bs[k][0] > cur:
                out.append((cur, bs[k][0]))
            cur = max(cur, bs[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi)`` that ``busy`` leaves uncovered."""
    return subtract([(lo, hi)], busy)


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))
