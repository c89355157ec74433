"""Window, rate, percentile and interval arithmetic of the benchmark.

Interval arithmetic follows ``repro.observe.profile`` and percentiles
``benchmarks/bench_service.py`` (numpy's linear interpolation), copied so
that the yardstick stays fixed while the program changes.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

Interval = Tuple[float, float]


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0 <= q <= 100) of ``values`` by linear
    interpolation between closest ranks (numpy's default method)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside [0, 100]")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def rate(count: int, seconds: float) -> float:
    """Events per second over a window; a window must have a length."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def merge_intervals(iv: Sequence[Interval]) -> List[Interval]:
    """Union of half-open intervals, sorted and coalesced."""
    out: List[Interval] = []
    for s, e in sorted(i for i in iv if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def intersect_intervals(a: Sequence[Interval],
                        b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists (two-pointer sweep)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip_intervals(iv: Sequence[Interval], lo: float,
                   hi: float) -> List[Interval]:
    """The parts of merged intervals ``iv`` inside [lo, hi]."""
    return intersect_intervals(iv, [(lo, hi)])


def gaps(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged intervals ``iv`` within [lo, hi]."""
    out, cur = [], lo
    for s, e in clip_intervals(iv, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def total(iv: Sequence[Interval]) -> float:
    return sum(e - s for s, e in iv)
