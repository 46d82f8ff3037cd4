"""The benchmark's arithmetic: percentiles, rates, interval unions.

Kept here, under the benchmark's own path, so that no later PR can change
how a number is computed. Pure Python, no jax, no program import.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it. No interpolation:
    a tail is one of the requests that happened."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(units: float, seconds: float) -> float:
    """Units of work per second over the whole of a window."""
    if seconds <= 0:
        raise ValueError(f"rate over {seconds} s")
    return units / seconds


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of the intervals inside [lo, hi]."""
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of merged ``busy`` intervals inside [lo, hi]."""
    out, at = [], lo
    for a, b in union(clip(busy, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def intersect(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of merged ``a`` that merged ``b`` covers."""
    out = []
    b = union(b)
    for lo, hi in union(a):
        out.extend(clip(b, lo, hi))
    return out


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of merged ``a`` that merged ``b`` leaves uncovered."""
    out = []
    for lo, hi in union(a):
        out.extend(gaps(b, lo, hi))
    return out

