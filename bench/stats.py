"""Arithmetic of the end-to-end metrics: percentiles over all requests or
all gaps, and whole-window rates. No metric is built from medians of
pieces."""
from __future__ import annotations

import math
from statistics import quantiles
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts(dues: Sequence[float], firsts: Sequence[Optional[float]],
          stop: float) -> List[float]:
    """Time to first token of each request, from its due time; a request
    with no first token by `stop` counts with its wait until then."""
    return [(f if f is not None and f <= stop else stop) - d
            for d, f in zip(dues, firsts)]


def gaps_in(times: Sequence[float], lo: float, hi: float) -> List[float]:
    """Gaps between consecutive tokens of one request that end in [lo, hi)."""
    return [b - a for a, b in zip(times, times[1:]) if lo <= b < hi]


def count_in(times: Iterable[float], lo: float, hi: float) -> int:
    return sum(1 for t in times if lo <= t < hi)


def rate(count: int, seconds: float) -> float:
    return count / seconds


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartiles, as a share of the
    median (statistics.quantiles, exclusive method)."""
    q1, q2, q3 = quantiles(values, n=4)
    return (q3 - q1) / q2
