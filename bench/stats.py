"""Exact statistics over the run record: percentiles and rates."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) of ``values`` by linear
    interpolation between closest ranks (numpy's default); None when
    there are no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, with the
    quartiles of ``statistics.quantiles(values, n=4)``."""
    import statistics
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
