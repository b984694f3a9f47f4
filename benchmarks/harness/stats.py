"""Percentiles and spreads, the one arithmetic every metric goes through."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 1] (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(float(v) for v in values)
    if len(xs) == 1:
        return xs[0]
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def summary(values: Sequence[float]) -> dict:
    """n, p50, p95, p99 and max of a sample, for the lines before the result."""
    return {
        "n": len(values),
        "p50": percentile(values, 0.50),
        "p95": percentile(values, 0.95),
        "p99": percentile(values, 0.99),
        "max": max(values),
    }


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, as the
    driver takes it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def stratified(lo: int, hi: int, n: int) -> list[int]:
    """``n`` whole numbers spread evenly over [lo, hi]: the fixed multiset a
    seed permutes, so every seed carries the same total work."""
    if n <= 0:
        return []
    span = hi - lo + 1
    return [lo + int((i + 0.5) * span / n) for i in range(n)]


def exponential_quantiles(rate: float, n: int) -> list[float]:
    """The ``n`` mid-point quantiles of an exponential inter-arrival time at
    ``rate`` per second, rescaled so that they sum to exactly ``n / rate``:
    one seed-independent multiset of Poisson-like gaps."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]
