"""Percentiles and spreads, as the benchmark reports them."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (numpy's default), q in [0, 100].
    Infinite entries (requests that failed or never finished) sort last,
    so a tail that reaches them is infinite."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or xs[lo] == xs[hi]:
        return float(xs[lo])
    if math.isinf(xs[hi]):
        return math.inf
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def spread(values) -> float:
    """Distance between the first and third quartile, as a share of the
    median (statistics.quantiles' default method)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
