"""Small order statistics, kept with the benchmark so that every PR computes
a percentile and a spread the same way."""
from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    """Linear-interpolated ``p``-th percentile (0..100) of ``values``."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def quartile_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``: the spread a bound is
    set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
