"""Small statistics the benchmark reports: a percentile that says how
many samples it had, and the quartile spread the bounds are set from."""
from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """(value, n): the ``q``-th percentile (0-100) by linear
    interpolation between order statistics, and the sample count.  No
    samples give (None, 0)."""
    xs = sorted(float(v) for v in values)
    n = len(xs)
    if n == 0:
        return None, 0
    pos = (n - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, n - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo), n


def median(values):
    xs = [float(v) for v in values]
    return statistics.median(xs) if xs else None


def quartile_spread(values):
    """(q3 - q1) / median with ``statistics.quantiles(values, n=4)`` —
    the spread the contract sets bounds from."""
    q1, _q2, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / statistics.median(values)
