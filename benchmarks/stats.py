"""Sample statistics the runners share.  Every helper that reduces a timing
returns its sample count beside the value, so the count is always printed."""
from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """``(value, count)``: the ``q``-th percentile (0-100) by the nearest-rank
    rule over ALL samples given, missing ones included as ``math.inf``."""
    xs = sorted(values)
    if not xs:
        return None, 0
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1], len(xs)


def median(values):
    xs = list(values)
    return (statistics.median(xs) if xs else None), len(xs)


def describe(name, values, unit, q=95):
    """One earlier-output record of a timing: median, tail, count."""
    med, n = median(values)
    tail, _ = percentile(values, q)
    return {"timing": name, "unit": unit, "median": med, f"p{q}": tail,
            "count": n, "beyond_tail": n - math.ceil(q / 100.0 * n) if n else 0}


def spread(values):
    """Inter-quartile distance over the median, as the driver computes it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
