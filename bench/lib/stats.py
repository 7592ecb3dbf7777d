"""Exact percentiles over a run's samples (a run holds hundreds to
thousands of them, so nothing is binned)."""
from __future__ import annotations

import math
import statistics


def rank_index(n: int, q: float) -> int:
    """Nearest-rank index of the q-th percentile among n sorted values."""
    if n <= 0:
        raise ValueError("percentile of no samples")
    return min(max(math.ceil(q / 100.0 * n) - 1, 0), n - 1)


def percentile(values, q: float) -> float:
    vals = sorted(values)
    return float(vals[rank_index(len(vals), q)])


def percentile_with_missing(values, missing, q: float) -> float:
    """q-th percentile where `missing` requests never completed.

    `values` are the completed samples; `missing` holds, for each request
    that never completed, a lower bound on its latency (from its due time
    to the end of the run).  Missing requests rank above every completed
    one.  Where the rank lands on a missing request, the result is its
    lower bound, raised to the largest completed sample: the true value
    is at least that."""
    done = sorted(values)
    lost = sorted(missing)
    n = len(done) + len(lost)
    k = rank_index(n, q)
    if k < len(done):
        return float(done[k])
    bound = lost[k - len(done)]
    return float(max(bound, done[-1] if done else bound))


def spread(values) -> float:
    """Interquartile distance over the median (Python's quartiles)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
