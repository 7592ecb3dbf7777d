"""Percentile arithmetic: nearest rank, missing requests at the top."""
import statistics

import pytest

from bench.lib import stats


def test_nearest_rank():
    vals = list(range(1, 101))          # 1..100
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile(vals, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile(list(reversed(vals)), 95) == 95


def test_no_samples_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_missing_requests_rank_at_the_top():
    served = [float(i) for i in range(1, 96)]       # 95 served, 1..95
    # five never served; the p95 rank (95th of 100) is the last served one
    assert stats.percentile_with_missing(served, [3.0] * 5, 95) == 95.0
    # six missing: the rank lands on a missing request, whose latency is
    # at least its bound and at least every served latency
    served = [float(i) for i in range(1, 95)]
    assert stats.percentile_with_missing(served, [200.0] * 6, 95) == 200.0
    assert stats.percentile_with_missing(served, [3.0] * 6, 95) == 94.0
    assert stats.percentile_with_missing([], [5.0, 9.0], 95) == 9.0


def test_spread_uses_pythons_quartiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)
