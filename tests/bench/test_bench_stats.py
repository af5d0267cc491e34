"""Tail, rate and spread arithmetic (bench/stats.py): tails over all
samples of the window, never a median of per-chunk percentiles."""

import statistics

import numpy as np
import pytest

from bench import stats


def test_percentile_matches_numpy_linear():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(3.0, 1.0, 1001)
    for q in (0, 5, 50, 95, 99, 100):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))


def test_tail_is_over_all_samples_not_a_median_of_chunks():
    # Nine quiet chunks and one chunk of stalls: the p95 of all gaps
    # sees the stalls, a median of per-chunk p95s hides them.
    quiet = [10.0] * 90
    stalls = [10.0] * 2 + [400.0] * 8
    xs = quiet + stalls
    assert stats.percentile(xs, 95) == pytest.approx(400.0)
    chunks = [xs[i:i + 10] for i in range(0, 100, 10)]
    per_chunk = [stats.percentile(c, 95) for c in chunks]
    assert statistics.median(per_chunk) == pytest.approx(10.0)


def test_rate_is_all_work_over_all_time():
    assert stats.rate(3000, 12.0) == 250.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_intertoken_gaps_pool_every_request():
    times = {1: [0.0, 0.1, 0.3], 2: [0.05], 3: [1.0, 1.5]}
    assert sorted(stats.intertoken_gaps(times)) == pytest.approx(
        [0.1, 0.2, 0.5])


def test_spread_is_python_quartiles_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 12.5)
    assert stats.median([3, 1, 2]) == 2
