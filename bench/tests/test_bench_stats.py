"""Rates, tails timed from due times over all requests, busy unions."""

import statistics

import numpy as np
import pytest

from bench import stats


def test_rate_is_all_work_over_all_time():
    assert stats.rate(3000, 1.5) == 2000
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_latency_counts_from_due_time_and_missing_lands_in_tail():
    due = [0.0, 0.1, 0.2, 0.3]
    # the first answer stalls the lane: the later ones wait behind it
    done = [0.45, 0.46, 0.47, None]
    lat = stats.latencies_from_due(due, done, missing_at=2.0)
    np.testing.assert_allclose(lat, [0.45, 0.36, 0.27, 1.7])
    assert stats.percentile(lat, 99) == pytest.approx(1.7)


def test_percentile_nearest_rank():
    v = np.arange(1, 101)
    assert stats.percentile(v, 99) == 99
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 99)


def test_tail_over_all_requests_sees_a_stall_a_median_of_chunks_hides():
    lat = np.full(10_000, 1.0)
    lat[5_000:5_150] = 500.0          # one 150-request stall, 1.5%
    over_all = stats.percentile(lat, 99)
    chunks = [stats.percentile(c, 99) for c in np.split(lat, 20)]
    assert over_all == 500.0
    assert statistics.median(chunks) == 1.0


def test_union_length_counts_overlaps_once_and_clips():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7), (9, 12)]
    assert stats.union_length(iv) == 3 + 1 + 3
    assert stats.union_length(iv, lo=1, hi=10) == 2 + 1 + 1
    assert stats.union_length([]) == 0


def test_gaps_and_idle_share():
    iv = [(1, 2), (1.5, 3), (6, 8)]
    assert stats.gaps(iv, 0, 10) == [(0, 1), (3, 6), (8, 10)]
    busy = stats.union_length(iv, 0, 10)
    assert stats.idle_pct(busy, 10) == pytest.approx(60.0)


def test_spread_is_quartile_distance_over_median():
    v = [10.0, 10.2, 9.8, 10.1, 9.9, 10.0]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)
