"""Exact percentiles, quartile spread, and due-time latency with a window
that never came counted as failed (fake clock)."""

import numpy as np
import pytest

import _pb  # noqa: F401
from perfbench import stats
from perfbench.kinds import stream


def test_percentiles_are_exact_over_raw_samples():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 5.0
    # 95th of 1..5: rank 0.95 * 4 = 3.8 -> 4 + 0.8 * (5 - 4)
    assert stats.percentile(xs, 95) == pytest.approx(4.8, abs=1e-12)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_quartile_distance_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    # statistics.quantiles (exclusive): q1 = 10.75, median 12.5, q3 = 14.25
    assert stats.spread(xs) == pytest.approx((14.25 - 10.75) / 12.5)


def test_due_time_latency_and_a_window_never_scored():
    # two streams, windows due every 10 ms from 5 ms (stream 0) and 8 ms
    due = np.array([[0.005, 0.015, 0.025, 0.035],
                    [0.008, 0.018, 0.028, 0.038]])
    got = [
        [(0.006, 1.0), (0.017, 2.0), (0.026, 3.0), (0.0365, 4.0)],
        [(0.0085, 5.0), (0.020, 6.0)],  # windows 2 and 3 never come
    ]
    found, missing = stream.answers(got, due, w0=0.010, w1=0.030)
    # due in [10, 30] ms: stream 0 windows 1, 2; stream 1 windows 1, 2
    assert [(i, k) for i, k, *_ in found] == [(0, 1), (0, 2), (1, 1)]
    assert missing == 1
    lat = [t_score - t_due for _, _, t_due, t_score, _ in found]
    assert lat == pytest.approx([0.002, 0.001, 0.002])
    assert [s for *_, s in found] == [2.0, 3.0, 6.0]
