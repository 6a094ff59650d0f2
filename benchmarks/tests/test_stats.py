"""Percentile and arrival arithmetic."""

import numpy as np
import pytest

from benchmarks import stats


def test_percentile_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 50) == 50
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, float("inf")], 95) == float("inf")
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_arrival_gaps_mean_rate_and_bursts():
    rng = np.random.default_rng(0)
    gaps = stats.arrival_gaps(rng, 20000, rate=100.0)
    assert np.mean(gaps) == pytest.approx(0.01, rel=0.05)
    bursty = stats.arrival_gaps(np.random.default_rng(0), 20000, rate=100.0, burst=4)
    assert sum(1 for g in bursty if g == 0.0) == 15000
    assert np.sum(bursty) / 20000 == pytest.approx(0.01, rel=0.05)
    with pytest.raises(ValueError):
        stats.arrival_gaps(rng, 1, rate=0.0)
