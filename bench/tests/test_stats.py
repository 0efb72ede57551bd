import statistics

import numpy as np
import pytest

from bench.lib import stats


@pytest.mark.parametrize("n", [1, 2, 10, 121, 1000])
@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_matches_numpy_linear(n, q):
    xs = np.random.default_rng(n).lognormal(0, 1, n)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_empty():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


@pytest.mark.parametrize("n,q,want", [(120, 90, 12), (121, 90, 12),
                                      (100, 95, 5), (11, 90, 1)])
def test_samples_beyond_the_percentile(n, q, want):
    assert stats.beyond(n, q) == want


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)
    assert stats.spread_trimmed(xs + [30.0]) == pytest.approx(
        stats.spread(xs))
