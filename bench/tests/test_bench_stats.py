"""The benchmark's percentile and spread."""
import statistics

import numpy as np
import pytest

from bench.stats import percentile, spread


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(3).exponential(size=257)
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q),
                                              rel=1e-12)


def test_percentile_is_monotone_on_a_tied_tail():
    xs = [1.0] * 5 + [7.3] * 95
    assert percentile(xs, 50) <= percentile(xs, 95) <= percentile(xs, 99)


def test_percentile_refuses_empty_and_out_of_range():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_spread_is_the_quartile_distance_over_the_median():
    xs = [10.0, 11.0, 9.0, 10.5, 9.5, 10.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs) == pytest.approx((q3 - q1) / med)
