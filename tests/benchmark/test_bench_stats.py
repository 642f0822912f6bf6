"""Percentile arithmetic, the ten-samples-beyond rule, the spread."""

import statistics

import pytest

from benchmark.lib import stats


@pytest.mark.parametrize("values,q,want", [
    ([], 0.5, None), ([5], 0.5, 5), ([5], 0.95, 5),
    ([1, 2, 3, 4], 0.5, 2), ([4, 1, 3, 2], 0.75, 3),
    (list(range(1, 101)), 0.95, 95), (list(range(1, 101)), 0.99, 99),
    (list(range(1, 201)), 0.95, 190), ([1, 1, 1, 9], 0.95, 9),
])
def test_quantile_is_nearest_rank(values, q, want):
    assert stats.quantile(values, q) == want


@pytest.mark.parametrize("n,q,beyond", [
    (200, 0.95, 10), (199, 0.95, 9), (100, 0.95, 5), (1000, 0.99, 10),
    (20, 0.5, 10), (19, 0.5, 9), (0, 0.95, 0),
])
def test_samples_beyond(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond
    assert stats.supported(n, q) == (beyond >= 10)


@pytest.mark.parametrize("n,want", [
    (1000, 99), (999, 98), (200, 95), (199, 94), (100, 90), (20, 50),
    (19, None), (0, None),
])
def test_highest_supported_percentile(n, want):
    assert stats.highest_supported_percentile(n) == want


@pytest.mark.parametrize("values", [
    [10, 11, 12, 13, 14, 15], [100.0, 100.5, 99.5, 101.0, 100.2, 99.9],
    [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
])
def test_spread_is_the_drivers(values):
    q = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q[2] - q[0]) / statistics.median(values))


@pytest.mark.parametrize("hist,q,want", [
    ([0] * 20, 0.99, None), ([], 0.5, None),
    ([0, 0, 0, 1] + [0] * 16, 0.5, 12.0),        # [8, 16) us -> 12
    ([10, 0, 0, 0] + [0] * 16, 0.99, 1.0),
    ([0, 0, 0, 98, 0, 0, 2] + [0] * 13, 0.99, 96.0),
    ([0, 0, 0, 99, 0, 0, 1] + [0] * 13, 0.99, 12.0),
    ([0] * 19 + [3], 0.99, 1.5 * 2 ** 19),
])
def test_hist_percentile(hist, q, want):
    assert stats.hist_percentile_us(hist, q) == want
