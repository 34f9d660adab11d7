"""The statistics the benchmark reports."""
import pytest

from bench.harness import stats


def test_kendall_tau_b_matches_scipy_with_ties():
    scipy_stats = pytest.importorskip("scipy.stats")
    xs = [12.4, 12.6, 12.6, 13.0, 13.0, 13.0, 13.8, 15.5]
    ys = [13.9, 15.3, 12.1, 14.4, 17.8, 21.4, 19.5, 26.5]
    assert stats.kendall_tau_b(xs, ys) == pytest.approx(
        scipy_stats.kendalltau(xs, ys).statistic)


def test_kendall_tau_b_edges():
    assert stats.kendall_tau_b([1, 2, 3], [1, 2, 3]) == 1.0
    assert stats.kendall_tau_b([1, 2, 3], [3, 2, 1]) == -1.0
    assert stats.kendall_tau_b([1, 1, 1], [1, 2, 3]) is None


def test_percentile_interpolates_between_ranks():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
