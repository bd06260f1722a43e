import pytest

from benchmark.stats import percentile, summary


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert percentile(xs, 0) == 10 and percentile(xs, 100) == 50
    assert percentile(xs, 50) == 30
    assert percentile(xs, 95) == pytest.approx(48.0)  # 4 * 0.95 = 3.8 -> 40 + 0.8 * 10
    assert percentile([7.0], 95) == 7.0
    assert percentile([], 50) is None
    assert percentile([3, 1, 2], 50) == 2  # order does not matter


def test_percentile_matches_numpy():
    np = pytest.importorskip("numpy")
    xs = [((i * 7919) % 1013) / 7.0 for i in range(401)]
    for q in (5, 50, 90, 95, 99):
        assert percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_summary_counts_its_samples():
    s = summary([1.0, 2.0, 3.0, 4.0])
    assert s["n"] == 4 and s["max"] == 4.0 and s["p50"] == 2.5

