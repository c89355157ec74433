"""Window, rate, percentile and interval arithmetic."""
import numpy as np
import pytest

from chipbench import stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(3).exponential(size=257)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_by_hand():
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], 50) == 2.5
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 95) == \
        pytest.approx(4.8)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate():
    assert stats.rate(30, 12.0) == 2.5
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_intervals():
    iv = stats.merge_intervals([(5, 7), (0, 2), (1, 3), (6, 6), (9, 10)])
    assert iv == [(0, 3), (5, 7), (9, 10)]
    assert stats.total(iv) == 6
    assert stats.intersect_intervals(iv, [(2, 6), (9.5, 20)]) == \
        [(2, 3), (5, 6), (9.5, 10)]
    assert stats.clip_intervals(iv, 1, 9.5) == [(1, 3), (5, 7), (9, 9.5)]
    assert stats.gaps(iv, -1, 11) == [(-1, 0), (3, 5), (7, 9), (10, 11)]
    assert stats.gaps([], 0, 2) == [(0, 2)]
