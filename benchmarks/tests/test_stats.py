"""The percentile, rate and interval arithmetic on fixed inputs."""

import pytest

import stats


def test_percentile_is_nearest_rank():
    xs = [15, 20, 35, 40, 50]
    assert stats.percentile(xs, 30) == 20
    assert stats.percentile(xs, 40) == 20
    assert stats.percentile(xs, 50) == 35
    assert stats.percentile(xs, 95) == 50
    assert stats.percentile(xs, 100) == 50
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([7.0], 50) == 7.0


@pytest.mark.parametrize("bad", [0, -1, 101])
def test_percentile_refuses_bad_q(bad):
    with pytest.raises(ValueError):
        stats.percentile([1, 2], bad)


def test_percentile_refuses_empty():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate():
    assert stats.rate(10240 * 13, 2.0) == 66560.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)


def test_union_gaps_intersect_subtract():
    iv = [(5, 6), (1, 3), (2, 4), (6, 7), (9, 9)]
    assert stats.union(iv) == [(1, 4), (5, 7)]
    assert stats.gaps(iv, 0, 10) == [(0, 1), (4, 5), (7, 10)]
    assert stats.gaps(iv, 2, 6) == [(4, 5)]
    assert stats.intersect([(0, 10)], iv) == [(1, 4), (5, 7)]
    assert stats.subtract([(0, 2), (3, 8)], iv) == [(0, 1), (4, 5), (7, 8)]

