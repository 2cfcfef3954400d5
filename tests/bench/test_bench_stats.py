"""Percentile and window arithmetic (``bench/stats.py``), on stamps
worked by hand."""
import pytest

from bench import stats


def test_percentile():
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0
    # linear interpolation: rank 0.95 * (5 - 1) = 3.8 -> 4 + 0.8 * 1
    assert stats.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)


def test_censored_waits():
    due = [0.5, 1.0, 2.0, 2.5, 4.0]
    done = [0.9, 1.5, None, 3.5, 4.1]
    # window [1, 3): due 1.0 -> 0.5; due 2.0 never -> 1.0; due 2.5 done
    # after the window -> 0.5; the others are not due in it
    assert stats.censored_waits(due, done, 1.0, 3.0) == [0.5, 1.0, 0.5]


def test_gaps_and_counts():
    stamps = [[0.5, 1.2, 1.2, 2.0], [2.9, 3.4], []]
    assert stats.gaps_ending_in(stamps, 1.0, 3.0) == pytest.approx(
        [0.7, 0.0, 0.8])
    assert stats.count_in(stamps, 1.0, 3.0) == 4
