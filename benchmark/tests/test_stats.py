import statistics

import pytest

from benchmark import stats


def test_rate_is_all_the_work_over_all_the_window():
    steady = stats.rate_over_window(30 * 16384, 30.0)
    assert steady == 16384.0
    # one reading of 9 s among 29 of 1 s: the stall is in the number
    assert stats.rate_over_window(30 * 16384, 29.0 + 9.0) == pytest.approx(
        16384 * 30 / 38)


def test_quartile_spread_is_the_drivers():
    xs = [100.0, 101.0, 99.0, 100.5, 99.5, 102.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))
    assert stats.quartile_spread([1.0]) is None
