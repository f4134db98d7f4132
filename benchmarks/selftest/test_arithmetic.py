import math

import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_between_closest_ranks():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([10, 20], 90) == pytest.approx(19.0)
    assert stats.percentile([7], 90) == 7


def test_percentile_counts_missing_as_beyond_any_value():
    values = list(range(1, 10))           # nine answered, one never did
    assert stats.percentile(values, 50, missing=1) == pytest.approx(5.5)
    assert stats.percentile(values, 95, missing=1) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_share_is_the_spread_the_bounds_are_set_from():
    import statistics

    values = [100, 101, 99, 102, 98, 100]
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    assert stats.iqr_share(values) == pytest.approx((q3 - q1) / 100)


def test_window_rate_is_all_the_work_over_all_the_time():
    # 18 fit.epoch stamps 2.5 s apart; one epoch took 6 s (a stall)
    stamps, t = [], 100.0
    for i in range(18):
        stamps.append(t)
        t += 6.0 if i == 7 else 2.5
    rate = stats.window_rate(stamps, 4096, chips=1)
    assert rate == pytest.approx(17 * 4096 / (16 * 2.5 + 6.0))
    # the median of the per-epoch readings, a per-layer metric, does
    # not see the stall; the rate, which is judged, does
    readings = stats.epoch_readings(stamps, 4096, chips=1)
    assert len(readings) == 17          # every interval of the window
    assert stats.median(readings) == pytest.approx(4096 / 2.5)
    assert rate < 0.93 * stats.median(readings)


def test_window_rate_and_readings_are_per_chip():
    assert stats.window_rate([0, 1, 2, 3], 400, chips=4) == 100
    assert stats.epoch_readings([0, 1, 2, 3], 400, chips=4) == [100] * 3
    assert stats.window_rate([5.0], 400, chips=1) is None
