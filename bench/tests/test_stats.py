import math

import pytest

from stats import percentile, spread


def test_percentile_matches_numpy_interpolation():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 90) == pytest.approx(4.6)
    assert percentile([7.0], 90) == 7.0
    assert math.isnan(percentile([], 90))


def test_failed_requests_count_as_infinite():
    ok = [float(i) for i in range(1, 10)]
    # one failure in ten: the 90th percentile interpolates towards it
    assert math.isinf(percentile(ok + [math.inf], 90))
    assert percentile(ok + [math.inf], 50) == pytest.approx(5.5)
    # one in twenty: the tail stays finite
    twenty = [float(i) for i in range(1, 20)] + [math.inf]
    assert percentile(twenty, 90) == pytest.approx(18.1)
    assert math.isinf(percentile([math.inf, math.inf], 50))


def test_spread_is_interquartile_over_median():
    assert spread([10.0] * 6) == 0.0
    assert spread([9.0, 10.0, 10.0, 10.0, 10.0, 11.0]) == pytest.approx(0.05)
