"""timer reset/reuse regression, percentile reservoirs, and the MeanMetric
scalar-NaN consistency fix (ISSUE 1 satellites)."""

import math

import numpy as np
import pytest

from sheeprl_tpu.utils.metric import MeanMetric, SumMetric
from sheeprl_tpu.utils.timer import timer


@pytest.fixture(autouse=True)
def _clean_timer_state():
    timer.reset()
    yield
    timer.reset()


def test_timer_instance_survives_reset():
    """Regression: a timer instance reused after timer.reset() must
    re-register its metric lazily instead of KeyError-ing in __exit__."""
    t = timer("Time/reused", SumMetric)
    with t:
        pass
    timer.reset()
    with t:  # KeyError here before the fix
        pass
    assert "Time/reused" in timer.compute()


def test_timer_decorator_survives_reset():
    @timer("Time/decorated", SumMetric)
    def work():
        return 1

    assert work() == 1
    timer.reset()
    assert work() == 1  # KeyError here before the fix
    assert timer.compute()["Time/decorated"] > 0


def test_timer_open_across_reset_lands_in_the_next_interval():
    """A region that holds the reset itself (the loops' Time/log) closes on
    a registry that no longer knows it: it registers again, and its time is
    the first entry of the next interval instead of a KeyError."""
    with timer("Time/outer", SumMetric):
        with timer("Time/inner", SumMetric):
            pass
        assert set(timer.compute()) == {"Time/outer", "Time/inner"}
        timer.reset()
        assert timer.compute() == {}
    assert set(timer.compute()) == {"Time/outer"} and timer.compute()["Time/outer"] > 0
    assert timer.percentiles()["Time/outer"]["n"] == 1


def test_timer_percentiles():
    t = timer("Time/pct", SumMetric)
    for _ in range(32):
        with t:
            pass
    pct = timer.percentiles()
    entry = pct["Time/pct"]
    assert entry["n"] == 32
    assert 0 <= entry["p50"] <= entry["p95"]
    # sums and samples agree in magnitude
    assert timer.compute()["Time/pct"] >= entry["p50"]


def test_timer_percentiles_empty_after_reset():
    with timer("Time/x", SumMetric):
        pass
    timer.reset()
    assert timer.percentiles() == {}


def test_timer_disabled_is_noop():
    timer.disabled = True
    try:
        with timer("Time/off", SumMetric):
            pass
        assert timer.compute() == {}
        assert timer.percentiles() == {}
    finally:
        timer.disabled = False


def test_mean_metric_scalar_nan_matches_array_nan():
    """A 0-d NaN must not increment the count (previously it did, while a
    1-d NaN array did not — metric.py:50)."""
    scalar = MeanMetric()
    scalar.update(float("nan"))
    assert math.isnan(scalar.compute())

    array = MeanMetric()
    array.update(np.asarray([float("nan")]))
    assert math.isnan(array.compute())

    # after a real value both paths agree exactly
    scalar.update(3.0)
    array.update(np.asarray([3.0]))
    assert scalar.compute() == array.compute() == 3.0


def test_mean_metric_mixed_finite_and_nan():
    m = MeanMetric()
    m.update(np.asarray([1.0, float("nan"), 3.0]))
    m.update(float("nan"))
    m.update(2.0)
    assert m.compute() == pytest.approx(2.0)
