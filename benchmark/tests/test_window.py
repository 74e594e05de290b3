"""The window is cut on step boundaries: the rate does not depend on where
``--seconds`` falls inside a step, and a stalled step lowers it."""
import numpy as np
import pytest

from benchmark.window import StepWindow, quantile


def drive(seconds, step, stall_at=None, stall=0.0):
    w = StepWindow(seconds, capacity=4096)
    t = 100.0
    w.open(t)
    k = 0
    while not w.closed:
        t += step + (stall if k == stall_at else 0.0)
        w.step_end(t)
        k += 1
    return w


@pytest.mark.parametrize("seconds", [10.0, 10.05, 10.2, 10.3649, 10.365,
                                     10.5, 10.73])
def test_rate_does_not_depend_on_where_seconds_falls(seconds):
    w = drive(seconds, 0.365)
    assert w.rate(256) == pytest.approx(256 / 0.365, rel=1e-9)
    assert w.elapsed >= seconds
    assert w.elapsed < seconds + 0.365 + 1e-9


def test_clock_cut_window_would_have_depended_on_it():
    # what the step-boundary cut replaces: steps counted over --seconds
    rates = [int(s / 0.365) * 256 / s for s in (10.0, 10.2, 10.5)]
    assert max(rates) / min(rates) > 1.01


def test_a_stalled_step_lowers_the_rate_and_is_counted():
    steady = drive(10.0, 0.365)
    stalled = drive(10.0, 0.365, stall_at=5, stall=1.0)
    assert stalled.rate(256) < 0.95 * steady.rate(256)
    assert np.max(stalled.step_seconds()) == pytest.approx(1.365)
    assert stalled.n == len(stalled.step_seconds())


def test_steps_before_the_opening_are_not_counted():
    w = StepWindow(1.0)
    assert w.step_end(5.0) is False and w.n == 0
    w.open(10.0)
    assert w.step_end(10.5) is False
    assert w.step_end(11.0) is True
    assert w.step_end(11.5) is False and w.n == 2
    assert w.elapsed == pytest.approx(1.0)


def test_quantile_is_over_all_values():
    assert quantile([1, 2, 3, 4, 100], 0.5) == 3
    assert quantile(list(range(101)), 0.95) == pytest.approx(95.0)
