"""Rate and tail arithmetic of a window, with and without a stall."""
import numpy as np
import pytest

from chipbench.window import p95, rate


def test_rate_is_all_work_over_all_time():
    lat = [0.5] * 20
    assert rate(20 * 1600, sum(lat)) == pytest.approx(3200.0)


def test_tail_is_over_every_operation_and_sees_a_stall():
    steady = [0.2] * 99
    assert p95(steady) == pytest.approx(0.2)
    stalled = steady[:90] + [5.0] * 9
    assert p95(stalled) == pytest.approx(5.0)
    # a stall also lowers the rate taken over the whole window
    assert rate(99, sum(stalled)) < 0.5 * rate(99, sum(steady))


def test_tail_interpolates_like_numpy():
    v = np.arange(1, 21, dtype=float)
    assert p95(v) == pytest.approx(np.percentile(v, 95))
