"""Rates are taken between call completions over real elapsed time."""
import pytest

import timing


def test_whole_calls_over_real_time():
    # 0.48 s calls, a nominal 2 s window: the interval closes at the first
    # completion at or after 2 s, i.e. after 5 calls and 2.4 s.
    comps = [0.48 * i for i in range(1, 10)]
    iv = timing.call_boundary_interval(0.0, comps, 2.0)
    assert iv.calls == 5 and iv.elapsed_s == pytest.approx(2.4)
    assert iv.rate(2048 * 32) == pytest.approx(2048 * 32 / 0.48)


@pytest.mark.parametrize("seconds", [1.9, 2.0, 2.1, 2.3, 2.39])
def test_window_edge_does_not_quantise(seconds):
    comps = [0.48 * i for i in range(1, 10)]
    iv = timing.call_boundary_interval(0.0, comps, seconds)
    assert iv.rate(1.0) == pytest.approx(1 / 0.48)


def test_a_stall_costs_its_real_share():
    steady = [0.5 * i for i in range(1, 21)]
    stalled = [t + (0.25 if t > 5.0 else 0.0) for t in steady]  # one 0.25 s stall
    a = timing.call_boundary_interval(0.0, steady, 10.0)
    b = timing.call_boundary_interval(0.0, stalled, 10.0)
    assert a.calls == b.calls == 20
    assert b.rate(1.0) / a.rate(1.0) == pytest.approx(10.0 / 10.25)


def test_start_offset_and_late_completions_ignored():
    comps = [100.0 + 0.5 * i for i in range(1, 9)]
    iv = timing.call_boundary_interval(100.0, comps, 2.0)
    assert iv.calls == 4 and iv.elapsed_s == pytest.approx(2.0)
    assert timing.window_done(100.0, comps[:4], 2.0)
    assert not timing.window_done(100.0, comps[:3], 2.0)
    assert not timing.window_done(100.0, [], 2.0)


def test_errors():
    with pytest.raises(ValueError):
        timing.call_boundary_interval(0.0, [0.5, 1.0], 2.0)  # never reached
    with pytest.raises(ValueError):
        timing.call_boundary_interval(0.0, [1.0, 0.5, 3.0], 2.0)  # goes backwards
    with pytest.raises(ValueError):
        timing.call_boundary_interval(0.0, [1.0], 0.0)
