"""Whole-unit accounting on a fake clock."""

import pytest

from benchmark.window import run_window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def fixed_units(clock, durations, images=50_000):
    it = iter(durations)

    def unit(i):
        clock.t += next(it)
        return {"images": images, "steps": 7, "failed": 0}
    return unit


def test_window_that_ends_mid_unit_counts_the_finished_unit_and_its_time():
    clock = FakeClock()
    fences = []
    w = run_window(fixed_units(clock, [0.8] * 100), 2.0, clock=clock,
                   fence=lambda: fences.append(clock.t))
    # 2.0 s runs out inside the third unit: it is finished and counted whole
    assert len(w.units) == 3
    assert w.seconds == pytest.approx(2.4)
    assert w.total("images") == 150_000
    assert w.rate("images") == pytest.approx(150_000 / 2.4)
    assert fences == [100.0, pytest.approx(102.4)]
    assert [u["start"] for u in w.units] == pytest.approx([0.0, 0.8, 1.6])


def test_the_divisor_is_the_measured_interval_not_the_nominal_length():
    clock = FakeClock()
    slow = run_window(fixed_units(clock, [0.8, 0.8, 3.0]), 2.0, clock=clock,
                      fence=lambda: None)
    assert slow.seconds == pytest.approx(4.6)            # a stall is inside
    assert slow.rate() == pytest.approx(150_000 / 4.6)
    assert slow.unit_ms_p50() == pytest.approx(800.0)    # the median is not


def test_a_unit_that_ends_on_the_mark_closes_the_window():
    clock = FakeClock()
    w = run_window(fixed_units(clock, [1.0] * 10), 2.0, clock=clock,
                   fence=lambda: None)
    assert len(w.units) == 2 and w.seconds == pytest.approx(2.0)


def test_min_units_and_first_index():
    clock = FakeClock()
    seen = []

    def unit(i):
        seen.append(i)
        clock.t += 5.0
        return {"images": 1, "steps": 1, "failed": 0}
    w = run_window(unit, 1.0, clock=clock, fence=lambda: None,
                   first_index=2, min_units=3)
    assert seen == [2, 3, 4] and len(w.units) == 3


def test_time_in_the_closing_fence_is_inside_the_interval():
    clock = FakeClock()
    state = {"n": 0}

    def fence():
        state["n"] += 1
        if state["n"] == 2:
            clock.t += 0.25         # device still draining at the close
    w = run_window(fixed_units(clock, [1.0] * 5), 2.0, clock=clock,
                   fence=fence)
    assert w.seconds == pytest.approx(2.25)
