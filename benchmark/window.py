"""The measured window: whole units between two fences.

A unit is whatever the traffic driver calls one (for `train-epochs`: one
epoch's train windows, ragged tail step and evaluation).  The harness
fences, reads the clock, runs units until the clock passes `seconds`,
fences and reads the clock again.  The work counted is that of the units
run and the divisor is the measured interval, never the nominal length: no
unit is cut, dropped or counted in part, so the edge of the window cannot
move the rate.  No JAX here: the clock and the fence are arguments, and the
tests drive this loop on a fake clock.
"""

from __future__ import annotations

import statistics
from typing import Callable, List


class Phases:
    """Set-up breakdown: seconds per named phase, in order."""

    def __init__(self, t_start: float, clock: Callable[[], float]):
        self.clock = clock
        self.rows = []
        self._last = t_start

    def mark(self, name: str) -> None:
        now = self.clock()
        self.rows.append([name, now - self._last])
        self._last = now


class Window:
    """What one measured window did."""

    def __init__(self, units: List[dict], t_open: float, t_close: float):
        self.units = units
        self.t_open = t_open
        self.t_close = t_close

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def total(self, key: str) -> float:
        return sum(u[key] for u in self.units)

    def rate(self, key: str = "images") -> float:
        """All the work of the window over all its time."""
        return self.total(key) / self.seconds

    def unit_seconds(self) -> List[float]:
        return [u["seconds"] for u in self.units]

    def unit_ms_p50(self) -> float:
        return 1e3 * statistics.median(self.unit_seconds())

    def to_json(self) -> dict:
        return {"t_open": self.t_open, "t_close": self.t_close,
                "seconds": self.seconds, "units": self.units}


def run_window(unit: Callable[[int], dict], seconds: float, *,
               clock: Callable[[], float], fence: Callable[[], None],
               first_index: int = 0, min_units: int = 1) -> Window:
    """Run whole units until `seconds` have passed (and at least
    `min_units`).  `unit(i)` does the i-th unit's work and returns its
    counts (at least "images" and "steps"); this loop adds the unit's own
    wall `seconds` and its start relative to the opening fence."""
    fence()
    t_open = clock()
    units: List[dict] = []
    while True:
        t0 = clock()
        rec = dict(unit(first_index + len(units)))
        t1 = clock()
        rec["start"] = t0 - t_open
        rec["seconds"] = t1 - t0
        units.append(rec)
        if len(units) >= min_units and t1 - t_open >= seconds:
            break
    fence()
    return Window(units, t_open, clock())
