"""Timing/metrics instrumentation with reference-parity reporting.

The reference brackets forward and backward+sync+step with ``time.time()``,
averages over 20-iteration windows, skips the FIRST window from the timing
report (compilation/warmup), and prints running loss every 20 iterations
(``/root/reference/src/Part 1/main.py:28-57``).  This module reproduces that
schedule exactly — the caller is responsible for fencing each timed region
with a VALUE FETCH (``np.asarray``/``float``) or ``jax.block_until_ready``
so the timers measure real device work rather than async dispatch
(SURVEY.md §5 "Tracing / profiling").
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional

from ..obs import NULL

WINDOW = 20  # reference: report every 20 iterations, skip the first window


class WindowedTimers:
    """Per-phase accumulators over 20-iteration windows, warmup excluded.

    ``telemetry`` mirrors every recorded iteration into the structured event
    log ALONGSIDE the reference-parity prints — the stdout schedule is the
    parity surface and is never altered by the recorder (guarded emit: the
    default ``NULL`` recorder costs nothing per step).
    """

    def __init__(self, log: Callable[[str], None] = print, *,
                 telemetry=NULL, epoch: int = 0):
        self.log = log
        self.telemetry = telemetry
        self.epoch = epoch
        self.iter_number = 1
        self.epoch_loss = 0.0
        self.forward_time = 0.0
        self.backward_time = 0.0
        self.total_time = 0.0
        # Full per-iteration loss trajectory (the reference's convergence
        # oracle, SURVEY.md §4) — what equivalence tests compare.
        self.losses: List[float] = []
        # Steady-state samples (first window excluded) for throughput calc.
        self.steady_step_times: List[float] = []
        self.steady_forward_times: List[float] = []

    def record(self, loss: float, step_time: float,
               forward_time: Optional[float] = None, *,
               steady: bool = True, extra: Optional[dict] = None) -> None:
        """Record one iteration. ``forward_time`` is optional because the
        functional step is a single fused program; when the trainer runs the
        split-phase timing mode it supplies both phases (the reference's
        'backward' bucket likewise absorbs sync+step, Part 2a/main.py:92-97).

        ``steady=False`` keeps the sample in the print schedule and epoch
        totals but OUT of the steady-state stats — used for the windowed
        path's ragged tail, whose lone per-dispatch sample carries a whole
        dispatch + fetch that the amortized per-window samples share over
        20 iterations (one outlier per epoch would skew the derived
        throughput).

        ``extra`` merges additional fields into the telemetry step event
        (ring-drain rows carry grad sqnorm + reconstructed step index);
        the stdout print schedule never changes with it.
        """
        self.epoch_loss += loss
        self.losses.append(loss)
        self.total_time += step_time
        warmup = self.iter_number <= WINDOW
        if self.telemetry.enabled:
            self.telemetry.step(
                epoch=self.epoch, iter=self.iter_number, loss=float(loss),
                step_time=step_time, forward_time=forward_time,
                steady=not warmup and steady, **(extra or {}))
        if forward_time is not None:
            self.forward_time += forward_time
            self.backward_time += step_time - forward_time
            if not warmup and steady:
                self.steady_forward_times.append(forward_time)
        if not warmup and steady:
            self.steady_step_times.append(step_time)

        if self.iter_number % WINDOW == 0:
            self.log(f"Training loss after {self.iter_number} iterations is "
                     f"{self.epoch_loss / WINDOW}")
            self.epoch_loss = 0.0
            if self.iter_number != WINDOW:  # reference warmup skip (main.py:51)
                if forward_time is not None:
                    self.log(f"Forward Pass time in iter {self.iter_number} "
                             f"is {self.forward_time / WINDOW}")
                    self.log(f"Backward Pass time in iter {self.iter_number} "
                             f"is {self.backward_time / WINDOW}")
                self.log(f"Average Pass time in iter {self.iter_number} is "
                         f"{self.total_time / WINDOW}")
            self.forward_time = 0.0
            self.backward_time = 0.0
            self.total_time = 0.0
        self.iter_number += 1

    def steady_images_per_sec(self, global_batch: int) -> Optional[float]:
        if not self.steady_step_times:
            return None
        return global_batch * len(self.steady_step_times) / sum(
            self.steady_step_times)


class Stopwatch:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.time() - self.t0
        return False
