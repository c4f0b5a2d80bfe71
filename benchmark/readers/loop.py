"""Dispatch loop (train/loop.py): round trips, idle time, unit times."""


def round_trips_per_epoch(run):
    n = run.counters.get("host_round_trips")
    return None if n is None else n / len(run.window.units)


def idle_ms_per_epoch(run):
    gaps = run.trace.get("gap_total_s_dev0")
    return None if gaps is None else 1e3 * gaps / len(run.window.units)


def epoch_ms_p50(run):
    """Median of the units' wall times: the statistic a stall does not
    move, beside the rate it does."""
    return run.window.unit_ms_p50()
