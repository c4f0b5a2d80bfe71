"""Device and compile caches."""


def idle_share(run):
    busy = run.trace.get("busy_s")
    return 100.0 * (1.0 - busy / run.window.seconds) if busy else None


def peak_hbm_bytes(run):
    return run.memory_peak_bytes


def compiles_in_window(run):
    return run.counters["compiles_in_window"]
