"""Gradient sync (parallel/strategies.py): collective time on device 0."""


def collective_ms_per_step(run):
    t = run.trace.get("collective_s_dev0")
    return 1e3 * t / run.window.total("steps") if t else None


def exposed_ms_per_step(run):
    """The part of it during which nothing else runs on that device."""
    if not run.trace.get("collective_s_dev0"):
        return None
    return 1e3 * run.trace["collective_exposed_s_dev0"] \
        / run.window.total("steps")
