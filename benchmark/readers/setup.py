"""Set-up (entry points, compile caches), from the program's own spans.

Set-up is every span of the process-wide log (`cs744_ddp_tpu.obs.span_log()`)
that ends before the first span of the window's first unit; the warm-up
units are set-up.  The program writes a `trainer_init` span around
`Trainer.__init__`, and, through `utils/compcache.py`, one span for every
trace (`jax_trace`), lowering (`jax_lower`) and backend compile
(`xla_compile`, with `cached` True where the persistent cache served it) of
every program, each with `program` = jax's name for it.

jax's spans overlap: a jitted function called while another is traced is
traced inside it, and a compile can run inside a trace.  So the times below
are lengths of unions of intervals, never sums of spans: trace and lowering
are the union of `jax_trace` and `jax_lower` less the compiles inside it.
The compile metrics include compiles nested inside `trainer_init`.  jax's
spans under an `obs_emit` span are the recorder's own work (with a recorder
the first epoch lowers the step once more for its collective statistics)
and are left out: the metrics read what the untraced set-up does.

An untraced run records through `NULL`, and a program older than these
spans writes none: every reader then returns None.
"""

from __future__ import annotations

from benchmark.readers import hostspans

TRACE_LOWER = ("jax_trace", "jax_lower")
COMPILE = "xla_compile"
JAX_SPANS = TRACE_LOWER + (COMPILE,)
OBSERVER = hostspans.OBSERVER


def setup_spans(log, epochs) -> list:
    """The spans of `log` that end before the first span of the window's
    first unit (`epochs[0]`); [] where the window has no span."""
    window = hostspans.window_spans(log, epochs)
    starts = [s["t_ns"] for s in window if s["epoch"] == epochs[0]]
    if not starts:
        return []
    t0 = min(starts)
    return [s for s in log if s["t_ns"] + s["dur_ns"] <= t0]


def union_ns(spans) -> int:
    """Nanoseconds covered by at least one of `spans`."""
    total, hi = 0, None
    for lo, end in sorted((s["t_ns"], s["t_ns"] + s["dur_ns"])
                          for s in spans):
        if hi is None or lo > hi:
            total += end - lo
            hi = end
        elif end > hi:
            total += end - hi
            hi = end
    return total


def _setup(run) -> list:
    from cs744_ddp_tpu import obs
    if not hasattr(obs, "span_log"):
        return []
    epochs = [u.get("epoch") for u in run.window.units]
    return setup_spans(obs.span_log(), epochs)


def _jax_spans(run):
    """Set-up's jax spans, or None where it holds none; those under an
    `obs_emit` span (work a run without the recorder does not do: the
    collective statistics lower the step once) left out."""
    setup = _setup(run)
    observer = {s["id"] for s in setup if s["name"] == OBSERVER}
    spans = [s for s in setup if s["name"] in JAX_SPANS
             and s.get("parent_id") not in observer]
    return spans or None


def trainer_s(run):
    """`Trainer.__init__`: the newest `trainer_init` span of set-up."""
    spans = [s for s in _setup(run) if s["name"] == "trainer_init"]
    return spans[-1]["dur_ns"] / 1e9 if spans else None


def trace_lower_s(run):
    """Tracing and lowering, warm or cold, less the compiles inside."""
    spans = _jax_spans(run)
    if spans is None:
        return None
    compiles = [s for s in spans if s["name"] == COMPILE]
    return (union_ns(spans) - union_ns(compiles)) / 1e9


def _compile_s(run, cached: bool):
    spans = _jax_spans(run)
    if spans is None:
        return None
    return union_ns([s for s in spans if s["name"] == COMPILE
                     and (s.get("cached") is True) == cached]) / 1e9


def compile_s(run):
    """Backend compiles the persistent cache did not serve."""
    return _compile_s(run, cached=False)


def cache_load_s(run):
    """Backend compiles the persistent cache served (a load)."""
    return _compile_s(run, cached=True)


def programs_built(run):
    """`xla_compile` spans of set-up, loads from the cache included."""
    spans = _jax_spans(run)
    if spans is None:
        return None
    return sum(1 for s in spans if s["name"] == COMPILE)
