"""Persistent XLA compilation cache shared by every entry point (cli.py,
serve/, chip_smoke.py, benchmark/, tools/ and the test suite).

One function, no arguments.  Where the cache lives is decided OUTSIDE the
program when ``JAX_COMPILATION_CACHE_DIR`` is set: jax reads that variable
itself, so this module sets no directory at all and the operator's choice
stands.  Unset, the cache is the fixed ``<checkout>/.jax_cache``
(git-ignored).  The directory is part of jax's cache key, so it never
carries a pid, a timestamp or a temporary name.  Entries below the
min-compile-time threshold are not persisted.

Hit/miss accounting: jax reports cache traffic through ``jax.monitoring``
events; a process-wide listener tallies them so the per-run telemetry
manifest can record whether this run's compiles actually came from the
cache (``cache_stats`` — a silent cache regression otherwise just looks
like a slow day).

Compile spans: jax also reports, after the fact, the interval of each
trace, lowering and backend compile (``jax.monitoring``'s time spans, Unix
seconds, with ``fun_name``).  ``attach`` hands them to an enabled recorder
(``obs.Telemetry`` attaches itself when it is built; ``NULL`` never does)
as the spans ``jax_trace``, ``jax_lower`` and ``xla_compile`` with
``program`` = ``fun_name``; a compile also carries ``cached`` (True where
the persistent cache's hit event fired inside it on that thread, False
where the cache was asked and missed, absent where it is off) and counts
one ``programs_built``.  The span's parent is the span open on the calling
thread.  A jitted function traced while another is being traced (every
``jax.numpy`` function is one) lies inside the outer ``jax_trace`` and gets
no span of its own: the tiny latent decoder's set-up traces 9,220 of them
beside 166 compiles, which would push the set-up out of the span log.  The
listener goes up with the first recorder of a process: a process that
never builds one runs none of this.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import weakref

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_ASKED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
# jax._src.dispatch's event names -> the span names a recorder writes
_SPAN_NAMES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    "/jax/core/compile/backend_compile_duration": "xla_compile",
}

_counts = {"hits": 0, "misses": 0}
_listener_on = False
_spans_on = False
_recorders: list = []           # weakrefs to enabled recorders, oldest first
_verdict = threading.local()    # .last = (cached?, Unix s) of this thread


def _default_dir() -> str:
    """``<checkout>/.jax_cache`` (the checkout is two levels above this
    file's package)."""
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _listen(event: str, **kw) -> None:
    if event == _HIT_EVENT:
        _counts["hits"] += 1
        _verdict.last = (True, time.time())
    elif event == _MISS_EVENT:
        _counts["misses"] += 1
    elif event == _ASKED_EVENT:
        _verdict.last = (False, time.time())


def _recorder():
    """The live recorder with a span open on this thread, else the newest
    live one (None when every attached recorder is gone)."""
    live = [r for r in (ref() for ref in _recorders) if r is not None]
    for r in reversed(live):
        if r.open_span_id() is not None:
            return r
    return live[-1] if live else None


def _listen_span(event: str, start: float, end: float,
                 fun_name: str = "?", **kw) -> None:
    name = _SPAN_NAMES.get(event)
    if name == "jax_trace":
        from jax._src import core
        if not core.trace_state_clean():
            return      # traced inside another program's trace: covered
    rec = _recorder() if name else None
    if rec is None:
        return
    attrs = {"program": fun_name}
    if name == "xla_compile":
        last = getattr(_verdict, "last", None)
        _verdict.last = None
        if last is not None and start <= last[1] <= end:
            attrs["cached"] = last[0]
        rec.counter("programs_built", **attrs)
    rec.span_event(name, start, end - start, **attrs)


def attach(recorder) -> None:
    """Send jax's trace / lowering / compile intervals to ``recorder`` (held
    weakly) from now on.  Registers the listener once per process, and only
    where jax is imported already: a process without jax compiles
    nothing."""
    global _spans_on
    jax = sys.modules.get("jax")
    if jax is None:
        return
    _recorders[:] = [r for r in _recorders if r() is not None]
    _recorders.append(weakref.ref(recorder))
    if not _spans_on:
        jax.monitoring.register_event_time_span_listener(_listen_span)
        _spans_on = True


def enable_persistent_compilation_cache() -> None:
    """Turn the persistent cache on (idempotent).  A cache that cannot be
    enabled raises: every caller's cold-start time depends on it."""
    global _listener_on
    import jax

    if not os.environ.get(_ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", _default_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    if not _listener_on:
        from jax import monitoring
        monitoring.register_event_listener(_listen)
        _listener_on = True


def cache_stats() -> dict:
    """Effective cache location (as jax holds it) + hit/miss tallies since
    the listener went up — recorded in the telemetry run manifest (cli.py)
    so compile-cache regressions are visible per run."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir if _listener_on else None
    return {"dir": cache_dir, "enabled": cache_dir is not None,
            "hits": _counts["hits"], "misses": _counts["misses"]}
