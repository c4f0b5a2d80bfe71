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
"""

from __future__ import annotations

import os

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_counts = {"hits": 0, "misses": 0}
_listener_on = False


def _default_dir() -> str:
    """``<checkout>/.jax_cache`` (the checkout is two levels above this
    file's package)."""
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _listen(event: str, **kw) -> None:
    if event == _HIT_EVENT:
        _counts["hits"] += 1
    elif event == _MISS_EVENT:
        _counts["misses"] += 1


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
