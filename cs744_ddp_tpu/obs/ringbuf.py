"""Device-resident metric ring buffer (ISSUE 8 tentpole a).

A fixed-capacity f32 ring of shape ``(capacity, N_METRICS)`` plus an i32
write counter, carried through the windowed training scan as part of the
donated carry.  Every scanned step writes one row via
``lax.dynamic_update_slice``; the host fetches the whole buffer ONCE per
window (a single ``np.asarray`` = one device round-trip) and reconstructs
per-step rows — including absolute step indices — from the ``marker``
column, instead of syncing per step.

Columns (see :data:`METRICS`):

- ``loss``         — the per-step scalar loss, bitwise-identical to what
                     the non-ring path stacks into the scan's ys (the
                     ring only observes; it never perturbs the math).
- ``grad_sqnorm``  — global post-sync gradient sqnorm (sum over leaves of
                     ``sum(g*g)``), replicated so the write is identical
                     on every shard.
- ``ok``           — the non-finite guard verdict (1.0 = applied); 1.0
                     when the guard is off.
- ``marker``       — the absolute batch index as f32.  Exact for indices
                     < 2**24, checked at drain; a run long enough to
                     break that would overflow the epoch counter first.

The write counter counts TOTAL writes (it is not reduced mod capacity on
device), so the host can detect overwrite and handle wraparound without a
second fetch.  ``capacity`` must be >= the largest window length or rows
would be overwritten before the drain — validated by the Trainer.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

METRICS = ("loss", "grad_sqnorm", "ok", "marker")
N_METRICS = len(METRICS)
DEFAULT_CAPACITY = 64          # >= WINDOW (20) with slack for ragged tails
_MARKER_EXACT = float(2 ** 24)  # largest exactly-representable f32 int


def make_ring(capacity: int = DEFAULT_CAPACITY, extras: int = 0):
    """Fresh (buffer, write-counter) pair.  Plain jnp arrays: the caller's
    jit placement (replicated specs in the shard_map builds) commits them;
    imported lazily so host-only consumers never pull in jax.  `extras`
    widens the row by a model's own per-step scalars (a decoder's routed
    rows and masked tokens), after the four columns every model has."""
    import jax.numpy as jnp
    if capacity < 1:
        raise ValueError(f"ring capacity must be >= 1, got {capacity}")
    return (jnp.zeros((capacity, N_METRICS + extras), jnp.float32),
            jnp.zeros((), jnp.int32))


def ring_write(ring, values):
    """Write one row (a tuple of scalars as wide as the ring's row, any
    real dtype) at the current slot; returns the advanced ring.  Traced
    inside the scan body — one dynamic-update-slice, no host sync."""
    import jax.numpy as jnp
    from jax import lax
    buf, count = ring
    width = buf.shape[1]
    if len(values) != width:
        raise ValueError(f"expected {width} metrics, got {len(values)}")
    row = jnp.stack([jnp.asarray(v, jnp.float32).reshape(())
                     for v in values]).reshape(1, width)
    slot = lax.rem(count, jnp.int32(buf.shape[0]))
    return (lax.dynamic_update_slice(buf, row, (slot, jnp.int32(0))),
            count + jnp.int32(1))


def drain_rows(buf_host, writes_total: int, count: int) -> np.ndarray:
    """Last ``count`` written rows in write order, from a host copy of the
    buffer.  ``writes_total`` is the host-tracked cumulative write count
    (tracking it host-side keeps the drain at exactly one device fetch —
    the buffer itself).  Handles wraparound; refuses overwritten reads."""
    buf = np.asarray(buf_host)
    cap = buf.shape[0]
    if count > cap:
        raise ValueError(
            f"drain of {count} rows exceeds ring capacity {cap}: rows were "
            "overwritten before the drain (raise --metrics-ring)")
    if count > writes_total:
        raise ValueError(
            f"drain of {count} rows exceeds total writes {writes_total}")
    idx = np.arange(writes_total - count, writes_total) % cap
    return buf[idx]


def marker_steps(rows: np.ndarray) -> np.ndarray:
    """Absolute step indices from the marker column, validated exact."""
    markers = rows[:, METRICS.index("marker")]
    if markers.size and float(np.max(markers)) >= _MARKER_EXACT:
        raise ValueError("ring marker exceeded exact-f32 integer range")
    return markers.astype(np.int64)


def split_columns(rows: np.ndarray) -> Tuple[np.ndarray, ...]:
    """(loss, grad_sqnorm, ok, steps) column views of drained rows."""
    return (rows[:, 0], rows[:, 1], rows[:, 2], marker_steps(rows))
