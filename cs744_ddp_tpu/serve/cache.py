"""Warm-start executable cache: serialize compiled XLA executables to disk.

Two layers make a restarted server skip compile:

  * the repo-wide persistent XLA compilation cache
    (``utils/compcache.enable_persistent_compilation_cache``) — enabled by
    the engine at startup; it dedupes compiles ACROSS programs but still
    pays lowering + cache lookup per bucket, and only persists compiles
    over its 2 s threshold;
  * this module — the whole compiled executable (``jax.jit(...).lower()
    .compile()``) serialized via ``jax.experimental.serialize_executable``
    and reloaded with zero XLA work, keyed by everything the executable
    depends on (model/abstract-arg digest, bucket, dtype, jax version,
    backend, device kind) — but NOT by the device it was compiled for.

Loading FOR A DEVICE.  A ladder rung is a single-device program; N replicas
on N chips need it loaded N times, once per chip.  jax's public
``deserialize_and_load`` cannot do that on the TPU: it resolves the payload's
devices by id among the ``execution_devices`` it is handed (all local devices
by default — an executable expecting one shard per local device), and it
loads the PJRT executable WITHOUT compile options, which libtpu 0.0.34
answers by assigning the program to TPU_0 whatever device it was compiled
for (``Buffer passed to Execute() ... is on device TPU_3, but replica is
assigned to device TPU_0`` — four v5e chips, PR 21; the CPU client restores
the original assignment, so only a multi-chip host shows it).  ``_OnDevice``
therefore unpickles the payload itself and hands PJRT compile options that
carry the TARGET device assignment — what jax's own persistent compilation
cache does on a hit — mapping every device in the payload to the target.
Measured on the same host: an entry compiled for TPU_0 loads and runs on
TPU_3, so one entry serves every replica.  This leans on jax 0.9.0
internals (the payload's three persistent ids, ``compiler.get_compile_
options``); tests/test_serve.py pins the round trip on a device >= 1 and
``chip_smoke.py`` pins it on every real chip.

Entries are pickles of ``(payload_bytes, in_tree, out_tree)`` written
atomically (tmp + ``os.replace``) so a killed startup never leaves a torn
entry; a stale or undeserializable entry is treated as a miss and
recompiled over.
"""

from __future__ import annotations

import hashlib
import io
import os
import pickle
import threading
from typing import Any, Optional

import jax
import numpy as np
from jax._src import compiler as _compiler
from jax._src.lib import xla_client as _xc
from jax.experimental import serialize_executable as _se


def cache_key(**fields) -> str:
    """Stable filename for an executable: sha256 over the sorted field
    repr (model digest, bucket, dtype, jax/backend identity)."""
    blob = repr(sorted(fields.items())).encode()
    return hashlib.sha256(blob).hexdigest()[:32]


class _OnDevice(pickle.Unpickler):
    """Unpickle a ``serialize_executable`` payload onto ONE device (see the
    module docstring): the executable is loaded under compile options that
    assign it to ``device``, and every device the payload names becomes
    ``device``."""

    def __init__(self, file, device):
        super().__init__(file)
        self._device = device

    def persistent_load(self, pid):
        kind = pid[0]
        if kind == "exec":
            options = _compiler.get_compile_options(
                num_replicas=1, num_partitions=1,
                device_assignment=np.array([[self._device]], dtype=object),
                backend=self._device.client)
            return self._device.client.deserialize_executable(
                pid[1], executable_devices=_xc.DeviceList((self._device,)),
                compile_options=options)
        if kind == "device":
            return self._device
        if kind == "client":
            return self._device.client
        raise pickle.UnpicklingError(f"unknown persistent id {pid!r}")


def _load_on_device(payload: bytes, in_tree, out_tree, device):
    """``serialize_executable.deserialize_and_load`` for a single-device
    program, placed on ``device``."""
    unloaded, args_info_flat, no_kwargs = _OnDevice(
        io.BytesIO(payload), device).load()
    return jax.stages.Compiled(
        unloaded.load(), [], in_tree.unflatten(args_info_flat), out_tree,
        no_kwargs=no_kwargs)


class ExecutableCache:
    """Directory of serialized executables; ``None`` dir disables it."""

    def __init__(self, cache_dir: Optional[str]):
        self.cache_dir = cache_dir
        self.hits = 0
        self.misses = 0
        if cache_dir is not None:
            os.makedirs(cache_dir, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.cache_dir, f"exec_{key}.pkl")

    def load(self, key: str, device) -> Optional[Any]:
        """Deserialize the executable for ``key`` and load it onto
        ``device``; None on miss or any deserialization failure (a stale
        entry from another jax/device kind is a miss, not an error)."""
        if self.cache_dir is None:
            return None
        path = self._path(key)
        if not os.path.exists(path):
            self.misses += 1
            return None
        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            loaded = _load_on_device(payload, in_tree, out_tree, device)
        except Exception:  # noqa: BLE001 - any unreadable entry is a miss
            self.misses += 1
            return None
        self.hits += 1
        return loaded

    def save(self, key: str, compiled) -> None:
        """Serialize ``compiled`` under ``key`` (no-op without a dir)."""
        if self.cache_dir is None:
            return
        payload, in_tree, out_tree = _se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        path = self._path(key)
        # Replicas share keys, so two threads may save the same entry.
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)

    def stats(self) -> dict:
        return {"dir": self.cache_dir, "hits": self.hits,
                "misses": self.misses}
