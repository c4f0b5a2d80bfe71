"""Runtime: multi-host bootstrap + device-mesh construction.

Replaces the reference's process-group bootstrap
(``init_process`` — ``/root/reference/src/Part 2a/main.py:148-153``: export
MASTER_ADDR/MASTER_PORT, ``dist.init_process_group('gloo', rank, world)``)
with the TPU-native equivalents:

  * ``jax.distributed.initialize(coordinator_address, num_processes,
    process_id)`` — DCN rendezvous; on TPU pods topology is auto-discovered.
  * a 1-D ``jax.sharding.Mesh`` over all chips, axis name ``"data"`` — the
    data-parallel axis every collective rides (ICI within a slice).

Unlike the reference (one OS process per worker, eager Gloo calls), the unit
of parallelism is the *device*: one process drives all its local chips and the
strategies are collectives inside one compiled SPMD program.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           port: int = 6585) -> None:
    """Multi-host rendezvous (MASTER_ADDR:6585 ≙ coordinator:port).

    No-op when single-process (the reference's Part 1 case).  The hardcoded
    default port 6585 mirrors ``Part 2a/main.py:172``.
    """
    if (num_processes or 1) <= 1:
        return
    if coordinator is None:
        # The reference makes --master required (Part 2a/main.py:158-159);
        # silently training N independent copies would be wrong.
        raise ValueError("multi-process run (num_processes "
                         f"= {num_processes}) requires a coordinator address")
    # Cross-process collectives on the CPU backend need an implementation;
    # gloo — the reference's own backend (Part 2a/main.py:148) — is the
    # fitting choice.  Inert for TPU meshes (collectives ride ICI/DCN).
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    addr = coordinator if ":" in coordinator else f"{coordinator}:{port}"
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=num_processes,
                               process_id=process_id)


def make_mesh(num_devices: Optional[int] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D data-parallel mesh over ``num_devices`` (default: all)."""
    if devices is None:
        devices = jax.devices()
        if num_devices is not None:
            if num_devices > len(devices):
                raise ValueError(
                    f"requested {num_devices} devices, have {len(devices)}")
            devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def probe_devices(mesh: Mesh) -> list:
    """Health-probe every device in ``mesh``: run a tiny computation on each
    and return the list of rank indices that FAILED it.

    This is the elastic coordinator's liveness check — after a
    ``RankDeathError`` (or any suspicion of a sick chip) it probes before
    deciding which rung of the degradation ladder applies: an empty list
    means the fault was transient (retry at the same world), a non-empty
    list names the ranks to exclude when shrinking.  On the CPU virtual
    mesh every device always passes; real failures are simulated by the
    ``rank_death`` chaos site, whose target rank the coordinator merges
    into this probe's result.
    """
    dead = []
    for rank, dev in enumerate(mesh.devices.flat):
        try:
            out = jax.device_put(np.int32(rank), dev)
            if int(out) != rank:
                dead.append(rank)
        except Exception:  # noqa: BLE001 - any failure marks the rank dead
            dead.append(rank)
    return dead


def shrink_mesh(mesh: Mesh, new_world: int, exclude: Sequence[int] = ()) \
        -> Mesh:
    """A 1-D mesh over the first ``new_world`` SURVIVING devices of ``mesh``.

    ``exclude`` lists dead rank indices (from ``probe_devices`` or the
    chaos plan); survivors keep their relative order so rank identities
    stay stable across the shrink — the resume planner's re-shard map
    depends only on (old_world, new_world), never on which physical chips
    remain.
    """
    flat = list(mesh.devices.flat)
    survivors = [d for r, d in enumerate(flat) if r not in set(exclude)]
    if new_world > len(survivors):
        raise ValueError(f"cannot shrink to world {new_world}: only "
                         f"{len(survivors)} of {len(flat)} devices survive")
    if new_world < 1:
        raise ValueError(f"new world must be >= 1, got {new_world}")
    return Mesh(np.asarray(survivors[:new_world]), (DATA_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [global_batch, ...] arrays: split dim 0 over the mesh."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def put_global(array, sharding: NamedSharding) -> jax.Array:
    """Place a host array as a GLOBAL array under ``sharding``, safely on
    meshes that span multiple processes.

    Single-process: a plain ``device_put`` (the fast batched-transfer path).
    Multi-process: ``device_put`` of a host-global value raises on meshes
    containing non-addressable devices, so each process instead feeds only
    its addressable devices' index-slices via ``make_array_from_callback``.
    Every process passes the same host value — the framework's seed-identical
    invariant (SURVEY.md C12: the reference relies on identical seeds instead
    of a broadcast), which makes the per-process slices globally consistent.
    """
    if jax.process_count() == 1:
        return jax.device_put(array, sharding)
    array = np.asarray(array)
    return jax.make_array_from_callback(
        array.shape, sharding, lambda idx: array[idx])


def put_global_tree(tree, sharding: NamedSharding):
    """``put_global`` over every leaf of a pytree (e.g. a TrainState)."""
    return jax.tree.map(lambda a: put_global(a, sharding), tree)
