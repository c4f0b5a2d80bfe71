"""Checkpoint/resume for training state (orbax-backed).

orbax is loaded only when ``--checkpoint-dir`` is given: this module imports
without it, and ``CheckpointManager.__init__`` is the one place that imports
``orbax.checkpoint`` (seconds of start-up, through ``google.cloud.logging``:
PERF.md §6, PR 27).  The JSON helpers here (``publish_fingerprint``, the
``read_*_meta`` readers) never need it.

The reference has NO checkpointing (no torch.save/load anywhere — SURVEY.md
§5: training state lives only in memory for the duration of a run), so this
subsystem is beyond-parity: it exists because a framework, unlike coursework
scripts, must survive preemption — the normal operating condition on TPU
pods.

Resume is EXACT: the per-epoch PRNG key is ``fold_in(seed, epoch)`` and the
reference's sampler never reshuffles across epochs (SURVEY.md C6), so
training epochs [0..k) then restoring and training [k..n) is bitwise
identical to training [0..n) in one run (pinned by
tests/test_checkpoint.py).  State on disk is the full TrainState pytree —
params, BatchNorm running stats, SGD momentum — saved per completed epoch;
orbax handles sharded/multi-host arrays natively.
"""

from __future__ import annotations

import json
import os
from typing import Optional, Tuple

import jax

from .step import TrainState

# Bumped whenever the on-disk TrainState pytree STRUCTURE changes (e.g. an
# optimizer-state field added/removed): old checkpoints cannot be restored
# across such changes, and without this stamp the failure is orbax's opaque
# structure error (or a config-digest mismatch that doesn't say WHY).
# History: 1 = SGDState carried a step counter; 2 = it doesn't;
# 3 = SGDState gained ``comm`` (gradient-compression error-feedback
# residuals / PowerSGD factors, stacked per worker — parallel/strategies).
STATE_FORMAT_VERSION = 3
# The structure every pre-stamp directory holds (the 1 -> 2 change predates
# the stamp's introduction) — what a missing stamp migrates to.
_UNSTAMPED_DIR_VERSION = 2

def _v2_structure_is_current(config: Optional[dict]) -> bool:
    """Whether a version-2 checkpoint holds this build's structure anyway.

    The 2 -> 3 bump added ``SGDState.comm`` — which is ``None`` (an empty
    pytree) for every stateless strategy, so a v2 save from such a run is
    leaf-for-leaf the structure this build stores and restores.  Refusing
    it would strand every pre-compression checkpoint for no reason; only
    the stateful tiers (compress-*/powersgd), which post-date version 2,
    genuinely need the new structure."""
    from ..parallel.strategies import STRATEGIES
    strat = STRATEGIES.get(str((config or {}).get("strategy", "")).lower())
    return strat is not None and not getattr(strat, "stateful", False)


# Mid-epoch (emergency) checkpoints are keyed by one orbax step integer
# encoding (epoch, step-within-epoch); an epoch never holds this many
# batches, so the encoding is collision-free and order-preserving.
_MID_KEY_BASE = 10 ** 6


def _atomic_write_json(path: str, obj) -> None:
    """Complete-or-absent JSON write (tmp + rename); a preemption signal
    arriving mid-write must never leave a torn metadata file."""
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


# The identity keys a published weight bundle carries (publish/): enough
# for the serving side to refuse a bundle from the wrong run/architecture,
# none of the training-only knobs (lr, augment, ...) that don't affect
# what the weights ARE.
_PUBLISH_FINGERPRINT_KEYS = ("model", "strategy", "precision", "seed",
                             "global_batch", "state_digest")


def publish_fingerprint(config: dict) -> dict:
    """Model/config identity stamped into published weight bundles —
    the same fields the checkpoint config guard validates, plus the
    state-format stamp."""
    fp = {k: config[k] for k in _PUBLISH_FINGERPRINT_KEYS if k in config}
    fp.setdefault("state_format_version", STATE_FORMAT_VERSION)
    return fp


# Config keys an ELASTIC resume is allowed to change: the whole point of
# the elastic layer is resuming at a different world size (and, under weak
# scaling, a rescaled global batch) — see cs744_ddp_tpu/elastic/.
_ELASTIC_FREE_KEYS = ("world", "global_batch")


def read_epoch_meta(directory: str) -> Optional[dict]:
    """The elastic metadata sidecar of the latest EPOCH save (world,
    global_batch, protocol, data order, per-rank keys), or None.  A
    standalone reader: the elastic coordinator re-derives membership from
    disk after ``coordinator_loss`` without constructing a manager."""
    path = os.path.join(os.path.abspath(directory), "epoch_meta.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def read_mid_epoch_meta(directory: str) -> Optional[dict]:
    """The mid-epoch (emergency) checkpoint's metadata sidecar, or None."""
    path = os.path.join(os.path.abspath(directory), "mid_epoch_meta.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


class CheckpointManager:
    """Thin orbax CheckpointManager wrapper keyed on completed epochs.

    ``config`` (a small JSON-able dict: model/strategy/seed/...) is written
    alongside the checkpoints and VALIDATED on construction when the
    directory already holds one — restoring foreign state (different model,
    seed, precision) either deep-fails inside orbax with an opaque shape
    error or, worse, silently resumes from the wrong run; this turns both
    into an immediate, explicit error.

    ``elastic=True`` relaxes exactly the two keys a world-resize resume
    legitimately changes (``world``, ``global_batch``) from the equality
    check — every other mismatch still fails.  The on-disk config is NOT
    rewritten: it keeps recording the run's ORIGINAL topology, and the
    elastic metadata sidecars carry the per-save truth.

    Constructing a manager is what imports ``orbax.checkpoint`` (kept as
    ``self._ocp`` for every other method), and that is deliberate: the
    import takes seconds, tens of them with a cold page cache, and
    ``save_mid_epoch`` runs in the grace period after SIGTERM, so it must
    never be the first importer.  ``Trainer.run`` builds its manager before
    the first step; build yours before the work a save would protect."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 config: Optional[dict] = None, *, elastic: bool = False):
        directory = os.path.abspath(directory)
        self._dir = directory
        self._mid = None  # lazy orbax manager for mid-epoch checkpoints
        self._elastic = elastic
        self._config_path = os.path.join(directory, "trainer_config.json")
        if config is not None:
            config = {**config,
                      "state_format_version": STATE_FORMAT_VERSION}
        if config is not None and os.path.exists(self._config_path):
            with open(self._config_path) as f:
                try:
                    existing = json.load(f)
                except json.JSONDecodeError as e:
                    raise ValueError(
                        f"checkpoint dir {directory} holds a corrupt "
                        f"trainer_config.json ({e}); refusing to resume from "
                        f"an unidentifiable run — delete the directory to "
                        f"start fresh") from e
            saved_ver = existing.get("state_format_version")
            needs_stamp = saved_ver is None
            if needs_stamp:
                # Dirs written before the stamp existed: the step-counter
                # removal (version 1 -> 2) predates the stamp's introduction
                # by three rounds, so every unstamped dir on disk is KNOWN to
                # hold the version-2 structure — read it as exactly that
                # (NOT blindly as the current version) and let the
                # structural migration below decide.
                saved_ver = _UNSTAMPED_DIR_VERSION
                existing["state_format_version"] = _UNSTAMPED_DIR_VERSION
            if saved_ver == _UNSTAMPED_DIR_VERSION != STATE_FORMAT_VERSION \
                    and _v2_structure_is_current(config):
                # One-time 2 -> 3 migration: the bump only changed the
                # stored structure for stateful (compressed) strategies,
                # so a stateless run's v2 dir is accepted — and re-stamped
                # as current — rather than stranded (ADVICE r4).
                saved_ver = STATE_FORMAT_VERSION
                existing["state_format_version"] = STATE_FORMAT_VERSION
            if saved_ver != STATE_FORMAT_VERSION:
                raise ValueError(
                    f"checkpoint dir {directory} holds state-format version "
                    f"{saved_ver}, but this build writes version "
                    f"{STATE_FORMAT_VERSION}; checkpoints do not survive "
                    f"TrainState structure changes — delete the directory "
                    f"to start fresh")
            if self._config_view(existing) != self._config_view(config):
                raise ValueError(
                    f"checkpoint dir {directory} belongs to a different "
                    f"training config: saved={existing}, current={config}")
            if needs_stamp and jax.process_index() == 0:
                # Persist the one-time migration stamp only AFTER both
                # validations pass: a rejected resume attempt must never
                # modify another run's on-disk metadata.
                tmp = f"{self._config_path}.{os.getpid()}.stamp.tmp"
                with open(tmp, "w") as f:
                    json.dump(existing, f)
                os.replace(tmp, self._config_path)
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self._mngr = ocp.CheckpointManager(
            directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep,
                                                 create=True),
        )
        if config is not None and not os.path.exists(self._config_path) \
                and jax.process_index() == 0:
            # Publish the config ATOMICALLY AND EXCLUSIVELY from process 0:
            # write a complete unique temp file (crash mid-write can never
            # leave a torn trainer_config.json), then hard-link it into
            # place — link fails with FileExistsError if another run won
            # the race, in which case the loser VALIDATES against the
            # winner instead of silently overwriting it (two different
            # configs racing one empty dir must not end with one of them
            # misidentified).
            tmp = f"{self._config_path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(config, f)
            try:
                os.link(tmp, self._config_path)
            except FileExistsError:
                with open(self._config_path) as f:
                    existing = json.load(f)
                if self._config_view(existing) != self._config_view(config):
                    raise ValueError(
                        f"checkpoint dir {directory} was concurrently "
                        f"claimed by a different training config: "
                        f"saved={existing}, current={config}")
            except OSError:
                # Filesystem without hard links: fall back to an atomic
                # (but last-writer-wins) rename.
                os.replace(tmp, self._config_path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)

    def _config_view(self, cfg: dict) -> dict:
        """The config as compared: under elastic mode the world-resize
        keys are excluded from equality (both sides symmetrically)."""
        if not self._elastic:
            return cfg
        return {k: v for k, v in cfg.items() if k not in _ELASTIC_FREE_KEYS}

    def latest_epoch(self) -> Optional[int]:
        """Last COMPLETED epoch saved, or None if no checkpoint exists."""
        return self._mngr.latest_step()

    def _epoch_meta_path(self) -> str:
        return os.path.join(self._dir, "epoch_meta.json")

    def save(self, epoch: int, state: TrainState,
             meta: Optional[dict] = None) -> None:
        """Persist state after ``epoch`` completed; blocks until durable.

        ``meta`` (elastic): topology/data-order sidecar for the LATEST
        epoch save — world, global_batch, protocol, per-rank data-order
        keys — written atomically after the checkpoint is durable so the
        sidecar can never describe a save that doesn't exist."""
        self._mngr.save(epoch, args=self._ocp.args.StandardSave(state))
        self._mngr.wait_until_finished()
        if meta is not None:
            _atomic_write_json(self._epoch_meta_path(),
                               {**meta, "epoch": epoch})

    def epoch_meta(self) -> Optional[dict]:
        return read_epoch_meta(self._dir)

    def mid_epoch_meta(self) -> Optional[dict]:
        return read_mid_epoch_meta(self._dir)

    def restore(self, state_like: TrainState,
                epoch: Optional[int] = None) -> Tuple[TrainState, int]:
        """(state, next_epoch_to_run); ``state_like`` supplies the pytree
        structure plus shardings (restored arrays land on the same mesh)."""
        if epoch is None:
            epoch = self.latest_epoch()
        if epoch is None:
            raise FileNotFoundError("no checkpoint to restore")
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            state_like)
        restored = self._mngr.restore(
            epoch, args=self._ocp.args.StandardRestore(abstract))
        return TrainState(*restored), epoch + 1

    # ------------------------------------------------------------------
    # Mid-epoch (emergency) checkpoints — the preemption path (ft/).
    #
    # A separate orbax manager under <dir>/mid_epoch keyed by the encoded
    # (epoch, step) holds AT MOST ONE checkpoint: the state after ``step``
    # batches of ``epoch``.  The data-order state needed to resume is fully
    # derivable from (seed, epoch, step) — the sampler is a fixed
    # permutation of (seed, epoch) and every PRNG fold uses the ABSOLUTE
    # batch index — so the sidecar meta records those plus the sampler
    # config for auditability, and restore needs only the step key.
    # ------------------------------------------------------------------

    def _mid_dir(self) -> str:
        return os.path.join(self._dir, "mid_epoch")

    def _mid_meta_path(self) -> str:
        return os.path.join(self._dir, "mid_epoch_meta.json")

    def _mid_mngr(self):
        if self._mid is None:
            self._mid = self._ocp.CheckpointManager(
                self._mid_dir(),
                options=self._ocp.CheckpointManagerOptions(
                    max_to_keep=1, create=True))
        return self._mid

    def save_mid_epoch(self, epoch: int, step: int, state: TrainState,
                       data_order: Optional[dict] = None) -> None:
        """Emergency step-level checkpoint: state after ``step`` batches of
        ``epoch``; blocks until durable (the caller is about to exit)."""
        if step >= _MID_KEY_BASE:
            raise ValueError(f"step {step} exceeds mid-epoch key space")
        m = self._mid_mngr()
        m.save(epoch * _MID_KEY_BASE + step,
               args=self._ocp.args.StandardSave(state))
        m.wait_until_finished()
        meta = {"epoch": epoch, "step": step}
        if data_order:
            meta["data_order"] = data_order
        _atomic_write_json(self._mid_meta_path(), meta)

    def latest_mid_epoch(self) -> Optional[Tuple[int, int]]:
        """(epoch, step) of the emergency checkpoint, or None.  The orbax
        step listing is the source of truth (the meta sidecar can lag by a
        crash between save and meta write)."""
        if not os.path.isdir(self._mid_dir()):
            return None
        key = self._mid_mngr().latest_step()
        if key is None:
            return None
        return divmod(key, _MID_KEY_BASE)

    def restore_mid_epoch(
            self, state_like: TrainState) -> Tuple[TrainState, int, int]:
        """(state, epoch, step): resume ``epoch`` from batch ``step``."""
        at = self.latest_mid_epoch()
        if at is None:
            raise FileNotFoundError("no mid-epoch checkpoint to restore")
        epoch, step = at
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            state_like)
        restored = self._mid_mngr().restore(
            epoch * _MID_KEY_BASE + step,
            args=self._ocp.args.StandardRestore(abstract))
        return TrainState(*restored), epoch, step

    def clear_mid_epoch(self) -> None:
        """Drop the emergency checkpoint (stale once its epoch completes)."""
        if os.path.exists(self._mid_meta_path()):
            os.unlink(self._mid_meta_path())
        if not os.path.isdir(self._mid_dir()):
            return
        m = self._mid_mngr()
        for key in list(m.all_steps()):
            try:
                m.delete(key)
            except (NotImplementedError, OSError):  # pragma: no cover
                break

    def close(self) -> None:
        if self._mid is not None:
            self._mid.close()
        self._mngr.close()
