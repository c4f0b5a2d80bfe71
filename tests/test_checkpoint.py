"""Checkpoint/resume: bitwise-exact continuation (beyond-parity subsystem).

The reference keeps training state only in memory (no torch.save/load —
SURVEY.md §5).  Here the full TrainState (params, BN running stats, SGD
momentum) persists per completed epoch, and resume is EXACT: the per-epoch
key is fold_in(seed, epoch) and the sampler never reshuffles (C6), so
[0..k) + restore + [k..n) must equal [0..n) in one run, bit for bit.
"""

import numpy as np
import pytest

import jax

from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.train.loop import Trainer

from test_import_graph import loaded_after
from tinynet import tiny_cnn


def shrink(tr, n=256):
    tr.train_split = cifar10.Split(tr.train_split.images[:n],
                                   tr.train_split.labels[:n])
    tr.test_split = cifar10.Split(tr.test_split.images[:128],
                                  tr.test_split.labels[:128])


def make(tmp_path, mesh, **kw):
    tr = Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh,
                 global_batch=64, data_dir=str(tmp_path), augment=True,
                 limit_eval_batches=1, log=lambda s: None, **kw)
    shrink(tr)
    return tr


def test_resume_is_bitwise_exact(tmp_path, mesh4):
    ckpt = tmp_path / "ckpt"

    # Continuous 3-epoch run (no checkpointing).
    tr_ref = make(tmp_path, mesh4)
    tr_ref.run(3)

    # 2 epochs with checkpointing...
    tr_a = make(tmp_path, mesh4)
    tr_a.run(2, checkpoint_dir=str(ckpt))

    # ...then a FRESH process-equivalent Trainer resumes epoch 2.
    lines = []
    tr_b = make(tmp_path, mesh4)
    tr_b.log = lines.append
    tr_b.run(3, checkpoint_dir=str(ckpt))
    assert any("Resumed from checkpoint: epoch 2" in l for l in lines)

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        tr_ref.state, tr_b.state)


def test_restore_errors_without_checkpoint(tmp_path, mesh4):
    import pytest
    from cs744_ddp_tpu.train.checkpoint import CheckpointManager
    mngr = CheckpointManager(str(tmp_path / "empty"))
    assert mngr.latest_epoch() is None
    tr = make(tmp_path, mesh4)
    with pytest.raises(FileNotFoundError):
        mngr.restore(tr.state)
    mngr.close()


def test_checkpoint_dir_rejects_foreign_config(tmp_path, mesh4):
    """Reusing a checkpoint dir under a different training config must fail
    loudly, not deep-fail in orbax or silently resume foreign state."""
    import pytest
    ckpt = str(tmp_path / "ckpt")
    tr = make(tmp_path, mesh4)
    tr.run(1, checkpoint_dir=ckpt)

    tr2 = Trainer(model=tiny_cnn(), strategy="allreduce", mesh=mesh4,
                  global_batch=64, data_dir=str(tmp_path), augment=True,
                  limit_eval_batches=1, log=lambda s: None)
    shrink(tr2)
    with pytest.raises(ValueError, match="different training config"):
        tr2.run(2, checkpoint_dir=ckpt)


def test_run_with_all_epochs_checkpointed_logs_and_exits(tmp_path, mesh4):
    ckpt = str(tmp_path / "ckpt")
    tr = make(tmp_path, mesh4)
    tr.run(1, checkpoint_dir=ckpt)
    lines = []
    tr2 = make(tmp_path, mesh4)
    tr2.log = lines.append
    tr2.run(1, checkpoint_dir=ckpt)
    assert any("nothing to run" in l for l in lines)


def test_checkpoint_dir_rejects_different_hyperparameters(tmp_path, mesh4):
    """Resume with a different lr must fail the config guard — a silent
    optimizer swap would break the bitwise-exact-resume contract."""
    import pytest
    from cs744_ddp_tpu.ops import sgd
    ckpt = str(tmp_path / "ckpt")
    tr = make(tmp_path, mesh4)
    tr.run(1, checkpoint_dir=ckpt)

    tr2 = Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh4,
                  global_batch=64, data_dir=str(tmp_path), augment=True,
                  sgd_cfg=sgd.SGDConfig(lr=0.001), limit_eval_batches=1,
                  log=lambda s: None)
    shrink(tr2)
    with pytest.raises(ValueError, match="different training config"):
        tr2.run(2, checkpoint_dir=ckpt)


def test_unstamped_checkpoint_dir_accepted_as_current_version(tmp_path,
                                                              mesh4):
    """Dirs written before the state_format_version stamp existed hold the
    version-2 structure (the 1->2 change predates the stamp).  For a
    stateless strategy that IS the current structure (the 2->3 bump only
    added ``SGDState.comm``, an empty pytree when stateless), so a missing
    stamp must be accepted — a one-time migration — rather than refusing
    resume (ADVICE r4)."""
    import json
    import os
    ckpt = str(tmp_path / "ckpt")
    tr = make(tmp_path, mesh4)
    tr.run(1, checkpoint_dir=ckpt)
    state_after_1 = jax.tree.map(np.asarray, tr.state)

    # Strip the stamp, simulating a pre-stamp dir.
    cfg_path = os.path.join(ckpt, "trainer_config.json")
    with open(cfg_path) as f:
        cfg = json.load(f)
    del cfg["state_format_version"]
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)

    lines = []
    tr2 = make(tmp_path, mesh4)
    tr2.log = lines.append
    tr2.run(2, checkpoint_dir=ckpt)  # must resume, not raise
    # Resume actually happened (a silent fresh start would also train, so
    # the log line is the discriminating evidence) and training continued.
    assert any("Resumed from checkpoint: epoch 1" in l for l in lines), lines
    d = max(
        float(np.max(np.abs(a - np.asarray(b)))) if a.size else 0.0
        for a, b in zip(jax.tree.leaves(state_after_1),
                        jax.tree.leaves(jax.tree.map(np.asarray, tr2.state))))
    assert d > 0.0  # trained past the restored epoch
    # The one-time migration stamped the dir as the CURRENT version (the
    # stateless v2 structure is leaf-for-leaf the v3 structure).
    from cs744_ddp_tpu.train.checkpoint import STATE_FORMAT_VERSION
    with open(cfg_path) as f:
        assert json.load(f)["state_format_version"] == STATE_FORMAT_VERSION


def test_unstamped_dir_rejected_for_stateful_strategy(tmp_path, mesh4):
    """The 2->3 migration is CONDITIONAL: a stateful (compressed) strategy
    stores error-feedback state in ``SGDState.comm``, so its structure is
    genuinely version 3 — an unstamped (v2-structured) dir must still be
    refused rather than deep-failing inside orbax on a structure
    mismatch."""
    import json
    import os
    import pytest
    ckpt = str(tmp_path / "ckpt")
    tr = Trainer(model=tiny_cnn(), strategy="compress-bf16", mesh=mesh4,
                 global_batch=64, data_dir=str(tmp_path), augment=True,
                 limit_eval_batches=1, log=lambda s: None)
    shrink(tr)
    tr.run(1, checkpoint_dir=ckpt)

    cfg_path = os.path.join(ckpt, "trainer_config.json")
    with open(cfg_path) as f:
        cfg = json.load(f)
    del cfg["state_format_version"]
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)

    tr2 = Trainer(model=tiny_cnn(), strategy="compress-bf16", mesh=mesh4,
                  global_batch=64, data_dir=str(tmp_path), augment=True,
                  limit_eval_batches=1, log=lambda s: None)
    shrink(tr2)
    with pytest.raises(ValueError, match="state-format version"):
        tr2.run(2, checkpoint_dir=ckpt)
    # A rejected resume never modifies the dir's metadata.
    with open(cfg_path) as f:
        assert "state_format_version" not in json.load(f)


# -- round 6: elastic metadata forward/backward compatibility ----------------
#
# Backward: checkpoints written BEFORE the elastic layer carry no topology
# metadata and must restore as world=1 with a one-time warning.  Forward:
# the round-6 sidecars must not break old-style (non-elastic) resume, and
# the elastic config guard relaxes exactly the world/global-batch keys.

def _elastic_make(tmp_path, world, *, ft=None, log=None):
    import cs744_ddp_tpu.train.loop as looplib
    from cs744_ddp_tpu.parallel import make_mesh
    assert looplib.WINDOW == 3, "callers must monkeypatch WINDOW first"
    return Trainer(model=tiny_cnn(), strategy="allreduce",
                   mesh=make_mesh(world), global_batch=64,
                   data_dir=str(tmp_path), seed=3, augment=True,
                   limit_train_batches=6, limit_eval_batches=1,
                   log=log or (lambda s: None), ft=ft, elastic="strong")


def test_pre_elastic_mid_epoch_checkpoint_resumes_world1_warns(
        tmp_path, monkeypatch):
    import json
    import os

    import pytest

    import cs744_ddp_tpu.train.loop as looplib
    from cs744_ddp_tpu.elastic import protocol as protolib
    from cs744_ddp_tpu.ft import ChaosPlan, FTConfig
    monkeypatch.setattr(looplib, "WINDOW", 3)

    ck = str(tmp_path / "ck")
    tr1 = _elastic_make(tmp_path, 1,
                        ft=FTConfig(chaos=ChaosPlan.parse(["preempt:3"])))
    tr1.run(1, checkpoint_dir=ck)
    assert tr1.preempted is True

    # Rewrite the mid-epoch sidecar into its pre-round-6 shape: resume
    # keys only, no world/global_batch/rank_keys.
    meta_path = os.path.join(ck, "mid_epoch_meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    order = meta["data_order"]
    meta["data_order"] = {k: order[k] for k in
                          ("seed", "epoch", "step", "reshuffle_each_epoch")}
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    monkeypatch.setattr(protolib, "_warned_missing_world", False)
    lines = []
    tr2 = _elastic_make(tmp_path, 1, log=lines.append)
    with pytest.warns(UserWarning, match="no world size"):
        tr2.run(1, checkpoint_dir=ck)
    assert any("Resumed from mid-epoch checkpoint: epoch 0, step 3" in l
               for l in lines)
    assert tr2.resume_plan.old_world == 1          # the compat default
    assert tr2.resume_plan.start_step == 3

    # Bitwise vs a never-interrupted run of the same elastic config.
    tr0 = _elastic_make(tmp_path, 1)
    tr0.run(1)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        tr2.state, tr0.state)


def test_elastic_checkpoint_readable_by_non_elastic_trainer(tmp_path,
                                                            monkeypatch):
    """Forward direction: the round-6 epoch sidecar rides ALONGSIDE the
    state — an old-style (non-elastic) trainer of the same config resumes
    it without noticing."""
    import cs744_ddp_tpu.train.loop as looplib
    monkeypatch.setattr(looplib, "WINDOW", 3)

    ck = str(tmp_path / "ck")
    tr1 = _elastic_make(tmp_path, 1)
    tr1.run(1, checkpoint_dir=ck)

    from cs744_ddp_tpu.parallel import make_mesh
    lines = []
    tr2 = Trainer(model=tiny_cnn(), strategy="allreduce",
                  mesh=make_mesh(1), global_batch=64,
                  data_dir=str(tmp_path), seed=3, augment=True,
                  limit_train_batches=6, limit_eval_batches=1,
                  log=lines.append)
    tr2.run(2, checkpoint_dir=ck)                  # must resume, not raise
    assert any("Resumed from checkpoint: epoch 1" in l for l in lines)


def test_elastic_config_guard_frees_world_nonelastic_still_rejects(
        tmp_path, monkeypatch):
    import pytest

    import cs744_ddp_tpu.train.loop as looplib
    monkeypatch.setattr(looplib, "WINDOW", 3)

    ck = str(tmp_path / "ck")
    tr1 = _elastic_make(tmp_path, 2)
    tr1.run(1, checkpoint_dir=ck)

    # Elastic manager: a world change is exactly what resume is FOR.
    lines = []
    tr2 = _elastic_make(tmp_path, 1, log=lines.append)
    tr2.run(2, checkpoint_dir=ck)
    assert any("Resumed from checkpoint: epoch 1" in l for l in lines)

    # Non-elastic manager over the same dir: the world key is back in the
    # config equality, so the mismatch fails loudly.
    from cs744_ddp_tpu.parallel import make_mesh
    tr3 = Trainer(model=tiny_cnn(), strategy="allreduce",
                  mesh=make_mesh(1), global_batch=64,
                  data_dir=str(tmp_path), seed=3, augment=True,
                  limit_train_batches=6, limit_eval_batches=1,
                  log=lambda s: None)
    with pytest.raises(ValueError, match="different training config"):
        tr3.run(2, checkpoint_dir=ck)


_ORBAX_AT_CONSTRUCTION = """
from cs744_ddp_tpu.train.checkpoint import CheckpointManager
assert "orbax.checkpoint" not in sys.modules, "loaded by the module import"
mngr = CheckpointManager(tmp + "/ck")
assert "orbax.checkpoint" in sys.modules, "not loaded by __init__"
assert mngr._mid is None and mngr.latest_epoch() is None    # nothing saved
mngr.close()
"""


def test_orbax_is_imported_by_manager_construction_not_by_a_save(tmp_path):
    """``save_mid_epoch`` runs in the grace period after SIGTERM: the
    seconds-long orbax import must be paid when the manager is built, so a
    save can never be its first importer.  A subprocess: this worker's
    other tests have loaded orbax already.  Also the probe's own check:
    it does see orbax once something imports it."""
    assert "orbax.checkpoint" in loaded_after(_ORBAX_AT_CONSTRUCTION,
                                              tmp_path)


@pytest.mark.parametrize("checkpointed", [True, False])
def test_checkpoint_open_span_only_when_a_manager_is_built(
        tmp_path, mesh4, checkpointed):
    from cs744_ddp_tpu.obs import Telemetry
    tel = Telemetry()
    tr = make(tmp_path, mesh4, telemetry=tel)
    tr.run(1, checkpoint_dir=str(tmp_path / "ck") if checkpointed else None)
    names = [r["name"] for r in tel.records if r["kind"] == "span"]
    assert names.count("checkpoint_open") == int(checkpointed)
    assert names.count("checkpoint_save") == int(checkpointed)
    if checkpointed:    # opened at the start of run(), before the first step
        assert names.index("checkpoint_open") < names.index("epoch_train")
