"""Fault-tolerance layer (ft/) tests: the deterministic chaos harness, the
supervised staging pipeline, the non-finite step guard, preemption-safe
mid-epoch resume, and the atomic-artifact/truncated-telemetry satellites.

The load-bearing pins are BITWISE: every recovery path that promises to
preserve the training stream (producer restart, degraded staging, checksum
repair, put retry, mid-epoch resume) must leave the final TrainState
byte-identical to an undisturbed run of the SAME program configuration.
Guard-on vs guard-off runs compile different step programs (XLA fuses them
differently, ~1e-10 apart), so no test compares across that boundary.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np

import jax
import pytest

import cs744_ddp_tpu.train.loop as looplib
from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.elastic import ElasticCoordinator
from cs744_ddp_tpu.ft import (NULL_CHAOS, PUBLISH_SITES, RANK_SITES, SITES,
                              ChaosPlan, FTConfig, NonFiniteError, NullChaos,
                              RankDeathError, StagingStalled, Watchdog,
                              batch_checksums, call_with_retry,
                              verify_checksums)
from cs744_ddp_tpu.parallel import make_mesh
from cs744_ddp_tpu.obs.telemetry import atomic_write_json, read_events_jsonl
from cs744_ddp_tpu.train.checkpoint import CheckpointManager
from cs744_ddp_tpu.train.loop import Trainer

from tinynet import tiny_cnn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- chaos plan ---------------------------------------------------------------

def test_chaos_parse_specs_and_empty():
    plan = ChaosPlan.parse(["put_fail:2", "corrupt_slot:3:7"])
    assert plan.enabled
    assert plan.spec() == [
        {"site": "put_fail", "step": 2, "seed": 0},
        {"site": "corrupt_slot", "step": 3, "seed": 7}]
    # Empty/None parse to the stateless disabled singleton, not a plan.
    assert ChaosPlan.parse(None) is NULL_CHAOS
    assert ChaosPlan.parse([]) is NULL_CHAOS


def test_chaos_parse_rejects_bad_specs():
    with pytest.raises(ValueError, match="SITE:step"):
        ChaosPlan.parse(["put_fail"])
    with pytest.raises(ValueError, match="integers"):
        ChaosPlan.parse(["put_fail:x"])
    with pytest.raises(ValueError, match="unknown chaos site"):
        ChaosPlan.parse(["meteor_strike:3"])
    with pytest.raises(ValueError, match=">= 0"):
        ChaosPlan.parse(["put_fail:-1"])


def test_chaos_fire_is_one_shot_and_recorded():
    plan = ChaosPlan.parse(["producer_crash:4"])
    assert not plan.fire("producer_crash", 3)
    assert plan.fire("producer_crash", 4)
    assert not plan.fire("producer_crash", 4)      # one-shot
    assert not plan.fire("put_fail", 4)            # other sites unaffected
    assert plan.fired == [("producer_crash", 4)]


def test_chaos_fire_range_and_reached():
    plan = ChaosPlan.parse(["put_fail:5", "preempt:3"])
    assert not plan.fire_range("put_fail", 0, 5)   # half-open: 5 excluded
    assert plan.fire_range("put_fail", 5, 8)
    assert not plan.fire_range("put_fail", 5, 8)
    assert not plan.fire_reached("preempt", 2)
    assert plan.fire_reached("preempt", 7)         # >= the planned step
    assert not plan.fire_reached("preempt", 7)
    assert plan.fired == [("put_fail", 5), ("preempt", 3)]


def test_chaos_steps_lists_planned_not_fired():
    plan = ChaosPlan.parse(["put_fail:1", "put_fail:9", "preempt:2"])
    assert plan.steps("put_fail") == (1, 9)
    plan.fire("put_fail", 1)
    assert plan.steps("put_fail") == (1, 9)        # fired entries stay listed


def test_chaos_rng_deterministic_in_seed_site_step():
    a = ChaosPlan.parse(["corrupt_slot:3:7"]).rng("corrupt_slot", 3)
    b = ChaosPlan.parse(["corrupt_slot:3:7"]).rng("corrupt_slot", 3)
    c = ChaosPlan.parse(["corrupt_slot:3:8"]).rng("corrupt_slot", 3)
    xs, ys, zs = (r.integers(0, 2**31, size=16) for r in (a, b, c))
    np.testing.assert_array_equal(xs, ys)
    assert not np.array_equal(xs, zs)


def test_chaos_fire_thread_safe_exactly_once():
    plan = ChaosPlan.parse(["producer_crash:0"])
    hits, barrier = [], threading.Barrier(8)

    def race():
        barrier.wait()
        if plan.fire("producer_crash", 0):
            hits.append(1)

    threads = [threading.Thread(target=race) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(hits) == 1


def test_null_chaos_is_stateless_and_all_false():
    assert NullChaos.__slots__ == ()
    assert NULL_CHAOS.enabled is False
    with pytest.raises(AttributeError):
        NULL_CHAOS.fired = []                      # no state can ever attach
    assert NULL_CHAOS.fire("producer_crash", 0) is False
    assert NULL_CHAOS.fire_range("put_fail", 0, 10) is False
    assert NULL_CHAOS.fire_reached("preempt", 10) is False
    assert NULL_CHAOS.steps("corrupt_slot") == ()
    assert NULL_CHAOS.spec() == []


def test_trainer_without_ft_compiles_no_supervision(tmp_path, mesh4):
    """ft=None is the zero-cost path: the chaos hook is the disabled
    singleton and none of the supervision/guard machinery is armed."""
    tr = Trainer(model=tiny_cnn(), strategy="allreduce", mesh=mesh4,
                 global_batch=64, data_dir=str(tmp_path), augment=True,
                 host_augment=True, log=lambda s: None)
    assert tr.chaos is NULL_CHAOS
    assert tr._supervise is False
    assert tr._guard_on is False
    assert tr._verify_chunks is False
    assert tr.staging_degraded is False


def test_chaos_nonfinite_requires_guard(tmp_path, mesh4):
    with pytest.raises(ValueError, match="nonfinite"):
        Trainer(model=tiny_cnn(), strategy="allreduce", mesh=mesh4,
                global_batch=64, data_dir=str(tmp_path), augment=True,
                host_augment=True, log=lambda s: None,
                ft=FTConfig(chaos=ChaosPlan.parse(["nonfinite_grad:1"])))


# -- supervision primitives ---------------------------------------------------

def test_watchdog_fires_once_detection_only():
    fired = []
    with Watchdog(0.02, on_timeout=fired.append) as wd:
        time.sleep(0.15)                           # body overruns but runs on
        body_done = True
    assert body_done and wd.fired and len(fired) == 1
    assert fired[0] >= 0.02


def test_watchdog_quiet_when_body_is_fast():
    fired = []
    with Watchdog(5.0, on_timeout=fired.append) as wd:
        pass
    assert not wd.fired and fired == []
    with Watchdog(None, on_timeout=fired.append):  # disabled deadline
        pass
    assert fired == []


def test_call_with_retry_backoff_and_callback_order():
    calls, retries, naps = [], [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError(f"transient {len(calls)}")
        return "ok"

    out = call_with_retry(flaky, attempts=4, backoff_base_s=0.05,
                          on_retry=lambda a, e: retries.append((a, str(e))),
                          sleep=naps.append)
    assert out == "ok" and len(calls) == 3
    assert retries == [(0, "transient 1"), (1, "transient 2")]
    assert naps == [0.05, 0.1]                     # base * 2**attempt


def test_call_with_retry_final_failure_propagates():
    with pytest.raises(OSError, match="always"):
        call_with_retry(lambda: (_ for _ in ()).throw(OSError("always")),
                        attempts=3, backoff_base_s=0.0, sleep=lambda s: None)
    with pytest.raises(ValueError, match="attempts"):
        call_with_retry(lambda: 1, attempts=0, backoff_base_s=0.0)


def test_checksums_detect_single_flipped_byte():
    rows = [np.arange(64, dtype=np.uint8).reshape(8, 8) for _ in range(3)]
    sums = batch_checksums(rows)
    assert verify_checksums(rows, sums) == []
    rows[1][3, 4] ^= 0x40
    assert verify_checksums(rows, sums) == [1]
    rows[1][3, 4] ^= 0x40                          # repair restores the sum
    assert verify_checksums(rows, sums) == []


# -- atomic artifact writes (satellite: kill-mid-write) -----------------------

def test_atomic_write_json_survives_sigkill_mid_write(tmp_path):
    """A process SIGKILLed at the worst instant — partial temp file written,
    atomic replace not yet reached — must leave the previous artifact
    intact and parseable (this is the window os.replace protects)."""
    path = tmp_path / "artifact.json"
    script = tmp_path / "killer.py"
    script.write_text(textwrap.dedent(f"""\
        import os, signal, sys
        sys.path.insert(0, {REPO!r})
        from cs744_ddp_tpu.obs.telemetry import atomic_write_json
        path = sys.argv[1]
        atomic_write_json(path, {{"generation": 0, "complete": True}})
        # Second write: die at the worst instant — the temp file holds a
        # torn half-document, the replace has not happened.
        tmp = f"{{path}}.{{os.getpid()}}.tmp"
        with open(tmp, "w") as f:
            f.write('{{"generation": 1, "comp')
            f.flush()
            os.fsync(f.fileno())
            os.kill(os.getpid(), signal.SIGKILL)
        """))
    proc = subprocess.run([sys.executable, str(script), str(path)],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == -signal.SIGKILL, proc.stderr
    with open(path) as f:
        assert json.load(f) == {"generation": 0, "complete": True}
    # The orphaned temp file must not confuse a later writer.
    atomic_write_json(str(path), {"generation": 2})
    with open(path) as f:
        assert json.load(f) == {"generation": 2}


def test_atomic_write_json_cleans_tmp_on_serialization_error(tmp_path):
    path = str(tmp_path / "artifact.json")
    atomic_write_json(path, {"v": 0})
    with pytest.raises(TypeError):
        # Non-string keys raise MID-dump, after partial bytes hit the temp
        # file; the artifact must keep its previous content and the temp
        # file must be cleaned up.
        atomic_write_json(path, {"v": 1, ("bad", "key"): 2})
    with open(path) as f:
        assert json.load(f) == {"v": 0}
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []


# -- truncated telemetry (satellite: report tolerates killed runs) ------------

def test_read_events_jsonl_tolerates_truncated_tail(tmp_path):
    p = str(tmp_path / "events.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"kind": "step", "iter": 1}) + "\n")
        f.write(json.dumps({"kind": "counter", "name": "c"}) + "\n")
        f.write('{"kind": "step", "it')            # run killed mid-write
    warns = []
    events, n_bad = read_events_jsonl(p, warn=warns.append)
    assert [e["kind"] for e in events] == ["step", "counter"]
    assert n_bad == 1
    assert len(warns) == 1 and "undecodable" in warns[0]
    # Missing file: empty, not an error (a run killed before any event).
    assert read_events_jsonl(str(tmp_path / "absent.jsonl")) == ([], 0)


def test_telemetry_report_surfaces_truncated_lines(tmp_path, monkeypatch):
    from cs744_ddp_tpu.obs.telemetry import Telemetry
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import telemetry_report

    d = str(tmp_path / "run")
    tel = Telemetry(d)
    tel.write_manifest({"model": "tiny", "strategy": "ddp", "world_size": 4,
                        "global_batch": 64})
    for i in range(1, 6):
        tel.step(epoch=0, iter=i, loss=1.0 / i, step_time=0.01, steady=i > 2)
    with open(os.path.join(d, "events.jsonl"), "a") as f:
        f.write('{"kind": "step", "epoch": 0, "iter": 6, "los')  # torn tail
    text = telemetry_report.render(d)
    assert "!! 1 undecodable event line(s) skipped" in text
    assert "5 (3 steady)" in text                  # good lines still counted


# -- integration: the chaos matrix -------------------------------------------
#
# tiny_cnn on the 4-device CPU mesh, 7 batches of 64 with WINDOW=3 (windows
# at 3/6, final batch through the absolute window grid).  Synthetic CIFAR-10
# is deterministic, so one clean reference state serves every bitwise pin.

LIMIT = 7

_CLEAN_STATE = {}


def _trainer(tmp_path, mesh4, *, ft=None, limit=LIMIT, log=None,
             strategy="allreduce"):
    return Trainer(model=tiny_cnn(), strategy=strategy, mesh=mesh4,
                   global_batch=64, data_dir=str(tmp_path), augment=True,
                   host_augment=True, limit_train_batches=limit,
                   log=log or (lambda s: None), ft=ft)


def _host_state(tr):
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a)), tr.state)


def _clean_state(tmp_path, mesh4, limit=LIMIT):
    assert looplib.WINDOW == 3, "callers must monkeypatch WINDOW first"
    if limit not in _CLEAN_STATE:
        tr = _trainer(tmp_path, mesh4, limit=limit)
        tr.train_model(0)
        _CLEAN_STATE[limit] = _host_state(tr)
    return _CLEAN_STATE[limit]


def _assert_bitwise(state_a, state_b):
    la, lb = jax.tree.leaves(state_a), jax.tree.leaves(state_b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def small_window(monkeypatch):
    monkeypatch.setattr(looplib, "WINDOW", 3)


def test_producer_crash_restart_is_bitwise(tmp_path, mesh4, small_window):
    clean = _clean_state(tmp_path, mesh4)
    plan = ChaosPlan.parse(["producer_crash:4"])
    tr = _trainer(tmp_path, mesh4, ft=FTConfig(chaos=plan))
    tr.train_model(0)
    assert plan.fired == [("producer_crash", 4)]
    assert tr.producer_failures == 1
    assert tr.staging_degraded is False            # one restart sufficed
    _assert_bitwise(_host_state(tr), clean)


def test_producer_double_crash_degrades_bitwise(tmp_path, mesh4,
                                                small_window):
    clean = _clean_state(tmp_path, mesh4)
    # The restarted producer hits the second entry at the same step: the
    # restart budget (1) is exhausted and staging degrades to synchronous
    # per-batch puts — overlap lost, stream unchanged.
    plan = ChaosPlan.parse(["producer_crash:2", "producer_crash:2"])
    lines = []
    tr = _trainer(tmp_path, mesh4, ft=FTConfig(chaos=plan), log=lines.append)
    tr.train_model(0)
    assert tr.producer_failures == 2
    assert tr.staging_degraded is True
    assert any("degrading to synchronous" in ln for ln in lines)
    _assert_bitwise(_host_state(tr), clean)


def test_degraded_staging_mode_is_bitwise(tmp_path, mesh4, small_window):
    clean = _clean_state(tmp_path, mesh4)
    tr = _trainer(tmp_path, mesh4, ft=FTConfig(degrade_staging=True))
    assert tr.staging_degraded is True
    tr.train_model(0)
    assert tr.producer_failures == 0
    _assert_bitwise(_host_state(tr), clean)


def test_corrupt_slot_detected_repaired_bitwise(tmp_path, mesh4,
                                                small_window):
    clean = _clean_state(tmp_path, mesh4)
    plan = ChaosPlan.parse(["corrupt_slot:3"])
    lines = []
    tr = _trainer(tmp_path, mesh4, ft=FTConfig(chaos=plan), log=lines.append)
    assert tr._verify_chunks is True               # auto-on with this site
    tr.train_model(0)
    assert ("corrupt_slot", 3) in plan.fired
    assert any("staged batch 3 failed its checksum" in ln for ln in lines)
    _assert_bitwise(_host_state(tr), clean)


def test_put_fail_retried_bitwise(tmp_path, mesh4, small_window):
    clean = _clean_state(tmp_path, mesh4)
    plan = ChaosPlan.parse(["put_fail:2"])
    lines = []
    tr = _trainer(tmp_path, mesh4,
                  ft=FTConfig(chaos=plan, backoff_base_s=0.001),
                  log=lines.append)
    tr.train_model(0)
    assert ("put_fail", 2) in plan.fired
    assert any("retrying with backoff" in ln for ln in lines)
    assert tr.producer_failures == 0               # retry absorbed the fault
    _assert_bitwise(_host_state(tr), clean)


def test_put_delay_trips_watchdog_bitwise(tmp_path, mesh4, small_window):
    clean = _clean_state(tmp_path, mesh4)
    plan = ChaosPlan.parse(["put_delay:2"])
    lines = []
    tr = _trainer(tmp_path, mesh4,
                  ft=FTConfig(chaos=plan, put_timeout_s=0.05),
                  log=lines.append)
    tr.train_model(0)
    assert ("put_delay", 2) in plan.fired
    # Detection-only: the watchdog logs the overrun, the put completes.
    assert any("watchdog deadline" in ln for ln in lines)
    _assert_bitwise(_host_state(tr), clean)


def test_stall_deadline_raises_staging_stalled(tmp_path, mesh4):
    tr = _trainer(tmp_path, mesh4, ft=FTConfig())

    def wedged_fill(emit):
        emit("first")
        time.sleep(1.6)                            # producer alive but stuck

    it = tr._prefetch_iter(wedged_fill, stall_timeout_s=0.1)
    assert next(it) == "first"
    with pytest.raises(StagingStalled, match="deadline"):
        next(it)
    it.close()


# -- integration: non-finite step guard ---------------------------------------

def test_nonfinite_skip_counts_and_keeps_params_finite(tmp_path, mesh4,
                                                       small_window):
    plan = ChaosPlan.parse(["nonfinite_grad:2"])
    tr = _trainer(tmp_path, mesh4,
                  ft=FTConfig(nonfinite="skip", chaos=plan))
    timers = tr.train_model(0)
    assert ("nonfinite_grad", 2) in plan.fired
    assert tr.nonfinite_skipped == 1
    assert tr.nonfinite_restored == 0
    assert np.isfinite(timers.losses).all()        # bad update never applied
    for leaf in jax.tree.leaves(_host_state(tr)):
        assert np.isfinite(leaf).all()


def test_nonfinite_halt_raises_before_applying(tmp_path, mesh4,
                                               small_window):
    tr = _trainer(tmp_path, mesh4,
                  ft=FTConfig(nonfinite="halt",
                              chaos=ChaosPlan.parse(["nonfinite_grad:2"])))
    with pytest.raises(NonFiniteError, match="policy=halt"):
        tr.train_model(0)


def test_nonfinite_restore_rolls_back_and_continues(tmp_path, mesh4,
                                                    small_window):
    plan = ChaosPlan.parse(["nonfinite_grad:2"])
    lines = []
    tr = _trainer(tmp_path, mesh4,
                  ft=FTConfig(nonfinite="restore", chaos=plan),
                  log=lines.append)
    tr.train_model(0)
    assert tr.nonfinite_restored == 1
    assert any("rolled back" in ln for ln in lines)
    for leaf in jax.tree.leaves(_host_state(tr)):
        assert np.isfinite(leaf).all()


# -- integration: preemption-safe mid-epoch resume ----------------------------

def test_chaos_preempt_without_checkpoint_dir_raises(tmp_path, mesh4,
                                                     small_window):
    tr = _trainer(tmp_path, mesh4,
                  ft=FTConfig(chaos=ChaosPlan.parse(["preempt:0"])))
    with pytest.raises(RuntimeError, match="chaos preempt requires"):
        tr.train_model(0)                          # no guard installed


def test_chaos_preempt_mid_epoch_resume_is_bitwise(tmp_path, mesh4,
                                                   small_window):
    """The tentpole pin: SIGTERM at a step boundary -> emergency mid-epoch
    checkpoint -> a fresh process-equivalent Trainer resumes from that
    exact step -> the finished epoch is bitwise identical to one that was
    never interrupted."""
    ck = str(tmp_path / "ck")
    lines = []

    def small_eval(tr):
        tr.test_split = cifar10.Split(tr.test_split.images[:64],
                                      tr.test_split.labels[:64])
        return tr

    # Interrupted run: injected SIGTERM once progress reaches step 5 —
    # the boundary poll sees it at trained=6 (WINDOW=3 grid).
    tr1 = small_eval(_trainer(
        tmp_path, mesh4, log=lines.append,
        ft=FTConfig(chaos=ChaosPlan.parse(["preempt:5"]))))
    tr1.run(1, checkpoint_dir=ck)
    assert tr1.preempted is True
    assert any("emergency checkpoint saved" in ln for ln in lines)

    peek = CheckpointManager(ck)
    assert peek.latest_mid_epoch() == (0, 6)
    assert peek.latest_epoch() is None
    peek.close()

    # Resume (no chaos): picks up at epoch 0 step 6, finishes the epoch.
    tr2 = small_eval(_trainer(tmp_path, mesh4, log=lines.append))
    tr2.run(1, checkpoint_dir=ck)
    assert tr2.preempted is False
    assert any("Resumed from mid-epoch checkpoint: epoch 0, step 6" in ln
               for ln in lines)

    # Uninterrupted reference with the same program configuration.
    tr0 = small_eval(_trainer(tmp_path, mesh4))
    tr0.run(1)
    _assert_bitwise(_host_state(tr2), _host_state(tr0))

    # The completed epoch checkpoint outranks — and clears — the mid-epoch
    # emergency save (a later run must not rewind into the epoch).
    peek = CheckpointManager(ck)
    assert peek.latest_epoch() == 0
    assert peek.latest_mid_epoch() is None
    peek.close()


def test_preempt_resume_carries_compressed_residuals_bitwise(
        tmp_path, mesh4, small_window):
    """Round-7 pin: the error-feedback residual stack (opt_state.comm) is
    part of the checkpointed TrainState — a preemption while residuals
    are NONZERO resumes bitwise, including the rest of the epoch whose
    arithmetic depends on the carried residuals."""
    ck = str(tmp_path / "ck_comp")
    lines = []

    def small_eval(tr):
        tr.test_split = cifar10.Split(tr.test_split.images[:64],
                                      tr.test_split.labels[:64])
        return tr

    # Preempt EARLY (boundary poll at trained=3 on the WINDOW=3 grid): on
    # this synthetic task the net later collapses to zero grads and the
    # bf16 residuals decay to EXACT zero, which would make the
    # nonzero-residual assertion below vacuous.
    tr1 = small_eval(_trainer(
        tmp_path, mesh4, strategy="compress-bf16", log=lines.append,
        ft=FTConfig(chaos=ChaosPlan.parse(["preempt:2"]))))
    tr1.run(1, checkpoint_dir=ck)
    assert tr1.preempted is True
    comm = jax.device_get(tr1.state.opt_state.comm)
    assert any(np.any(np.asarray(l)) for l in jax.tree.leaves(comm)), \
        "preempted too late: every EF residual already decayed to zero"

    # Resume (no chaos) and finish; compare against never-interrupted.
    tr2 = small_eval(_trainer(tmp_path, mesh4, strategy="compress-bf16",
                              log=lines.append))
    tr2.run(1, checkpoint_dir=ck)
    assert any("Resumed from mid-epoch checkpoint" in ln for ln in lines)
    tr0 = small_eval(_trainer(tmp_path, mesh4, strategy="compress-bf16"))
    tr0.run(1)
    # _assert_bitwise spans the WHOLE TrainState, comm residuals included.
    _assert_bitwise(_host_state(tr2), _host_state(tr0))
    assert jax.tree.leaves(tr2.state.opt_state.comm)[0].shape[0] == 4


CHILD_SCRIPT = """\
import os
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
import sys
repo, tests_dir, ck, data = sys.argv[1:5]
sys.path.insert(0, repo)
sys.path.insert(0, tests_dir)
import jax
jax.config.update("jax_platforms", "cpu")
from cs744_ddp_tpu.utils.compcache import enable_persistent_compilation_cache
enable_persistent_compilation_cache()
import cs744_ddp_tpu.train.loop as looplib
looplib.WINDOW = 3
from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.parallel import make_mesh
from tinynet import tiny_cnn
tr = looplib.Trainer(model=tiny_cnn(), strategy="allreduce",
                     mesh=make_mesh(4), global_batch=64, data_dir=data,
                     augment=True, host_augment=True, limit_train_batches=45,
                     log=lambda s: print(s, flush=True))
tr.test_split = cifar10.Split(tr.test_split.images[:64],
                              tr.test_split.labels[:64])
tr.run(1, checkpoint_dir=ck)
print("CHILD_PREEMPTED" if tr.preempted else "CHILD_COMPLETED", flush=True)
"""


def test_sigterm_subprocess_emergency_checkpoint_and_resume(
        tmp_path, mesh4, small_window):
    """End-to-end preemption exactly as a pod scheduler delivers it: a REAL
    SIGTERM to a separate training process mid-epoch.  The child finishes
    its in-flight step, writes the emergency checkpoint and exits cleanly;
    resuming from its checkpoint dir completes the epoch bitwise identical
    to a never-interrupted run."""
    ck = str(tmp_path / "ck")
    script = tmp_path / "child.py"
    script.write_text(CHILD_SCRIPT)
    proc = subprocess.Popen(
        [sys.executable, str(script), REPO, os.path.dirname(__file__),
         ck, str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    reaper = threading.Timer(420, proc.kill)       # hang backstop only
    reaper.start()
    signaled = False
    lines = []
    try:
        for line in proc.stdout:
            lines.append(line)
            if not signaled and "Training loss after 20 iterations" in line:
                proc.send_signal(signal.SIGTERM)   # mid-epoch, mid-training
                signaled = True
        proc.wait(timeout=120)
    finally:
        reaper.cancel()
    out = "".join(lines)
    assert signaled, f"child never reached iteration 20:\n{out}"
    assert proc.returncode == 0, out               # clean exit, not a kill
    assert "emergency checkpoint saved" in out
    assert "CHILD_PREEMPTED" in out

    peek = CheckpointManager(ck)
    mid = peek.latest_mid_epoch()
    peek.close()
    assert mid is not None and mid[0] == 0 and 20 < mid[1] <= 45

    def small_eval(tr):
        tr.test_split = cifar10.Split(tr.test_split.images[:64],
                                      tr.test_split.labels[:64])
        return tr

    lines2 = []
    tr2 = small_eval(_trainer(tmp_path, mesh4, limit=45, log=lines2.append))
    tr2.run(1, checkpoint_dir=ck)
    assert any("Resumed from mid-epoch checkpoint" in ln for ln in lines2)

    tr0 = small_eval(_trainer(tmp_path, mesh4, limit=45))
    tr0.run(1)
    _assert_bitwise(_host_state(tr2), _host_state(tr0))


# -- integration: rank-level chaos + the elastic degradation ladder -----------
#
# New round-6 sites: rank_death / slow_rank target a RANK (the spec's third
# field), coordinator_loss targets the coordinator's recovery progress.
# Every recovery that promises to preserve the stream stays BITWISE.

def test_chaos_rank_sites_target_ranks_one_shot():
    assert RANK_SITES == ("rank_death", "slow_rank")
    assert "coordinator_loss" in SITES
    plan = ChaosPlan.parse(["rank_death:3:1", "slow_rank:5:2",
                            "coordinator_loss:0"])
    # The third field is the target rank, carried in the seed slot.
    assert plan.seed_of("rank_death", 3) == 1
    assert plan.seed_of("slow_rank", 5) == 2
    assert plan.fire_reached("rank_death", 4)      # >= planned step
    assert not plan.fire_reached("rank_death", 9)  # one-shot
    assert plan.fire_reached("coordinator_loss", 0)
    err = RankDeathError(1, 0, 3)
    assert (err.rank, err.epoch, err.step) == (1, 0, 3)


def _small_eval(tr):
    tr.test_split = cifar10.Split(tr.test_split.images[:64],
                                  tr.test_split.labels[:64])
    return tr


def test_rank_death_emergency_checkpoint_same_world_resume_bitwise(
        tmp_path, mesh4, small_window):
    """Rank death mid-epoch -> emergency mid-epoch checkpoint (with the
    round-6 topology metadata) -> a same-world resume finishes the epoch
    bitwise identical to an undisturbed run (the coordinator's retry rung
    is exactly this plain resume)."""
    clean = _clean_state(tmp_path, mesh4)
    ck = str(tmp_path / "ck_rd")
    plan = ChaosPlan.parse(["rank_death:3:1"])
    lines = []
    tr = _small_eval(_trainer(tmp_path, mesh4, ft=FTConfig(chaos=plan),
                              log=lines.append))
    tr.run(1, checkpoint_dir=ck)
    assert tr.rank_death == (1, 0, 3)
    assert ("rank_death", 3) in plan.fired
    assert any("Rank 1 died at epoch 0 step 3" in ln for ln in lines)

    from cs744_ddp_tpu.elastic import flat_meta
    from cs744_ddp_tpu.train.checkpoint import read_mid_epoch_meta
    meta = flat_meta(read_mid_epoch_meta(ck))
    assert meta["world"] == 4 and meta["step"] == 3
    assert len(meta["rank_keys"]) == 4

    lines2 = []
    tr2 = _small_eval(_trainer(tmp_path, mesh4, log=lines2.append))
    tr2.run(1, checkpoint_dir=ck)
    assert any("Resumed from mid-epoch checkpoint: epoch 0, step 3" in ln
               for ln in lines2)
    assert tr2.rank_death is None
    _assert_bitwise(_host_state(tr2), clean)


def _elastic_trainer(tmp_path, world, *, ft=None, log=None, limit=6):
    return Trainer(model=tiny_cnn(), strategy="allreduce",
                   mesh=make_mesh(world), global_batch=64,
                   data_dir=str(tmp_path), seed=3, augment=True,
                   limit_train_batches=limit, limit_eval_batches=1,
                   log=log or (lambda s: None), ft=ft, elastic="strong")


def test_rank_death_ladder_shrinks_and_recovery_is_bitwise(tmp_path,
                                                           small_window):
    """ISSUE round 6 acceptance: a chaos-injected mid-epoch rank death at
    world 2 drives the coordinator down the ladder (emergency checkpoint ->
    shrink -> resume at world 1), and the recovered run's final state is
    BITWISE equal to a fault-free run at the target world — the strong-
    scaling world-invariance pin cashed in as a recovery guarantee."""
    tr0 = _elastic_trainer(tmp_path, 1)            # fault-free world-1 ref
    tr0.run(1)

    plan = ChaosPlan.parse(["rank_death:3:1"])
    lines = []
    coord = ElasticCoordinator(
        lambda w: _elastic_trainer(tmp_path, w, ft=FTConfig(chaos=plan),
                                   log=lines.append),
        world=2, global_batch=64, microshards=4, chaos=plan,
        log=lines.append)
    tr = coord.run(1, str(tmp_path / "ck_ladder"))

    assert [e["kind"] for e in coord.events] == ["shrink"]
    assert any("shrinking world 2 -> 1" in ln for ln in lines)
    rep = coord.report()
    assert rep["world"] == 1 and rep["degraded"] is True
    assert rep["generation"] == 1 and len(rep["members"]) == 1
    plan_r = tr.resume_plan
    assert (plan_r.old_world, plan_r.new_world) == (2, 1)
    assert plan_r.start_step == 3                  # strong: step carries
    assert plan_r.examples_replayed == 0
    _assert_bitwise(_host_state(tr), _host_state(tr0))


def test_coordinator_loss_rederives_membership_from_disk_bitwise(
        tmp_path, small_window):
    """The coordinator_loss site drops the in-memory membership mid-
    recovery; the coordinator must re-derive it from checkpoint metadata
    alone and still land the same bitwise-pinned shrink."""
    tr0 = _elastic_trainer(tmp_path, 1)
    tr0.run(1)

    plan = ChaosPlan.parse(["rank_death:3:1", "coordinator_loss:0"])
    lines = []
    coord = ElasticCoordinator(
        lambda w: _elastic_trainer(tmp_path, w, ft=FTConfig(chaos=plan),
                                   log=lines.append),
        world=2, global_batch=64, microshards=4, chaos=plan,
        log=lines.append)
    tr = coord.run(1, str(tmp_path / "ck_closs"))

    assert any("re-deriving from checkpoint metadata" in ln for ln in lines)
    assert ("coordinator_loss", 0) in plan.fired
    assert [e["kind"] for e in coord.events] == ["shrink"]
    assert coord.report()["world"] == 1
    _assert_bitwise(_host_state(tr), _host_state(tr0))


def test_slow_rank_flags_straggler_and_stream_unchanged(tmp_path, mesh4,
                                                        small_window):
    """slow_rank injects a real stall attributed to one rank's step-time
    gauge: the detector must flag exactly that rank, and the training
    stream must be untouched (detection-only, bitwise pin)."""
    clean = _clean_state(tmp_path, mesh4)
    plan = ChaosPlan.parse(["slow_rank:3:2"])
    lines = []
    tr = _trainer(tmp_path, mesh4,
                  ft=FTConfig(chaos=plan, slow_rank_stall_s=2.0),
                  log=lines.append)
    tr.train_model(0)
    assert ("slow_rank", 3) in plan.fired
    assert any("rank 2 straggling" in ln for ln in lines)
    assert tr._straggler.flag_counts.get(2, 0) >= 1
    assert tr.rank_death is None
    _assert_bitwise(_host_state(tr), clean)


# -- publish/hot-swap chaos sites (round 10) ----------------------------------


def test_chaos_publish_and_swap_sites_one_shot_seeded():
    assert PUBLISH_SITES == ("publish_torn", "publish_stale")
    assert "swap_mid_batch" in SITES
    assert all(s in SITES for s in PUBLISH_SITES)
    plan = ChaosPlan.parse(["publish_torn:1:7", "publish_stale:2",
                            "swap_mid_batch:4:1"])
    # The third field targets a replica for swap_mid_batch — carried in
    # the seed slot, same convention as the rank/replica sites.
    assert plan.seed_of("swap_mid_batch", 4) == 1
    assert not plan.fire("publish_torn", 0)
    assert plan.fire("publish_torn", 1)
    assert not plan.fire("publish_torn", 1)            # one-shot
    assert plan.fire("publish_stale", 2)
    assert plan.fired == [("publish_torn", 1), ("publish_stale", 2)]
    # Torn-byte offsets are deterministic in (seed, site, step).
    a = ChaosPlan.parse(["publish_torn:1:7"]).rng("publish_torn", 1)
    b = ChaosPlan.parse(["publish_torn:1:7"]).rng("publish_torn", 1)
    np.testing.assert_array_equal(a.integers(0, 2**31, size=8),
                                  b.integers(0, 2**31, size=8))


def _publish_stack(tmp_path, chaos):
    """Minimal publish->serve loop: one publisher, one CPU replica, one
    watcher (probes attached) — the recovery-pin fixture for the three
    round-10 chaos sites."""
    from cs744_ddp_tpu import models as model_zoo
    from cs744_ddp_tpu.publish import WeightPublisher, WeightWatcher
    from cs744_ddp_tpu.serve import EngineReplica
    model_zoo.register_model("tiny", tiny_cnn)
    pub = WeightPublisher(str(tmp_path / "pub"), chaos=chaos,
                          fingerprint={"model": "tiny"})
    replica = EngineReplica(0, model="tiny", buckets=(2,), seed=0,
                            chaos=chaos)
    replica.startup()
    watcher = WeightWatcher(pub.directory, [replica])
    return pub, replica, watcher


def _tiny_state(seed):
    from cs744_ddp_tpu.train.step import init_train_state
    init_fn, _ = tiny_cnn()
    return init_train_state(init_fn, jax.random.PRNGKey(seed))


def test_publish_torn_rejected_by_crc_old_version_serves(tmp_path):
    """publish_torn recovery pin: the torn bundle (seeded payload bytes
    flipped after the atomic rename) is rejected at crc-verify time and
    the previously installed version keeps serving bitwise-unchanged."""
    plan = ChaosPlan.parse(["publish_torn:1"])
    pub, replica, watcher = _publish_stack(tmp_path, plan)
    assert pub.publish(_tiny_state(1))["torn"] is False
    assert watcher.poll_once() == "installed"
    imgs = cifar10._synthetic_split(8, seed=5).images[:2]
    before, _, _ = replica.engine.infer_counts(imgs)
    rec = pub.publish(_tiny_state(2))
    assert rec["torn"] is True and ("publish_torn", 1) in plan.fired
    assert watcher.poll_once() == "rejected"
    rep = watcher.report()
    assert rep["rejected"] == 1 and rep["installed_version"] == 1
    assert replica.engine.weights_version == 1
    after, _, _ = replica.engine.infer_counts(imgs)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(before))


def test_publish_stale_skipped_current_version_keeps_serving(tmp_path):
    """publish_stale recovery pin: a duplicate publisher re-announcing an
    already-installed version is skipped — never re-installed, never an
    error, the current version keeps serving."""
    plan = ChaosPlan.parse(["publish_stale:1"])
    pub, replica, watcher = _publish_stack(tmp_path, plan)
    assert pub.publish(_tiny_state(1))["version"] == 1
    assert watcher.poll_once() == "installed"
    rec = pub.publish(_tiny_state(2))
    assert rec["stale"] is True and rec["version"] == 1
    assert rec["file"].endswith(".dup.ccwb")
    assert ("publish_stale", 1) in plan.fired
    assert watcher.poll_once() == "stale"
    rep = watcher.report()
    assert rep["stale"] == 1 and rep["installed_version"] == 1
    assert replica.engine.weights_version == 1


def test_swap_mid_batch_probe_never_mixes_weights(tmp_path):
    """swap_mid_batch recovery pin: chaos fires the watcher's poll from
    INSIDE dispatch 1's hook on the scheduler worker thread; the racing
    dispatch is answered ENTIRELY by the old weights (the flip lands at
    the next loop boundary) and the next dispatch by the new — a batch
    never sees mixed weights, and every reply's model_version tag says
    which model computed it."""
    plan = ChaosPlan.parse(["swap_mid_batch:1:0"])
    pub, replica, watcher = _publish_stack(tmp_path, plan)
    pub.publish(_tiny_state(1))
    assert watcher.poll_once() == "installed"
    imgs = cifar10._synthetic_split(8, seed=5).images[:2]
    replica.start()
    try:
        r0 = replica.scheduler.submit(imgs, slo_ms=None).result(30.0)
        pub.publish(_tiny_state(2))   # v2 on disk; only the probe polls
        r1 = replica.scheduler.submit(imgs, slo_ms=None).result(30.0)
        r2 = replica.scheduler.submit(imgs, slo_ms=None).result(30.0)
    finally:
        replica.stop()
    assert ("swap_mid_batch", 1) in plan.fired
    assert (r0.model_version, r1.model_version, r2.model_version) == (1, 1, 2)
    np.testing.assert_array_equal(r1.logits, r0.logits)   # old model, whole batch
    assert not np.array_equal(r2.logits, r1.logits)       # new model after flip


# -- round 14: completion-side chaos (dispatch_fault) -------------------------


def test_chaos_dispatch_fault_site_registered_one_shot():
    from cs744_ddp_tpu.ft.chaos import REPLICA_SITES
    assert "dispatch_fault" in SITES
    assert "dispatch_fault" in REPLICA_SITES
    plan = ChaosPlan.parse(["dispatch_fault:1:0"])
    assert plan.seed_of("dispatch_fault", 1) == 0   # third field = replica
    assert plan.fire("dispatch_fault", 1)
    assert not plan.fire("dispatch_fault", 1)       # one-shot
    assert plan.fired == [("dispatch_fault", 1)]


def test_dispatch_fault_isolated_bitwise_recovery_pipelined_vs_serial():
    """dispatch_fault recovery pin: the chaos site discards dispatch 1's
    device result at its completion fence (with the pipelined worker,
    while dispatch 2 is already in flight).  Both workers isolate the
    fault — dispatch 1's request gets an explicit error reply, every
    neighbour resolves ok on the SAME weights, the worker survives —
    and the non-faulted replies are bitwise-identical between the
    pipelined and serial paths."""
    from cs744_ddp_tpu import models as model_zoo
    from cs744_ddp_tpu.serve import EngineReplica
    model_zoo.register_model("tiny", tiny_cnn)
    pool = cifar10._synthetic_split(16, seed=5)

    def _serve(pipeline):
        plan = ChaosPlan.parse(["dispatch_fault:1:0"])
        rep = EngineReplica(0, model="tiny", buckets=(2, 4), seed=0,
                            chaos=plan, pipeline=pipeline)
        # Full-max-bucket requests submitted before the worker starts:
        # each dispatch carries exactly one request, so the faulted
        # dispatch number maps deterministically to one reply.
        futs = [rep.scheduler.submit(pool.images[4 * i:4 * i + 4],
                                     slo_ms=None)
                for i in range(4)]
        rep.start()
        try:
            replies = [f.result(30.0) for f in futs]
        finally:
            rep.stop()
        return plan, replies

    plan_p, piped = _serve(True)
    plan_s, serial = _serve(False)
    for plan, replies in ((plan_p, piped), (plan_s, serial)):
        assert [r.status for r in replies] == ["ok", "error", "ok", "ok"]
        assert plan.fired == [("dispatch_fault", 1)]    # fired exactly once
        assert "ChaosError" in replies[1].reason
        assert replies[1].logits is None
        # Old weights keep serving around the fault: one version tag.
        assert {r.model_version for r in replies} == {0}
    for a, b in zip(serial, piped):
        if a.status == "ok":
            np.testing.assert_array_equal(a.logits, b.logits)
