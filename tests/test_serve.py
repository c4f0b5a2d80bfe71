"""Serving fast-path tests (cs744_ddp_tpu/serve/) on the CPU backend.

The central pin is the ISSUE's acceptance bar: bucketed serving output is
BITWISE-identical (f32) to an unpadded direct forward at the exact request
size, including ragged fills — with ``train=False`` BatchNorm every row is
computed independently of its batchmates, so padding must be a pure layout
detail.  Around it: the batching policy's determinism under a seeded trace
(the pure ``plan_batches`` replay), the threaded micro-batcher returning
each request its own rows, the warm-start executable-cache roundtrip, the
staged-ingest slot-reuse safety, and the telemetry-off path touching the
recorder not at all.
"""

import threading
import time

import numpy as np
import pytest

from cs744_ddp_tpu import models as model_zoo
from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.obs import NULL
from cs744_ddp_tpu.serve import (InferenceEngine, MicroBatcher, QueueFull,
                                 StagedIngest, coalesce, plan_batches)
from cs744_ddp_tpu.serve.batcher import smallest_bucket
from cs744_ddp_tpu.serve.demo import parse_buckets, synthetic_trace

from tinynet import tiny_cnn


def setup_module(module):
    model_zoo.register_model("tiny", tiny_cnn)


@pytest.fixture(scope="module")
def pool():
    return cifar10._synthetic_split(64, seed=3)


@pytest.fixture(scope="module")
def engine():
    model_zoo.register_model("tiny", tiny_cnn)
    return InferenceEngine("tiny", buckets=(2, 4, 8), seed=0)


# -- ladder shape -------------------------------------------------------------

def test_bucket_for_edges(engine):
    assert engine.bucket_for(1) == 2
    assert engine.bucket_for(2) == 2
    assert engine.bucket_for(3) == 4
    assert engine.bucket_for(8) == 8
    assert engine.max_batch == 8
    with pytest.raises(ValueError, match="at least one"):
        engine.bucket_for(0)
    with pytest.raises(ValueError, match="exceeds the largest"):
        engine.bucket_for(9)


def test_engine_validates_config():
    with pytest.raises(ValueError, match="strictly increasing"):
        InferenceEngine("tiny", buckets=(4, 2))
    with pytest.raises(ValueError, match="strictly increasing"):
        InferenceEngine("tiny", buckets=(2, 2, 4))
    with pytest.raises(ValueError, match="at least one bucket"):
        InferenceEngine("tiny", buckets=())
    with pytest.raises(ValueError, match="unknown precision"):
        InferenceEngine("tiny", buckets=(2,), precisions=("f16",))


# -- bitwise equivalence (the acceptance pin) ---------------------------------

def test_bucketed_output_bitwise_equals_direct_forward(engine, pool):
    """Every ragged fill of every bucket: the engine's sliced logits must be
    BITWISE-identical f32 to jit-compiling the same forward at the exact
    request size with no padding.

    n=1 is excluded from the bitwise leg: XLA specializes batch-1 codegen
    (different instruction order, last-ulp drift vs every batch>=2 program
    — measured on this CPU backend), so the DIRECT program is the outlier
    there, not the padding; the singleton case is pinned separately via
    composition invariance below."""
    import jax
    direct = jax.jit(engine._forward["f32"])
    for n in (2, 3, 5, 7, 8):
        imgs = pool.images[:n]
        labs = pool.labels[:n]
        logits, loss, correct = engine.infer_counts(imgs, labs)
        d_logits, d_loss, d_correct = direct(
            engine.params, engine.bn_state, imgs,
            np.asarray(labs, np.int32))
        assert logits.shape == (n, 10) and logits.dtype == np.float32
        assert np.array_equal(logits, np.asarray(d_logits)), \
            f"bucketed logits differ from direct forward at n={n}"
        # The masked counts: pad rows carry label -1 and contribute zero.
        # correct is an integer count (exact); loss_sum's reduction tree
        # differs between bucket sizes, so it is float-close, not bitwise.
        assert correct == int(d_correct)
        assert loss == pytest.approx(float(d_loss), rel=1e-6)


def test_request_rows_are_batchmate_invariant(engine, pool):
    """A request's logits rows are BITWISE-independent of what rides (or
    pads) alongside it — the property that makes bucketed serving exact
    at every fill, including n=1."""
    import jax
    # Same bucket program, different fill/pad composition.
    solo = engine.infer(pool.images[:1])
    paired = engine.infer(pool.images[:2])[:1]
    assert np.array_equal(solo, paired)
    full = engine.infer(np.concatenate([pool.images[:5],
                                        pool.images[20:23]]))[:5]
    assert np.array_equal(engine.infer(pool.images[:5]), full)
    # The singleton still matches the batch-1 direct program float-close
    # (see the bitwise test's docstring for why not bitwise).
    direct = jax.jit(engine._forward["f32"])
    d_logits, _, _ = direct(engine.params, engine.bn_state,
                            pool.images[:1], np.full((1,), -1, np.int32))
    np.testing.assert_allclose(solo, np.asarray(d_logits), rtol=1e-5)


def test_staging_and_plain_copy_paths_identical(engine, pool):
    """use_staging=False (padded np copy) must produce the same staged
    bytes, hence bitwise-identical logits, as the arena path."""
    plain = InferenceEngine("tiny", buckets=(2, 4, 8), seed=0,
                            use_staging=False)
    for n in (1, 3, 6):
        a = engine.infer(pool.images[:n])
        b = plain.infer(pool.images[:n])
        assert np.array_equal(a, b)


def test_unlabeled_request_counts_are_zero(engine, pool):
    logits, loss, correct = engine.infer_counts(pool.images[:3])
    assert logits.shape == (3, 10)
    assert loss == 0.0 and correct == 0


# -- batching policy (pure functions) -----------------------------------------

def test_coalesce_prefix_selection():
    assert coalesce([1, 2, 4], 8) == (3, 7)
    assert coalesce([1, 2, 4, 2], 8) == (3, 7)   # 4th would overflow
    assert coalesce([8, 1], 8) == (1, 8)
    assert coalesce([], 8) == (0, 0)
    # FIFO atomicity: an oversized head blocks the prefix entirely rather
    # than being skipped around (requests are never reordered or split).
    assert coalesce([9, 1], 8) == (0, 0)


def test_smallest_bucket():
    assert smallest_bucket((2, 4, 8), 3) == 4
    assert smallest_bucket((2, 4, 8), 8) == 8
    with pytest.raises(ValueError, match="exceed"):
        smallest_bucket((2, 4, 8), 9)


def test_plan_batches_deterministic_and_policy_sound():
    buckets = (2, 4, 8)
    max_wait = 0.004
    trace = synthetic_trace(48, offered_rps=300.0, seed=5,
                            size_choices=(1, 1, 2, 4, 8))
    plan = plan_batches(trace, buckets, max_wait)
    # Determinism: the same seeded trace replans to the same batches.
    assert plan == plan_batches(trace, buckets, max_wait)
    assert plan != plan_batches(trace, buckets, max_wait * 4)

    # Coverage: every request rides exactly once, in FIFO order.
    ridden = [i for b in plan for i in b["requests"]]
    assert ridden == list(range(len(trace)))
    for b in plan:
        # The recorded totals are consistent and fit the chosen bucket,
        # which is the smallest covering one.
        assert b["images"] == sum(trace[i][1] for i in b["requests"])
        assert b["bucket"] == smallest_bucket(buckets, b["images"])
        # No dispatch is released before its requests arrive, and none
        # later than the oldest request's deadline.
        first_t = trace[b["requests"][0]][0]
        last_t = max(trace[i][0] for i in b["requests"])
        assert last_t <= b["t"] + 1e-9
        assert b["t"] <= first_t + max_wait + 1e-9


def test_plan_batches_zero_wait_degenerates_to_per_request():
    trace = synthetic_trace(16, offered_rps=50.0, seed=2,
                            size_choices=(1, 2))
    plan = plan_batches(trace, (2, 4), 0.0)
    # Distinct arrival stamps + zero wait: nothing ever coalesces.
    assert len(plan) == len(trace)
    assert all(len(b["requests"]) == 1 for b in plan)


def test_plan_batches_rejects_oversized_request():
    with pytest.raises(ValueError, match="exceeds the largest"):
        plan_batches([(0.0, 9)], (2, 4, 8), 0.01)


def test_synthetic_trace_seeded():
    a = synthetic_trace(20, offered_rps=30.0, seed=4)
    assert a == synthetic_trace(20, offered_rps=30.0, seed=4)
    assert a != synthetic_trace(20, offered_rps=30.0, seed=5)
    assert a[0][0] == 0.0
    assert all(t1 <= t2 for (t1, _), (t2, _) in zip(a, a[1:]))


def test_parse_buckets():
    assert parse_buckets("8,1,32") == (1, 8, 32)
    assert parse_buckets("4,4") == (4,)


# -- threaded micro-batcher ---------------------------------------------------

def test_microbatcher_returns_each_request_its_own_rows(engine, pool):
    """Futures resolve to the submitting request's exact logits rows —
    bitwise equal to serving each request alone."""
    rng = np.random.default_rng(0)
    sizes = [1, 3, 2, 8, 1, 4, 5, 2]
    reqs = [pool.images[rng.integers(0, len(pool.images), size=s)]
            for s in sizes]
    with MicroBatcher(engine, max_wait_ms=2.0) as mb:
        futs = [mb.submit(imgs) for imgs in reqs]
        outs = [f.result(timeout=30) for f in futs]
    for imgs, out in zip(reqs, outs):
        assert out.shape == (len(imgs), 10)
        assert np.array_equal(out, engine.infer(imgs))


def test_microbatcher_lifecycle_and_bounds(engine, pool):
    mb = MicroBatcher(engine)
    with pytest.raises(RuntimeError, match="not running"):
        mb.submit(pool.images[:1])
    with mb:
        with pytest.raises(ValueError, match="exceeds the largest"):
            mb.submit(pool.images[:9])   # > max_batch, before enqueue
    with pytest.raises(RuntimeError, match="already started"):
        mb.start() and mb.start()


class _GatedEngine:
    """Engine stub whose dispatch blocks on an event: makes queue-pressure
    tests deterministic (the worker is provably busy while we fill)."""

    buckets = (8,)
    max_batch = 8
    telemetry = NULL

    def __init__(self):
        self.gate = threading.Event()
        self.calls = []

    def infer_counts(self, images, labels, precision="f32"):
        self.gate.wait(timeout=30)
        self.calls.append(len(images))
        return np.zeros((len(images), 10), np.float32), 0.0, 0


def test_microbatcher_bounded_queue_rejects():
    eng = _GatedEngine()
    with MicroBatcher(eng, max_wait_ms=0.0, max_queue_images=8) as mb:
        first = mb.submit(np.zeros((8, 32, 32, 3), np.uint8))
        # The worker owns the first batch (blocked at the gate); the queue
        # itself now has room for exactly one more full bucket.
        deadline = time.time() + 5
        while time.time() < deadline:
            with mb._cond:
                if not mb._pending:
                    break
            time.sleep(0.001)
        second = mb.submit(np.zeros((8, 32, 32, 3), np.uint8))
        with pytest.raises(QueueFull) as ei:
            mb.submit(np.zeros((1, 32, 32, 3), np.uint8))
        # Backpressure hint: queue depth x service EWMA (10 ms prior
        # before the first dispatch completes), never a bare reject.
        assert ei.value.retry_after_ms > 0.0
        eng.gate.set()
        first.result(timeout=30)
        second.result(timeout=30)
    assert eng.calls == [8, 8]


class _FailingEngine:
    buckets = (4,)
    max_batch = 4
    telemetry = NULL

    def infer_counts(self, images, labels, precision="f32"):
        raise RuntimeError("device fell over")


def test_microbatcher_propagates_engine_failure():
    with MicroBatcher(_FailingEngine(), max_wait_ms=0.0) as mb:
        fut = mb.submit(np.zeros((2, 32, 32, 3), np.uint8))
        with pytest.raises(RuntimeError, match="fell over"):
            fut.result(timeout=30)


# -- warm-start executable cache ----------------------------------------------

@pytest.mark.parametrize("device_index", [None, 3])
def test_executable_cache_roundtrip(tmp_path, pool, device_index):
    """Cold startup compiles + saves; a fresh engine on the same dir loads
    every rung from cache and serves bitwise-identical logits ON ITS OWN
    DEVICE — unpinned (default device) and as a replica pinned to device
    index >= 1 of the 8-device mesh, where an executable loaded for "all
    local devices" dies at its first call."""
    import jax
    device = None if device_index is None else jax.devices()[device_index]
    kw = dict(buckets=(2, 4), seed=0, cache_dir=str(tmp_path), device=device)
    cold = InferenceEngine("tiny", **kw)
    r_cold = cold.startup()
    assert not r_cold["warm"]
    assert all(v["source"] == "compile"
               for v in r_cold["per_bucket"].values())

    warm = InferenceEngine("tiny", **kw)
    r_warm = warm.startup()
    assert r_warm["warm"]
    assert all(v["source"] == "cache"
               for v in r_warm["per_bucket"].values())
    assert r_warm["executable_cache"]["hits"] == 2
    assert r_warm["startup_s"] < r_cold["startup_s"]
    for n in (1, 3):
        assert np.array_equal(cold.infer(pool.images[:n]),
                              warm.infer(pool.images[:n]))
    want = jax.devices()[device_index or 0]
    ex = warm._executable(2, "f32")
    logits, _, _ = ex(warm.params, warm.bn_state,
                      np.zeros((2, 32, 32, 3), np.uint8),
                      np.full((2,), -1, np.int32))
    assert logits.devices() == {want}


def test_replica_warm_starts_from_another_devices_entry(tmp_path, pool):
    """One entry serves every replica: a ladder compiled (and saved) by the
    replica on device 0 loads for the replica on device 2, runs THERE, and
    answers with the same logits."""
    import jax
    kw = dict(buckets=(2,), seed=0, cache_dir=str(tmp_path))
    first = InferenceEngine("tiny", device=jax.devices()[0], **kw)
    assert not first.startup()["warm"]
    other = InferenceEngine("tiny", device=jax.devices()[2], **kw)
    report = other.startup()
    assert report["warm"] and report["device_id"] == jax.devices()[2].id
    logits = other._executable(2, "f32")(
        other.params, other.bn_state, pool.images[:2],
        np.full((2,), -1, np.int32))[0]
    assert logits.devices() == {jax.devices()[2]}
    assert np.array_equal(np.asarray(logits), first.infer(pool.images[:2]))


def test_executable_cache_treats_garbage_as_miss(tmp_path):
    import jax
    from cs744_ddp_tpu.serve.cache import ExecutableCache, cache_key
    cache = ExecutableCache(str(tmp_path))
    key = cache_key(bucket=2, model="x")
    with open(cache._path(key), "wb") as f:
        f.write(b"not a pickle")
    assert cache.load(key, jax.devices()[0]) is None
    assert cache.stats()["misses"] == 1


def test_cache_key_is_stable_and_field_sensitive():
    from cs744_ddp_tpu.serve.cache import cache_key
    assert cache_key(a=1, b="x") == cache_key(b="x", a=1)
    assert cache_key(a=1) != cache_key(a=2)


# -- staged ingest ------------------------------------------------------------

def test_staged_ingest_roundtrip_and_slot_reuse(pool):
    """Staged rows match the source with zeroed pads, and results staged
    earlier survive the arena cycling through all its slots."""
    ing = StagedIngest(8, nslots=2)
    batches = [pool.images[i * 8:i * 8 + n]
               for i, n in enumerate((3, 8, 5))]   # > nslots stages
    handles = [ing.stage(b, 8) for b in batches]
    for src, h in zip(batches, handles):
        got = np.asarray(h)
        assert got.shape == (8, 32, 32, 3)
        assert np.array_equal(got[:len(src)], src)
        assert not got[len(src):].any()   # deterministic zero padding


def test_staged_ingest_bounds(pool):
    ing = StagedIngest(8)
    with pytest.raises(ValueError, match="cannot stage"):
        ing.stage(pool.images[:0], 8)
    with pytest.raises(ValueError, match="cannot stage"):
        ing.stage(pool.images[:9], 8)
    with pytest.raises(ValueError, match="cannot stage"):
        ing.stage(pool.images[:4], 16)   # bucket beyond the arena


# -- telemetry-off path -------------------------------------------------------

class _ExplodingRecorder:
    """enabled=False recorder whose every method call fails the test: the
    disabled serving path must never touch the recorder (the NULL path's
    zero-allocation contract)."""

    enabled = False

    def __getattr__(self, name):
        raise AssertionError(
            f"telemetry.{name} touched with telemetry disabled")


def test_disabled_telemetry_is_never_touched(pool):
    eng = InferenceEngine("tiny", buckets=(2, 4), seed=0,
                          telemetry=_ExplodingRecorder())
    eng.startup()
    eng.infer_counts(pool.images[:3], pool.labels[:3])
    with MicroBatcher(eng, max_wait_ms=1.0) as mb:
        futs = [mb.submit(pool.images[:2]) for _ in range(4)]
        for f in futs:
            f.result(timeout=30)
    # And the shared NULL singleton holds no per-call state at all.
    assert not hasattr(NULL, "records")
    assert NULL.counter_totals() == {}


# -- end-to-end demo / cli ----------------------------------------------------

def _report_module(monkeypatch):
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
    import telemetry_report
    return telemetry_report


def test_cli_serve_demo_end_to_end(capsys, tmp_path, monkeypatch):
    import json

    from cs744_ddp_tpu import cli
    cli.main(["--serve-demo", "--model", "tiny", "--serve-buckets", "2,4",
              "--serve-requests", "12", "--serve-load", "300",
              "--serve-max-wait-ms", "2", "--serve-seed", "1",
              "--telemetry-out", str(tmp_path)])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(out) == {"startup", "demo"}
    assert set(out["startup"]["per_bucket"]) == {"2", "4"}
    demo = out["demo"]["300rps"]
    assert demo["completed"] + demo["rejected"] == 12
    assert demo["completed"] > 0 and "latency_ms" in demo
    # The run directory carries the serving manifest + events; the report
    # tool renders it (serving section present exactly when serve gauges
    # exist — tools/telemetry_report.py).
    tr = _report_module(monkeypatch)
    text = tr.render(str(tmp_path))
    assert "== serving ==" in text
    assert "request latency by bucket" in text
    man = json.loads((tmp_path / "manifest.json").read_text())
    assert man["mode"] == "serve"
    assert "compilation_cache" in man


def test_report_tolerates_run_without_serving_events(tmp_path, monkeypatch):
    """A plain training-run directory renders with no serving section."""
    from cs744_ddp_tpu.obs import Telemetry
    tr = _report_module(monkeypatch)
    tel = Telemetry(out_dir=str(tmp_path))
    tel.write_manifest({"model": "tiny"})
    tel.step(epoch=0, iter=0, loss=1.0, step_time=0.01)
    tel.finalize()
    assert "== serving ==" not in tr.render(str(tmp_path))
