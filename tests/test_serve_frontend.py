"""Serving-tier tests (round 9): continuous-batching SLO scheduler,
replica router, socket front-end (cs744_ddp_tpu/serve/) — all tier-1 CPU.

The pins, mirroring the ISSUE's acceptance bar:

* ``admit()`` is pure and deterministic — the same seeded trace replays
  to the identical plan (dispatches AND shed set), sheds the lowest tier
  earliest-to-miss first, and never sheds a high-tier request while a
  lower-tier batchmate could be deferred instead (the priority-inversion
  negative test).
* The virtual-time planners: continuous batching holds strictly lower
  p99 queue-wait than the micro-batcher's drain policy at matched load.
* The threaded scheduler accounts deadline misses (ok vs late vs shed)
  and backpressures with a QueueFull retry-after hint.
* The router places on the least-loaded live replica, falls through on
  QueueFull, and on replica death fails over every unfinished request —
  no accepted request is ever silently dropped (chaos ``replica_death``
  through real device-pinned engines).
* The socket front-end round-trips the wire protocol: served logits are
  BITWISE what the engine computes, overload replies carry the
  retry-after hint.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from cs744_ddp_tpu import models as model_zoo
from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.ft import ChaosPlan
from cs744_ddp_tpu.serve import (EngineReplica, FrontendClient,
                                 InferenceEngine, LoopbackClient, QueueFull,
                                 ReplicaRouter, ServiceModel, ServingFrontend,
                                 SLOScheduler, admit, make_request,
                                 plan_continuous, plan_drain,
                                 virtual_requests)
from cs744_ddp_tpu.serve.demo import synthetic_load_trace
from cs744_ddp_tpu.serve.frontend import (decode_reply, decode_request,
                                          encode_reply, encode_request)

from tinynet import tiny_cnn


def setup_module(module):
    model_zoo.register_model("tiny", tiny_cnn)


@pytest.fixture(scope="module")
def pool():
    return cifar10._synthetic_split(64, seed=5)


@pytest.fixture(scope="module")
def engine():
    model_zoo.register_model("tiny", tiny_cnn)
    return InferenceEngine("tiny", buckets=(2, 4, 8), seed=0)


# -- pure admission policy ----------------------------------------------------


def _vreq(n, tier, deadline, seq, t_arrival=0.0):
    reqs = virtual_requests([(t_arrival, n, tier, 0)])
    r = reqs[0]
    r.deadline = deadline
    r.seq = seq
    return r


def test_admit_determinism_over_seeded_trace():
    trace = synthetic_load_trace(300, offered_rps=800.0, seed=7)
    predict = {1: 0.001, 8: 0.004, 32: 0.012, 128: 0.04, 256: 0.07}.get
    buckets = (1, 8, 32, 128, 256)
    a = plan_continuous(virtual_requests(trace), buckets=buckets,
                        predict_s=predict)
    b = plan_continuous(virtual_requests(trace), buckets=buckets,
                        predict_s=predict)
    assert a == b                      # dispatches, records, shed set — all
    assert a["served"] + len(a["shed"]) == len(trace)


def test_admit_sheds_lowest_tier_earliest_miss_first():
    # Everyone predicted to miss, nobody deferrable: the shed order must
    # be lowest tier (largest tier number) first, earliest deadline first.
    pending = [_vreq(1, 0, 0.5, seq=1), _vreq(1, 1, 0.45, seq=2),
               _vreq(1, 1, 0.4, seq=3)]
    adm = admit(pending, 0.0, buckets=(4,), predict_s=lambda b: 1.0)
    assert adm.batch == ()
    assert [(r.seq, reason) for r, reason in adm.shed] == \
        [(3, "predicted_miss"), (2, "predicted_miss"), (1, "predicted_miss")]


def test_admit_sheds_already_late_with_reason():
    pending = [_vreq(1, 0, -1.0, seq=1), _vreq(1, 0, 10.0, seq=2)]
    adm = admit(pending, 0.0, buckets=(4,), predict_s=lambda b: 0.01)
    assert [r.seq for r in adm.batch] == [2]
    assert [(r.seq, reason) for r, reason in adm.shed] == [(1, "deadline")]
    # shed=False: late requests dispatch anyway.
    pending = [_vreq(1, 0, -1.0, seq=1)]
    adm = admit(pending, 0.0, buckets=(4,), predict_s=lambda b: 0.01,
                shed=False)
    assert [r.seq for r in adm.batch] == [1] and adm.shed == ()


def test_admit_defers_bulk_to_save_tight_slo():
    # A 20-image background request packs the batch into the slow 32
    # bucket and would drag the interactive request past its deadline.
    # admit() must DEFER the bulk (leave it queued — not shed) and
    # dispatch the tight request in the fast bucket.
    predict = {1: 0.01, 8: 0.02, 32: 0.5}.get
    tight = _vreq(1, 0, 0.1, seq=1)
    bulk = _vreq(20, 2, 10.0, seq=2)
    adm = admit([tight, bulk], 0.0, buckets=(1, 8, 32), predict_s=predict)
    assert adm.batch == (tight,)
    assert adm.bucket == 1
    assert adm.shed == ()              # deferred, not shed
    assert adm.predicted_done == pytest.approx(0.01)


def test_no_priority_inversion_under_overload():
    # Tiered overload: tier-0 traffic alone is schedulable by
    # construction (its 200ms SLO exceeds the 140ms worst case of one
    # in-flight dispatch plus its own — both <=70ms in this service
    # model), bulk tier-2 oversubscribes the ladder.  Whatever is shed,
    # it is never tier 0.
    trace = synthetic_load_trace(
        400, offered_rps=1500.0, seed=11,
        tiers=((0, 1, 200.0), (2, 9, 300.0)))
    predict = {1: 0.001, 8: 0.004, 32: 0.012, 128: 0.04, 256: 0.07}.get
    plan = plan_continuous(virtual_requests(trace),
                           buckets=(1, 8, 32, 128, 256), predict_s=predict)
    assert len(plan["shed"]) > 0       # genuinely overloaded
    assert all(tier == 2 for _trace, tier, _reason in plan["shed"])
    t0 = [rec for rec in plan["records"] if rec["tier"] == 0]
    assert t0 and all(rec["status"] == "ok" for rec in t0)


def test_continuous_beats_drain_p99_at_matched_load():
    trace = synthetic_load_trace(400, offered_rps=900.0, seed=3,
                                 tiers=((0, 1, 0),))   # no deadlines
    predict = {1: 0.001, 8: 0.004, 32: 0.012, 128: 0.04, 256: 0.07}.get
    buckets = (1, 8, 32, 128, 256)
    cont = plan_continuous(virtual_requests(trace), buckets=buckets,
                           predict_s=predict, shed=False)
    drain = plan_drain(virtual_requests(trace), buckets=buckets,
                       predict_s=predict)
    assert cont["served"] == drain["served"] == len(trace)
    assert cont["p99_wait_ms"] < drain["p99_wait_ms"]


def test_service_model_prior_and_ewma():
    svc = ServiceModel((2, 4, 8), anchor_s=1e-3)
    # Prior: anchored at the smallest bucket, scaled by weight (= size).
    assert svc.predict(2) == pytest.approx(1e-3)
    assert svc.predict(8) == pytest.approx(4e-3)
    # One observation re-anchors every bucket through the weight ratio.
    svc.observe(4, 0.010)
    assert svc.predict(4) == pytest.approx(0.010)
    assert svc.predict(8) == pytest.approx(0.020)
    # EWMA, not last-sample.
    svc.observe(4, 0.020)
    assert 0.010 < svc.predict(4) < 0.020
    snap = svc.snapshot()
    assert set(snap) == {2, 4, 8}
    with pytest.raises(ValueError, match="missing buckets"):
        ServiceModel((2, 4), weights={2: 1.0})


# -- threaded scheduler -------------------------------------------------------


class StubEngine:
    """Engine stand-in: fixed service sleep, zero logits, dispatch log."""

    def __init__(self, buckets=(1, 2, 4), service_s=0.0, fail_at=None):
        self.buckets = tuple(buckets)
        self.max_batch = self.buckets[-1]
        self.service_s = service_s
        self.fail_at = fail_at
        self.calls = []
        self.gate = None

    def infer_counts(self, images, labels=None, *, precision="f32",
                     trace_ids=None):
        if self.fail_at is not None and len(self.calls) >= self.fail_at:
            raise RuntimeError("stub engine exploded")
        self.calls.append(int(images.shape[0]))
        if self.gate is not None:
            self.gate.wait(5.0)
        if self.service_s:
            time.sleep(self.service_s)
        return np.zeros((images.shape[0], 10), np.float32), 0, 0


def _imgs(n):
    return np.zeros((n, 32, 32, 3), np.uint8)


def test_scheduler_deadline_miss_accounting():
    # shed=False so late requests are SERVED and reported late.
    eng = StubEngine(service_s=0.05)
    with SLOScheduler(eng, shed=False) as sched:
        late = sched.submit(_imgs(1), slo_ms=1.0)
        ok = sched.submit(_imgs(1), slo_ms=10_000.0)
        r_late, r_ok = late.result(5.0), ok.result(5.0)
    assert r_late.status == "late" and r_ok.status == "ok"
    assert r_ok.logits.shape == (1, 10)
    for r in (r_late, r_ok):
        assert r.queue_wait_ms >= 0.0
        assert r.latency_ms == pytest.approx(
            r.queue_wait_ms + r.service_ms, abs=1.0)


def test_scheduler_sheds_doomed_requests():
    eng = StubEngine(service_s=0.05)
    with SLOScheduler(eng, shed=True) as sched:
        gate_first = sched.submit(_imgs(1), slo_ms=10_000.0)
        doomed = sched.submit(_imgs(1), slo_ms=0.001)  # already late
        r = doomed.result(5.0)
    assert r.status == "shed" and r.reason in ("deadline", "predicted_miss")
    assert gate_first.result(5.0).status == "ok"


def test_scheduler_queuefull_retry_hint():
    # Unstarted scheduler: nothing drains, so the bounded queue fills and
    # the QueueFull carries a positive backlog-derived retry hint.
    eng = StubEngine(buckets=(1, 2, 4))
    sched = SLOScheduler(eng, max_queue_images=4)
    sched.submit(_imgs(4), slo_ms=None)
    with pytest.raises(QueueFull) as ei:
        sched.submit(_imgs(2), slo_ms=None)
    assert ei.value.retry_after_ms > 0.0
    assert sched.queue_depth() == 4


# -- router -------------------------------------------------------------------


class StubSched:
    """Bare scheduler stand-in for routing-policy tests."""

    class _Eng:
        max_batch = 8

    def __init__(self, replica, outstanding=0.0, alive=True, full=False):
        self.engine = self._Eng()
        self.replica = replica
        self.buckets = (8,)
        self.svc = ServiceModel((8,))
        self.alive = alive
        self.full = full
        self._outstanding = outstanding
        self.got = []
        self.on_death = None

    def outstanding_s(self):
        return self._outstanding

    def enqueue(self, req):
        if self.full:
            raise QueueFull(f"stub {self.replica} full",
                            retry_after_ms=10.0 * (self.replica + 1))
        self.got.append(req)
        return req.future


def test_router_routes_least_loaded_with_fallthrough():
    scheds = [StubSched(0, 0.3), StubSched(1, 0.1), StubSched(2, 0.2)]
    router = ReplicaRouter(scheds)
    router.submit(_imgs(1))
    assert [len(s.got) for s in scheds] == [0, 1, 0]
    # Least-loaded now full: falls through to the next by load.
    scheds[1].full = True
    router.submit(_imgs(1))
    assert [len(s.got) for s in scheds] == [0, 1, 1]
    # Everyone full: QueueFull with the SMALLEST hint across replicas.
    for s in scheds:
        s.full = True
    with pytest.raises(QueueFull) as ei:
        router.submit(_imgs(1))
    assert ei.value.retry_after_ms == pytest.approx(10.0)
    # Nobody alive: explicit error, not a hang.
    for s in scheds:
        s.full, s.alive = False, False
    with pytest.raises(RuntimeError, match="no live replicas"):
        router.submit(_imgs(1))


def test_router_ties_break_by_replica_index():
    scheds = [StubSched(0, 0.0), StubSched(1, 0.0)]
    router = ReplicaRouter(scheds)
    for _ in range(3):
        router.submit(_imgs(1))
    assert [len(s.got) for s in scheds] == [3, 0]


def test_router_failover_resolves_every_request():
    # Replica 0's engine dies on its FIRST dispatch while more requests
    # are queued behind it: every unfinished request (in-flight AND
    # queued) must fail over to replica 1 and resolve ok — zero silent
    # drops, zero errors.
    dead_eng = StubEngine(service_s=0.02, fail_at=0)
    live_eng = StubEngine(service_s=0.0)
    s0 = SLOScheduler(dead_eng, replica=0)
    s1 = SLOScheduler(live_eng, replica=1)
    router = ReplicaRouter([s0, s1])
    with router:
        futs = [router.submit(_imgs(1), slo_ms=None) for _ in range(10)]
        replies = [f.result(10.0) for f in futs]
    assert [r.status for r in replies] == ["ok"] * 10
    assert all(r.replica == 1 for r in replies)
    assert len({r.trace for r in replies}) == 10
    stats = router.stats()
    assert stats["failovers"] >= 1
    assert not s0.alive


def test_replica_death_chaos_failover_end_to_end(pool):
    # Real device-pinned engines; chaos kills replica 0 at its first
    # dispatch; the router fails over and every request still gets its
    # logits.  (``replica_death:0:0`` = dispatch 0 of replica 0.)
    model_zoo.register_model("tiny", tiny_cnn)
    chaos = ChaosPlan.parse(["replica_death:0:0"])
    replicas = [EngineReplica(i, model="tiny", buckets=(2, 4), seed=0,
                              chaos=chaos)
                for i in range(2)]
    router = ReplicaRouter(replicas)
    with router:
        futs = [router.submit(pool.images[i:i + 2], slo_ms=None)
                for i in range(8)]
        replies = [f.result(30.0) for f in futs]
        assert not replicas[0].alive and replicas[1].alive
    assert [r.status for r in replies] == ["ok"] * 8
    assert all(r.logits.shape == (2, 10) for r in replies)
    assert len({r.trace for r in replies}) == 8
    assert router.stats()["failovers"] >= 1


# -- wire protocol + socket e2e ----------------------------------------------


def test_slow_replica_chaos_stalls_but_serves(pool):
    # ``slow_replica:0:0`` stalls replica 0's first dispatch (a straggling
    # chip): the request is served — slower, never dropped — and the
    # stall shows up in the measured latency the router's EWMA feeds on.
    model_zoo.register_model("tiny", tiny_cnn)
    chaos = ChaosPlan.parse(["slow_replica:0:0"])
    replica = EngineReplica(0, model="tiny", buckets=(2,), seed=0,
                            chaos=chaos, slow_stall_s=0.15)
    router = ReplicaRouter([replica])
    with router:
        rep = router.submit(pool.images[:2], slo_ms=None).result(30.0)
    assert rep.status == "ok"
    assert ("slow_replica", 0) in chaos.fired
    assert rep.service_ms >= 150.0


def test_wire_codec_roundtrip(pool):
    imgs = pool.images[:3]
    payload = encode_request(7, imgs, tier=2, slo_ms=125.0)
    req_id, out, tier, slo = decode_request(payload)
    assert (req_id, tier, slo) == (7, 2, 125.0)
    assert np.array_equal(out, imgs)
    logits = np.arange(30, dtype=np.float32).reshape(3, 10)
    rep = decode_reply(encode_reply(7, {
        "status": "ok", "trace": 99, "logits": logits, "reason": "",
        "queue_wait_ms": 1.5, "service_ms": 2.5, "retry_after_ms": 0.0}))
    assert rep["status"] == "ok" and rep["trace"] == 99
    assert np.array_equal(rep["logits"], logits)
    assert rep["queue_wait_ms"] == 1.5 and rep["service_ms"] == 2.5


def test_socket_e2e_logits_bitwise(engine, pool):
    imgs = pool.images[:2]
    direct, _, _ = engine.infer_counts(imgs)
    with SLOScheduler(engine) as sched:
        with ServingFrontend(sched) as fe:
            with FrontendClient(fe.address, timeout=30.0) as client:
                rep = client.request(imgs, slo_ms=None)
    assert rep["status"] == "ok" and rep["trace"] > 0
    assert np.array_equal(rep["logits"], np.asarray(direct))


def test_socket_pipelined_out_of_order_replies(engine, pool):
    with SLOScheduler(engine) as sched:
        with ServingFrontend(sched) as fe:
            with FrontendClient(fe.address, timeout=30.0) as client:
                futs = [client.submit(pool.images[i:i + 1], slo_ms=None)
                        for i in range(6)]
                reps = [f.result(30.0) for f in futs]
    assert all(r["status"] == "ok" for r in reps)
    assert len({r["trace"] for r in reps}) == 6


class FullBackend:
    def submit(self, images, labels=None, *, tier=0, slo_ms=None):
        raise QueueFull("full", retry_after_ms=42.0)


def test_socket_overload_reply_carries_retry_hint():
    with ServingFrontend(FullBackend()) as fe:
        with FrontendClient(fe.address, timeout=10.0) as client:
            rep = client.request(_imgs(1))
    assert rep["status"] == "overload" and rep["reason"] == "queue_full"
    assert rep["retry_after_ms"] == pytest.approx(42.0)


def test_loopback_overload_is_reply_not_exception():
    client = LoopbackClient(FullBackend())
    rep = client.request(_imgs(1))
    assert rep["status"] == "overload"
    assert rep["retry_after_ms"] == pytest.approx(42.0)


def test_telemetry_report_slo_section(tmp_path, monkeypatch):
    """The scheduler's per-request gauges/counters render as the report's
    ``== slo ==`` section (tiered attainment, shed reasons); a run with
    no SLO signal renders without it — absent-safe for older runs."""
    import os
    from cs744_ddp_tpu.obs import Telemetry
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
    import telemetry_report

    served = tmp_path / "served"
    tel = Telemetry(out_dir=str(served))
    eng = StubEngine(service_s=0.01)
    with SLOScheduler(eng, telemetry=tel) as sched:
        ok = sched.submit(_imgs(1), tier=0, slo_ms=10_000.0)
        shed = sched.submit(_imgs(1), tier=2, slo_ms=0.001)
        ok.result(5.0), shed.result(5.0)
    tel.finalize()
    text = telemetry_report.render(str(served))
    assert "== slo (tiered attainment) ==" in text
    assert "tier 0" in text and "tier 2" in text
    assert "shed by reason" in text

    plain = tmp_path / "plain"
    tel2 = Telemetry(out_dir=str(plain))
    tel2.step(epoch=0, iter=0, loss=1.0, step_time=0.01)
    tel2.finalize()
    assert "== slo" not in telemetry_report.render(str(plain))


def test_make_request_validation():
    with pytest.raises(ValueError, match="empty"):
        make_request(_imgs(0))
    with pytest.raises(ValueError, match="exceeds the largest"):
        make_request(_imgs(9), max_batch=8)
    with pytest.raises(ValueError, match="labels shape"):
        make_request(_imgs(2), labels=np.zeros(3, np.int32))
    req = make_request(_imgs(2), slo_ms=None)
    assert req.deadline == float("inf") and isinstance(req.future, Future)


# -- dispatch pipeline (round 14) ---------------------------------------------


def test_admit_free_at_two_slot_semantics():
    """``admit(free_at=)`` — pipelined second-slot admission: predicted
    completions are measured from when the engine actually frees a slot,
    not the admission instant; ``None`` / a past ``free_at`` (idle
    pipeline) is the round-13 policy bit-for-bit; already-late shed is
    still judged against NOW."""
    svc = ServiceModel((2, 4), anchor_s=0.010)
    now = 1000.0
    r = _vreq(2, 0, now + 0.035, seq=0)
    base = admit([r], now, buckets=(2, 4), predict_s=svc.predict)
    idle = admit([r], now, buckets=(2, 4), predict_s=svc.predict,
                 free_at=now - 5.0)
    assert idle == base
    assert base.batch == (r,)
    assert base.predicted_done == pytest.approx(now + 0.010)
    # Second slot: the engine frees at now+20ms, so this batch completes
    # at now+30ms — still inside its deadline, admitted.
    busy = admit([r], now, buckets=(2, 4), predict_s=svc.predict,
                 free_at=now + 0.020)
    assert busy.batch == (r,)
    assert busy.predicted_done == pytest.approx(now + 0.030)
    # A deadline the idle slot makes but the busy slot cannot is a
    # predicted miss (nothing lower-priority to defer -> shed).
    tight = _vreq(2, 0, now + 0.012, seq=1)
    assert admit([tight], now, buckets=(2, 4),
                 predict_s=svc.predict).batch == (tight,)
    a = admit([tight], now, buckets=(2, 4), predict_s=svc.predict,
              free_at=now + 0.020)
    assert a.batch == ()
    assert [(req.seq, reason) for req, reason in a.shed] \
        == [(1, "predicted_miss")]
    # Already-late: shed as "deadline" vs NOW, free_at irrelevant.
    late = _vreq(2, 0, now - 1.0, seq=2)
    a2 = admit([late], now, buckets=(2, 4), predict_s=svc.predict,
               free_at=now + 0.020)
    assert [(req.seq, reason) for req, reason in a2.shed] \
        == [(2, "deadline")]


def test_scheduler_rejects_pipeline_without_async_engine():
    with pytest.raises(ValueError, match="infer_counts_async"):
        SLOScheduler(StubEngine(), pipeline=True)
    # Auto-detection: a bare infer_counts engine falls back to serial.
    assert SLOScheduler(StubEngine()).pipeline is False


def test_pipelined_bitwise_vs_serial_seeded_trace(pool):
    """Tentpole pin: the pipelined worker answers a mixed-bucket trace
    (ragged tail included) bitwise-identically to the serial round-13
    worker.  Batch composition may differ between the two runs — rows
    are batchmate-invariant (train=False BN, pinned in test_serve.py) —
    so the per-request logits must still match exactly."""
    sizes = [1, 3, 2, 4, 8, 5, 2, 1, 7, 3, 4, 6]

    def _serve(pipeline):
        rep = EngineReplica(0, model="tiny", buckets=(2, 4, 8), seed=0,
                            pipeline=pipeline)
        assert rep.scheduler.pipeline is pipeline
        futs, off = [], 0
        for n in sizes:
            futs.append(rep.scheduler.submit(pool.images[off:off + n],
                                             slo_ms=None))
            off += n
        with rep.scheduler:
            return [f.result(60.0) for f in futs]

    serial = _serve(False)
    piped = _serve(True)
    assert [r.status for r in serial] == ["ok"] * len(sizes)
    assert [r.status for r in piped] == ["ok"] * len(sizes)
    for a, b in zip(serial, piped):   # futures in submit order
        np.testing.assert_array_equal(a.logits, b.logits)
    # The accounting invariant survives the overlap: latency decomposes
    # into queue wait + service, with service the fence-to-fence window
    # of the request's own dispatch (not the overlapped wall clock).
    for r in piped:
        assert r.latency_ms == pytest.approx(
            r.queue_wait_ms + r.service_ms, abs=1.0)


def test_pipelined_occupancy_bound_and_span_causality(pool):
    """Runtime two-slot occupancy meets the static bound exactly, and
    the engine's async spans stay causally attributable: each
    ``serve_dispatch``/``serve_fetch`` span names exactly its batch's
    trace ids, and the dispatch spans are occupancy-honest — clipped to
    issue order, never overlapping."""
    from cs744_ddp_tpu.analysis import dispatch as dispatchlib
    from cs744_ddp_tpu.obs import Telemetry

    tel = Telemetry()           # in-memory recorder
    rep = EngineReplica(0, model="tiny", buckets=(2, 4), seed=0,
                        telemetry=tel, pipeline=True)
    # Full-max-bucket requests, submitted before the worker starts: each
    # dispatch carries exactly one request, and the queue holds several
    # dispatches at start so the second slot MUST fill.
    futs = [rep.scheduler.submit(pool.images[4 * i:4 * i + 4], slo_ms=None)
            for i in range(5)]
    with rep.scheduler:
        replies = [f.result(60.0) for f in futs]
    assert [r.status for r in replies] == ["ok"] * 5
    events = tel.records
    bound = dispatchlib.serving_inflight_bound()
    assert bound == 2
    assert dispatchlib.max_serving_inflight(events) == bound
    dspans = [e for e in events if e.get("kind") == "span"
              and e["name"] == "serve_dispatch"]
    fspans = [e for e in events if e.get("kind") == "span"
              and e["name"] == "serve_fetch"]
    assert len(dspans) == len(fspans) == 5
    want = [[r.trace] for r in replies]
    assert [d["traces"] for d in dspans] == want
    assert [f["traces"] for f in fspans] == want
    for prev, nxt in zip(dspans, dspans[1:]):
        assert nxt["t_ns"] >= prev["t_ns"] + prev["dur_ns"] - 1000


def test_telemetry_report_pipeline_section(tmp_path, monkeypatch):
    """The pipelined worker's occupancy gauges and fault counter render
    as ``== dispatch pipeline ==``; a serial run renders without it —
    absent-safe for older runs."""
    import os
    from cs744_ddp_tpu.obs import Telemetry
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
    import telemetry_report

    run = tmp_path / "piped"
    tel = Telemetry(out_dir=str(run))
    for v in (1, 2, 2, 1, 0):
        tel.gauge("serve_inflight", v, replica=0)
    tel.counter("serve_dispatch_fault", bucket=4, replica=0,
                error="ChaosError")
    tel.finalize()
    text = telemetry_report.render(str(run))
    assert "== dispatch pipeline ==" in text
    assert "replica 0" in text and "max 2" in text
    assert "dispatch faults        1" in text

    plain = tmp_path / "plain"
    tel2 = Telemetry(out_dir=str(plain))
    tel2.step(epoch=0, iter=0, loss=1.0, step_time=0.01)
    tel2.finalize()
    assert "== dispatch pipeline" not in telemetry_report.render(str(plain))
