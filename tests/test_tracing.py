"""Distributed tracing + SLO alerting tests (round 12) — all tier-1 CPU.

The pins, mirroring the ISSUE's acceptance bar:

* Wire-protocol forward compat BOTH directions: extension-free frames
  are byte-identical to the pre-round-12 layout and decode everywhere;
  extended frames decode on the old 4-tuple surface with the extension
  dropped; unknown TLV tags are skipped by length; non-extension
  trailing bytes still fail decode (torn frames never pass silently).
* Cross-process aggregation: NTP-midpoint skew correction stays within
  the RTT/2 bound even under asymmetric path delays; torn tails and
  rotated event files degrade gracefully; a replica death leaves an
  ORPHANED (complete=False) but attributable waterfall.
* The alert engine's chaos drills fire EXACTLY their expected rule ids
  (slow_replica -> STRAGGLER+SLO_BURN; publish_torn -> PUBLISH_LAG;
  clean -> none), and replaying a log yields the live alert sequence.
* The acceptance scenario: one request served across two real OS
  processes reconstructs into a single skew-corrected waterfall whose
  stage sum is bounded by the client-measured latency.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from cs744_ddp_tpu import models as model_zoo
from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.ft import ChaosPlan
from cs744_ddp_tpu.obs import AlertEngine, Telemetry, TraceContext
from cs744_ddp_tpu.obs import aggregate
from cs744_ddp_tpu.obs.telemetry import read_events_jsonl
from cs744_ddp_tpu.obs.tracing import (EXT_MAGIC, TAG_TRACE, new_id,
                                       pack_ext, pack_trace, unpack_ext,
                                       unpack_ext_ex, unpack_trace)
from cs744_ddp_tpu.serve import (EngineReplica, LoopbackClient,
                                 ReplicaRouter, ServingFrontend)
from cs744_ddp_tpu.serve.frontend import (decode_reply, decode_request,
                                          decode_request_ex, encode_reply,
                                          encode_request)

from tinynet import tiny_cnn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_module(module):
    model_zoo.register_model("tiny", tiny_cnn)


@pytest.fixture(scope="module")
def pool():
    return cifar10._synthetic_split(64, seed=5)


# -- trace context + wire extension codec -------------------------------------


def test_trace_context_lineage():
    root = TraceContext.new_root("client")
    assert root.trace_id and root.span_id and root.parent_span_id == 0
    child = root.child("frontend")
    assert child.trace_id == root.trace_id
    assert child.parent_span_id == root.span_id
    assert child.span_id not in (0, root.span_id)
    a = child.attrs()
    assert a == {"trace_id": child.trace_id, "span_id": child.span_id,
                 "parent_span_id": root.span_id, "origin": "frontend"}
    assert all(new_id() != 0 for _ in range(64))


def test_ext_block_skips_unknown_tags_and_tolerates_torn():
    ctx = TraceContext.new_root("client")
    blob = pack_ext({TAG_TRACE: pack_trace(ctx), 99: b"future-field"})
    fields = unpack_ext(blob)
    assert unpack_trace(fields[TAG_TRACE]) == ctx
    assert fields[99] == b"future-field"       # unknown tag carried by len
    # Torn mid-field: the partial trailing field is dropped, not fatal.
    assert TAG_TRACE not in unpack_ext(blob[:6])
    # Wrong magic/version degrades to "no extension", never raises.
    assert unpack_ext(b"\x00" + blob[1:]) == {}
    assert unpack_ext(b"") == {}


def test_ext_block_counts_skipped_and_torn():
    """Round 13: ``unpack_ext_ex`` COUNTS what forward-compat skipping
    silently tolerated — unknown tags (still carried) and dropped torn
    trailing fields — so the codec can surface cross-version drift."""
    ctx = TraceContext.new_root("client")
    blob = pack_ext({TAG_TRACE: pack_trace(ctx), 99: b"future-field"})
    fields, skipped, torn = unpack_ext_ex(blob)
    assert unpack_trace(fields[TAG_TRACE]) == ctx
    assert fields[99] == b"future-field" and (skipped, torn) == (1, 0)
    # Torn trailing field: dropped and counted; earlier fields survive.
    fields, skipped, torn = unpack_ext_ex(blob[:-1])
    assert TAG_TRACE in fields and 99 not in fields
    assert (skipped, torn) == (0, 1)
    # A clean all-known block counts nothing.
    clean = pack_ext({TAG_TRACE: pack_trace(ctx)})
    assert unpack_ext_ex(clean)[1:] == (0, 0)
    # Missing/unversioned blocks stay zero-count empty, never raising.
    assert unpack_ext_ex(b"") == ({}, 0, 0)
    assert unpack_ext_ex(b"\x00" + blob[1:]) == ({}, 0, 0)


def test_wire_ext_skipped_counter_emission(pool):
    """The decoders feed skip/torn counts into the ``wire_ext_skipped``
    telemetry counter, attributed per frame kind — and clean frames
    emit nothing."""
    root = TraceContext.new_root("client")
    traced = encode_request(4, pool.images[:2], tier=2, slo_ms=25.0,
                            ctx=root)

    def skips(tel):
        return [r for r in tel.records if r.get("kind") == "counter"
                and r.get("name") == "wire_ext_skipped"]

    tel = Telemetry()
    assert decode_request_ex(traced, tel)[4] == root
    assert skips(tel) == []                    # same-build frame: silent
    future = traced + pack_ext({7: b"xyz"})[2:]
    assert decode_request_ex(future, tel)[4] == root
    (rec,) = skips(tel)
    assert (rec["inc"], rec["unknown"], rec["torn"]) == (1, 1, 0)
    assert rec["frame"] == "request"
    # A torn trailing field on a reply counts on the reply side; the
    # known fields still decode.
    logits = np.arange(20, dtype=np.float32).reshape(2, 10)
    rep = {"status": "ok", "trace": 5, "logits": logits, "reason": "",
           "queue_wait_ms": 1.0, "service_ms": 2.0, "retry_after_ms": 0.0}
    timed = encode_reply(9, rep, t_recv=10.5, t_send=10.75)
    torn = timed + pack_ext({7: b"xyz"})[2:-1]
    tel2 = Telemetry()
    out = decode_reply(torn, tel2)
    assert (out["t_recv"], out["t_send"]) == (10.5, 10.75)
    (rec,) = skips(tel2)
    assert (rec["inc"], rec["unknown"], rec["torn"]) == (1, 0, 1)
    assert rec["frame"] == "reply"


def test_telemetry_report_wire_ext_section(tmp_path, monkeypatch, pool):
    """tools/telemetry_report surfaces the skip counts as a
    ``== wire extension skips ==`` section — absent on same-build runs."""
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import telemetry_report
    root = TraceContext.new_root("client")
    traced = encode_request(4, pool.images[:2], tier=2, slo_ms=25.0,
                            ctx=root)
    future = traced + pack_ext({7: b"xyz"})[2:]
    run = tmp_path / "run"
    tel = Telemetry(str(run))
    decode_request_ex(future, tel)
    tel.finalize()
    text = telemetry_report.render(str(run))
    assert "== wire extension skips ==" in text
    assert "request" in text and "unknown tags skipped 1" in text

    plain = tmp_path / "plain"
    tel2 = Telemetry(str(plain))
    tel2.step(epoch=0, iter=0, loss=1.0, step_time=0.01)
    tel2.finalize()
    assert "wire extension" not in telemetry_report.render(str(plain))


def test_wire_request_compat_both_directions(pool):
    imgs = pool.images[:2]
    # Direction 1: NEW encoder, tracing off -> byte-identical to the
    # pre-round-12 frame (zero wire cost), and ctx decodes as None.
    plain = encode_request(3, imgs, tier=1, slo_ms=50.0)
    assert plain == encode_request(3, imgs, tier=1, slo_ms=50.0, ctx=None)
    req_id, out, tier, slo, ctx = decode_request_ex(plain)
    assert (req_id, tier, slo, ctx) == (3, 1, 50.0, None)
    assert np.array_equal(out, imgs)
    # Direction 2: NEW traced frame on the OLD 4-tuple surface — the
    # extension is tolerated and dropped, images bitwise intact.
    root = TraceContext.new_root("client")
    traced = encode_request(4, imgs, tier=2, slo_ms=25.0, ctx=root)
    assert traced[:len(plain)] != plain        # different header fields
    req_id, out, tier, slo = decode_request(traced)
    assert (req_id, tier, slo) == (4, 2, 25.0)
    assert np.array_equal(out, imgs)
    # And the new surface recovers the full context.
    *_, ctx2 = decode_request_ex(traced)
    assert ctx2 == root
    # A future field rides along without breaking today's decoder.
    future = traced + pack_ext({7: b"xyz"})[2:]   # splice extra TLV
    assert decode_request_ex(future)[4] == root
    # Non-extension trailing garbage is a TORN frame: still fails.
    with pytest.raises(ValueError, match="not an extension block"):
        decode_request_ex(plain + b"garbage!")


def test_wire_reply_compat_both_directions():
    logits = np.arange(20, dtype=np.float32).reshape(2, 10)
    rep = {"status": "ok", "trace": 5, "logits": logits, "reason": "",
           "queue_wait_ms": 1.0, "service_ms": 2.0, "retry_after_ms": 0.0}
    plain = encode_reply(9, rep)
    out = decode_reply(plain)
    assert "t_recv" not in out and np.array_equal(out["logits"], logits)
    timed = encode_reply(9, rep, t_recv=10.5, t_send=10.75)
    assert timed[:len(plain)] == plain         # strictly trailing ext
    assert timed[len(plain)] == EXT_MAGIC
    out = decode_reply(timed)
    assert (out["t_recv"], out["t_send"]) == (10.5, 10.75)
    assert np.array_equal(out["logits"], logits)
    with pytest.raises(ValueError, match="not an extension block"):
        decode_reply(plain + b"\x00\x01")


# -- aggregation --------------------------------------------------------------


def _span(name, t, dur, ctx, **extra):
    return {"kind": "span", "name": name, "t_ns": round(t * 1e9),
            "dur_ns": round(dur * 1e9), **ctx.attrs(), **extra}


def _stream_pair(n=20, offset=5.0, d_req=0.001, d_rep=0.009):
    """Client+server streams with a KNOWN clock offset and asymmetric
    path delays: request leg ``d_req``, reply leg ``d_rep`` seconds."""
    client, server = [], []
    for i in range(n):
        root = TraceContext.new_root("client")
        t1 = 100.0 + i
        t2 = t1 + d_req + offset          # server clock
        t3 = t2 + 0.002
        t4 = (t3 - offset) + d_rep        # back on the client clock
        client.append(_span("trace_client", t1, t4 - t1, root))
        server.append(_span("frontend_request", t2, t3 - t2,
                            root.child("frontend")))
    return (aggregate.ProcessStream("client", client),
            aggregate.ProcessStream("server", server))


def test_skew_asymmetric_rtt_stays_within_bound():
    # NTP midpoint under ASYMMETRIC legs: the estimate is biased by
    # (d_req - d_rep)/2 but the reported rtt bound must still cover the
    # true offset — that inequality is the whole point of the bound.
    d_req, d_rep, offset = 0.001, 0.009, 5.0
    cli, srv = _stream_pair(offset=offset, d_req=d_req, d_rep=d_rep)
    est = aggregate.estimate_offsets([srv, cli])
    # Server (reference) pinned at zero; client estimated from all pairs.
    assert est["server"] == aggregate.ClockEstimate(0.0, 0.0, 0, True)
    c = est["client"]
    assert c.estimated and c.n_pairs == 20
    assert c.offset_s == pytest.approx(offset + (d_req - d_rep) / 2.0,
                                       abs=1e-9)
    assert abs(c.offset_s - offset) <= c.rtt_bound_s + 1e-12
    assert c.rtt_bound_s == pytest.approx((d_req + d_rep) / 2.0, abs=1e-9)
    # The merged spans land on ONE timeline: client span starts before
    # the server window it encloses, despite the 5s raw clock gap.
    report = aggregate.aggregate_streams([srv, cli])
    assert report["reference"] == "server"
    assert report["traces"] == 20 and report["orphaned"] == 20  # no stages
    traces = aggregate.merge_traces([srv, cli], est)
    for spans in traces.values():
        assert [s["name"] for s in spans] == ["trace_client",
                                              "frontend_request"]


def test_aggregate_rotated_and_torn_event_files(tmp_path):
    # One trace's spans split across a ROTATED generation and the live
    # file, with a torn half-written line at the tail: the reader counts
    # the bad line, and the waterfall still reconstructs COMPLETE.
    root = TraceContext.new_root("client")
    sched = root.child("sched")
    d = tmp_path / "server"
    d.mkdir()
    old = [_span("wire_decode", 1.0, 0.001, root.child("frontend")),
           _span("sched_queue", 1.001, 0.002, sched, trace=7, bucket=2)]
    new = [_span("serve_dispatch", 1.003, 0.004,
                 TraceContext(0, 0, 0, ""), traces=[7], bucket=2),
           _span("reply_encode", 1.008, 0.001, root.child("frontend"))]
    new[0].pop("trace_id")        # batch spans carry traces=, not trace_id
    (d / "events.1.jsonl").write_text(
        "\n".join(json.dumps(e) for e in old) + "\n")
    (d / "events.jsonl").write_text(
        "\n".join(json.dumps(e) for e in new) + "\n"
        + '{"kind": "span", "name": "torn')      # killed mid-write
    cli = tmp_path / "client"
    cli.mkdir()
    (cli / "events.jsonl").write_text(
        json.dumps(_span("trace_client", 0.999, 0.012, root, trace=7))
        + "\n")
    report = aggregate.aggregate_run_dirs([str(d), str(cli)])
    assert report["processes"]["server"]["bad_lines"] == 1
    assert report["traces"] == 1 and report["complete"] == 1
    (w,) = report["waterfalls"]
    assert w["complete"] and w["bucket"] == 2
    assert set(w["stages"]) == {"wire_decode", "queue_wait",
                                "device_compute", "reply_encode"}
    assert w["client_ms"] == pytest.approx(12.0)


def test_aggregate_directory_with_only_rotated_generations(tmp_path):
    """Round 13 satellite: a process killed right after rotation leaves a
    directory with ONLY ``events.N.jsonl`` generations — no live
    ``events.jsonl``.  The reader must still yield the generations
    oldest-first and the multi-directory merge must reconstruct the
    cross-process waterfall COMPLETE."""
    root = TraceContext.new_root("client")
    sched = root.child("sched")
    d = tmp_path / "server"
    d.mkdir()
    gen1 = [_span("wire_decode", 1.0, 0.001, root.child("frontend")),
            _span("sched_queue", 1.001, 0.002, sched, trace=7, bucket=2)]
    gen2 = [_span("serve_dispatch", 1.003, 0.004,
                  TraceContext(0, 0, 0, ""), traces=[7], bucket=2),
            _span("reply_encode", 1.008, 0.001, root.child("frontend"))]
    gen2[0].pop("trace_id")       # batch spans carry traces=, not trace_id
    # Rotation numbers count up from the most recent: .2 is OLDER than .1.
    (d / "events.2.jsonl").write_text(
        "\n".join(json.dumps(e) for e in gen1) + "\n")
    (d / "events.1.jsonl").write_text(
        "\n".join(json.dumps(e) for e in gen2) + "\n")
    # No live events.jsonl: the reader tolerates its absence and keeps
    # generation order.
    events, bad = read_events_jsonl(str(d / "events.jsonl"))
    assert bad == 0
    assert [e["name"] for e in events] == ["wire_decode", "sched_queue",
                                           "serve_dispatch", "reply_encode"]
    cli = tmp_path / "client"
    cli.mkdir()
    (cli / "events.jsonl").write_text(
        json.dumps(_span("trace_client", 0.999, 0.012, root, trace=7))
        + "\n")
    report = aggregate.aggregate_run_dirs([str(d), str(cli)])
    assert report["processes"]["server"]["bad_lines"] == 0
    assert report["traces"] == 1 and report["complete"] == 1
    (w,) = report["waterfalls"]
    assert w["complete"] and set(w["procs"]) == {"client", "server"}
    assert set(w["stages"]) == {"wire_decode", "queue_wait",
                                "device_compute", "reply_encode"}


def test_replica_death_leaves_attributable_orphan(pool):
    # Chaos kills the ONLY replica at dispatch 0: the request resolves
    # (error reply — no silent drop), and its trace renders as an
    # ORPHANED waterfall whose surviving spans still attribute the
    # origins that ran.  chaos_fired telemetry marks the injection.
    model_zoo.register_model("tiny", tiny_cnn)
    tel = Telemetry()
    chaos = ChaosPlan.parse(["replica_death:0:0"])
    replica = EngineReplica(0, model="tiny", buckets=(2,), seed=0,
                            chaos=chaos, telemetry=tel)
    router = ReplicaRouter([replica], telemetry=tel)
    with router:
        client = LoopbackClient(router, telemetry=tel)
        rep = client.request(pool.images[:2], slo_ms=None)
    assert rep["status"] == "error"
    assert ("replica_death", 0) in chaos.fired
    events = tel.records
    assert any(e.get("kind") == "counter" and e.get("name") == "chaos_fired"
               and e.get("site") == "replica_death" for e in events)
    report = aggregate.aggregate_streams(
        [aggregate.ProcessStream("proc", list(events))])
    assert report["complete"] == 0 and report["orphaned"] >= 1
    w = report["waterfalls"][0]
    assert not w["complete"]
    assert "device_compute" not in w["stages"]
    assert "client" in w["origins"]          # attributable to its hops


def test_loopback_trace_spans_one_process(pool):
    # Tracing through the in-process client: every hop parents under the
    # client root, per-request spans carry the batcher trace id, and the
    # stage sum is bounded by the client-measured round-trip.
    model_zoo.register_model("tiny", tiny_cnn)
    tel = Telemetry()
    replica = EngineReplica(0, model="tiny", buckets=(2,), seed=0,
                            telemetry=tel)
    replica.startup()
    router = ReplicaRouter([replica], telemetry=tel)
    with router:
        client = LoopbackClient(router, telemetry=tel)
        client.request(pool.images[:2], slo_ms=None)     # warm compile
        rep = client.request(pool.images[:2], slo_ms=None)
    assert rep["status"] == "ok"
    report = aggregate.aggregate_streams(
        [aggregate.ProcessStream("proc", list(tel.records))])
    complete = [w for w in report["waterfalls"] if w["complete"]]
    assert complete
    w = complete[-1]
    assert "device_compute" in w["stages"] and "queue_wait" in w["stages"]
    assert 0.0 < w["sum_ms"] <= w["client_ms"] + 0.1
    spans = [e for e in tel.records
             if e.get("kind") == "span" and e.get("trace_id")]
    child = next(e for e in spans if e["name"] == "sched_queue"
                 and e["trace_id"] == w["trace_id"])
    assert child["parent_span_id"] != 0          # parented, not floating
    assert child["origin"] == "sched"
    root = next(e for e in spans if e["name"] == "trace_client"
                and e["trace_id"] == w["trace_id"])
    assert root["parent_span_id"] == 0           # the client minted it


# -- alert engine chaos drills ------------------------------------------------


def _healthy_events(t0=0.0):
    evs = []
    for i in range(80):
        t = t0 + 0.05 * i
        evs.append({"kind": "gauge", "name": "serve_latency_ms", "t": t,
                    "value": 5.0, "met": True, "tier": 0})
        evs.append({"kind": "gauge", "name": "serve_queue_depth", "t": t,
                    "value": 4, "replica": i % 2})
        evs.append({"kind": "gauge", "name": "serve_service_ms", "t": t,
                    "value": 2.0 + (i % 2), "replica": i % 2})
    evs.append({"kind": "gauge", "name": "publish_version", "t": t0 + 4.0,
                "value": 3})
    evs.append({"kind": "gauge", "name": "installed_version",
                "t": t0 + 4.1, "value": 3})
    return evs


def test_alert_drill_clean_run_fires_nothing():
    eng = AlertEngine()
    eng.run(_healthy_events())
    assert eng.fired_rules() == []
    assert eng.summary() == {"fired": [], "by_rule": {}, "total": 0}


def test_alert_drill_slow_replica_exact_rules():
    # The slow_replica signature: one replica's service EWMA far above
    # its peer, every request late.  EXACTLY straggler + burn-rate fire
    # — not shed-rate, not queue-depth, not publish-lag.
    evs = []
    for i in range(70):
        t = 0.1 * i
        evs.append({"kind": "gauge", "name": "serve_service_ms", "t": t,
                    "value": 500.0 if i % 2 == 0 else 5.0,
                    "replica": i % 2})
        evs.append({"kind": "gauge", "name": "serve_latency_ms", "t": t,
                    "value": 400.0, "met": False, "tier": 0})
    eng = AlertEngine()
    eng.run(evs)
    assert eng.fired_rules() == ["SLO_BURN", "STRAGGLER"]
    burn = next(a for a in eng.alerts if a.rule == "SLO_BURN")
    assert burn.attrs["attainment"] == 0.0
    strag = next(a for a in eng.alerts if a.rule == "STRAGGLER")
    assert strag.attrs["replica"] == 0


def test_alert_drill_publish_torn_exact_rules():
    # The publish_torn signature: the watcher REJECTS a corrupt bundle
    # (crc) while serving stays healthy — publish-lag only.
    evs = _healthy_events()
    evs.append({"kind": "counter", "name": "publish_rejected", "t": 4.2,
                "inc": 1, "why": "crc"})
    eng = AlertEngine()
    eng.run(evs)
    assert eng.fired_rules() == ["PUBLISH_LAG"]
    (alert,) = [a for a in eng.alerts if a.rule == "PUBLISH_LAG"]
    assert alert.attrs == {"counter": "publish_rejected", "reason": "crc"}


def test_alert_publish_lag_is_time_driven_and_cooldown_event_time():
    # installed_version trailing publish_version for > publish_lag_s of
    # EVENT time trips the lag rule; the cooldown is event-time too, so
    # replaying the log reproduces the live alert count exactly.
    evs = [{"kind": "gauge", "name": "publish_version", "t": 0.0,
            "value": 2},
           {"kind": "gauge", "name": "installed_version", "t": 0.1,
            "value": 1}]
    evs += [{"kind": "gauge", "name": "serve_queue_depth", "t": t,
             "value": 1} for t in (2.0, 6.0, 7.0, 12.0)]
    live = AlertEngine(publish_lag_s=5.0, cooldown_s=5.0)
    fired = [a.rule for e in evs for a in live.observe(e)]
    assert fired == ["PUBLISH_LAG", "PUBLISH_LAG"]    # t=6 then t=12
    replay = AlertEngine(publish_lag_s=5.0, cooldown_s=5.0)
    replay.run(evs)
    assert [(a.rule, a.t) for a in replay.alerts] == \
        [(a.rule, a.t) for a in live.alerts]


def test_alert_live_tap_slow_replica_chaos(pool):
    # LIVE drill: real engines, chaos slow_replica stalls replica 0's
    # first dispatch, the engine rides the telemetry tap.  With shedding
    # off and an unmeetable SLO the drill fires exactly straggler +
    # burn-rate, and the alerts land in the event stream as kind=alert.
    model_zoo.register_model("tiny", tiny_cnn)
    tel = Telemetry()
    alerts = AlertEngine(tel, burn_window=4, straggler_min_steps=1,
                         cooldown_s=0.0)
    tel.add_tap(alerts.observe)
    chaos = ChaosPlan.parse(["slow_replica:0:0"])
    replicas = [EngineReplica(i, model="tiny", buckets=(2,), seed=0,
                              chaos=chaos, slow_stall_s=0.3, shed=False,
                              telemetry=tel)
                for i in range(2)]
    for r in replicas:
        r.startup()
    router = ReplicaRouter(replicas, telemetry=tel)
    with router:
        client = LoopbackClient(router, telemetry=tel)
        futs = [client.submit(pool.images[:2], slo_ms=0.01)
                for _ in range(6)]
        statuses = [f.result(30.0)["status"] for f in futs]
    assert statuses == ["late"] * 6            # served, never dropped
    assert ("slow_replica", 0) in chaos.fired
    assert alerts.fired_rules() == ["SLO_BURN", "STRAGGLER"]
    assert any(a.rule == "STRAGGLER" and a.attrs["replica"] == 0
               for a in alerts.alerts)
    assert any(e.get("kind") == "alert" and e.get("rule") == "SLO_BURN"
               for e in tel.records)


# -- two OS processes -> one waterfall (the acceptance scenario) --------------


def test_two_process_waterfall_acceptance(tmp_path):
    # A real second OS process (tools/serve_load.py) replays requests
    # over the socket; merging both processes' event files reconstructs
    # skew-corrected end-to-end waterfalls: pairs estimated, stages from
    # BOTH processes, stage sum bounded by the client's measured
    # round-trip (the residual is wire + scheduling gaps, never
    # negative beyond the skew bound).
    model_zoo.register_model("tiny", tiny_cnn)
    srv_dir, cli_dir = str(tmp_path / "server"), str(tmp_path / "client")
    stel = Telemetry(srv_dir)
    replica = EngineReplica(0, model="tiny", buckets=(2, 4), seed=0,
                            telemetry=stel)
    replica.startup()
    router = ReplicaRouter([replica], telemetry=stel)
    with router:
        with ServingFrontend(router, telemetry=stel) as fe:
            # Warm every bucket OUTSIDE the traced window so cold
            # compiles don't ride the measured waterfalls.
            warm = LoopbackClient(router)
            for b in (2, 4):
                warm.submit(np.zeros((b, 32, 32, 3), np.uint8),
                            slo_ms=None).result(60.0)
            proc = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "serve_load.py"), "replay",
                 "--port", str(fe.address[1]), "--rps", "40",
                 "--requests", "12", "--max-size", "4",
                 "--telemetry-out", cli_dir, "--timeout", "60"],
                # The load client must never initialise a jax backend: on
                # the chip the server process holds the device and a client
                # that touched it would fail or hang.  An unknown platform
                # name turns any backend initialisation into an error.
                env={**os.environ, "JAX_PLATFORMS": "no_such_platform"},
                capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-800:]
    stats = json.loads(proc.stdout.strip().splitlines()[-1])
    assert stats["replies"] == 12 and stats["unresolved"] == 0
    stel.finalize()
    report = aggregate.aggregate_run_dirs([srv_dir, cli_dir])
    assert report["reference"] == "server"
    cli = report["processes"]["client"]
    assert cli["skew_estimated"] and cli["skew_pairs"] >= 10
    assert report["complete"] >= 10
    spanning = [w for w in report["waterfalls"]
                if w["complete"] and set(w["procs"]) == {"client",
                                                         "server"}]
    assert spanning
    for w in spanning:
        assert "device_compute" in w["stages"]
        assert {"client", "frontend", "sched"} <= set(w["origins"])
        # Stage sum vs client-measured latency: sum <= client + skew
        # tolerance; the residual is the un-spanned wire/callback time.
        assert w["sum_ms"] <= w["client_ms"] + 2e3 * cli["rtt_bound_s"]
    res = report["client_minus_stages_ms"]
    assert res["p50"] > -2e3 * cli["rtt_bound_s"]
    assert res["p50"] < 250.0                 # sane on a loaded CI host


def test_trace_waterfall_cli_renders(tmp_path, monkeypatch):
    # tools/trace_waterfall.py over synthetic two-process dirs: human
    # rendering names the reference clock and the skew estimate, and
    # --json round-trips the report.
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import trace_waterfall
    cli, srv = _stream_pair(n=4)
    for name, stream in (("client", cli), ("server", srv)):
        d = tmp_path / name
        d.mkdir()
        (d / "events.jsonl").write_text(
            "\n".join(json.dumps(e) for e in stream.events) + "\n")
    out = []
    monkeypatch.setattr("builtins.print", lambda *a, **k: out.append(
        " ".join(str(x) for x in a)))
    rc = trace_waterfall.main([str(tmp_path / "server"),
                               str(tmp_path / "client")])
    assert rc == 0
    text = "\n".join(out)
    assert "reference clock" in text and "server" in text
    assert "offset" in text
    out.clear()
    assert trace_waterfall.main([str(tmp_path / "server"),
                                 str(tmp_path / "client"), "--json"]) == 0
    parsed = json.loads("\n".join(out))
    assert parsed["reference"] == "server"
    assert parsed["processes"]["client"]["skew_pairs"] == 4


def test_telemetry_report_waterfall_and_alert_sections(tmp_path,
                                                       monkeypatch):
    # The run report grows ``== waterfall ==`` and ``== alerts ==``
    # sections when the stream carries traced spans / alert records —
    # and stays absent-safe for pre-round-12 runs.
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import telemetry_report

    traced = tmp_path / "traced"
    tel = Telemetry(str(traced))
    root = TraceContext.new_root("client")
    t0 = time.time()
    tel.span_event("trace_client", t0, 0.010, **root.attrs())
    tel.span_event("sched_queue", t0 + 0.001, 0.002, trace=1,
                   **root.child("sched").attrs())
    tel.alert("SLO_BURN", "page", attainment=0.5)
    tel.finalize()
    text = telemetry_report.render(str(traced))
    assert "== waterfall (distributed traces, this stream) ==" in text
    assert "== alerts ==" in text
    assert "SLO_BURN" in text

    plain = tmp_path / "plain"
    tel2 = Telemetry(str(plain))
    tel2.step(epoch=0, iter=0, loss=1.0, step_time=0.01)
    tel2.finalize()
    text2 = telemetry_report.render(str(plain))
    assert "== waterfall" not in text2 and "== alerts" not in text2
