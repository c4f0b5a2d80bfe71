"""Round 8: device-resident metric ring + cost-model attribution.

Five contracts, each pinned here:

* ``obs/ringbuf`` — the ring primitive: wraparound-correct drains, the
  overwrite refusal, and exact marker reconstruction.
* Trainer wiring — the ``--metrics-ring`` windowed epoch reports a loss
  trajectory BITWISE-identical to the non-ring path (ragged last window
  and buffer wraparound included), with device->host round-trips pinned
  at <= windows + 2 per epoch, and memory gauges at window boundaries
  that stay allocation-free through a disabled recorder.
* ``analysis/costmodel`` — analytic FLOPs pinned against hand-computed
  values for the VGG-11 forward (convs + fc) and an MLP train step
  (fwd + dw + the DCE-surviving dx dots), plus scan trip inference.
* Audit host-sync certification — a seeded ring-drain-inside-the-scan
  program FAILS; the real ring-write lowering (pure
  dynamic-update-slice) passes, with the donation floor raised by the
  two ring leaves.
* Serving causality + report rendering — every request's trace id rides
  its dispatch, queue-wait + service-time compose to the client latency,
  events.jsonl rotation round-trips through ``read_events_jsonl``, and
  tools/telemetry_report renders the ``attribution``/``traces`` sections
  (tolerantly absent on older runs).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cs744_ddp_tpu import models as model_zoo
from cs744_ddp_tpu.analysis import audit as auditlib
from cs744_ddp_tpu.analysis import costmodel
from cs744_ddp_tpu.obs import NULL, Telemetry, ringbuf
from cs744_ddp_tpu.obs import attribution as attrlib
from cs744_ddp_tpu.obs.telemetry import read_events_jsonl
from cs744_ddp_tpu.train.loop import Trainer, emit_memory_gauges

from tinynet import tiny_cnn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_module(module):
    model_zoo.register_model("tiny", tiny_cnn)


# ---------------------------------------------------------------------------
# ringbuf: the primitive
# ---------------------------------------------------------------------------

def test_ring_write_drain_wraparound():
    cap = 5
    ring = ringbuf.make_ring(cap)

    @jax.jit
    def fill(ring, vals):
        def step(r, v):
            return ringbuf.ring_write(r, (v, 2 * v, 1.0, v + 100.0)), None
        r, _ = jax.lax.scan(step, ring, vals)
        return r

    # 8 writes through a 5-slot ring: the last 3 drains all wrap.
    ring = fill(ring, jnp.arange(8, dtype=jnp.float32))
    buf = np.asarray(ring[0])
    assert int(ring[1]) == 8                     # total writes, not mod cap
    rows = ringbuf.drain_rows(buf, 8, 4)
    losses, gsq, oks, steps = ringbuf.split_columns(rows)
    np.testing.assert_array_equal(losses, [4.0, 5.0, 6.0, 7.0])
    np.testing.assert_array_equal(gsq, [8.0, 10.0, 12.0, 14.0])
    np.testing.assert_array_equal(oks, [1.0, 1.0, 1.0, 1.0])
    np.testing.assert_array_equal(steps, [104, 105, 106, 107])
    # Overwritten rows refuse to drain; so do more rows than ever written.
    with pytest.raises(ValueError, match="exceeds ring capacity"):
        ringbuf.drain_rows(buf, 8, 6)
    with pytest.raises(ValueError, match="exceeds total writes"):
        ringbuf.drain_rows(np.zeros((5, ringbuf.N_METRICS)), 2, 3)


def test_ring_marker_exactness_guard():
    rows = np.zeros((2, ringbuf.N_METRICS), np.float32)
    rows[:, ringbuf.METRICS.index("marker")] = [2.0 ** 24 - 1, 2.0 ** 24]
    with pytest.raises(ValueError, match="exact-f32"):
        ringbuf.marker_steps(rows)
    rows[:, ringbuf.METRICS.index("marker")] = [0.0, 2.0 ** 24 - 1]
    assert list(ringbuf.marker_steps(rows)) == [0, 2 ** 24 - 1]


def test_ring_capacity_validation():
    with pytest.raises(ValueError, match=">= 1"):
        ringbuf.make_ring(0)
    with pytest.raises(ValueError, match="expected 4 metrics"):
        ringbuf.ring_write(ringbuf.make_ring(2), (1.0, 2.0))


# ---------------------------------------------------------------------------
# Trainer wiring: bitwise parity, round-trip pin, memory gauges
# ---------------------------------------------------------------------------

def _ring_trainer(tmp_path, mesh4, telemetry, metrics_ring):
    return Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh4,
                   global_batch=64, data_dir=str(tmp_path), augment=False,
                   limit_train_batches=25, limit_eval_batches=2,
                   log=lambda s: None, telemetry=telemetry,
                   metrics_ring=metrics_ring)


def test_ring_epoch_bitwise_parity_and_round_trip_pin(tmp_path, mesh4):
    """The acceptance bar: capacity 20 over 25 batches forces BOTH a
    ragged 5-step window and a buffer wraparound on the second drain, and
    the reported trajectory must still be bitwise-identical to the
    non-ring windowed path — with exactly <= windows + 2 host round-trips
    for the whole epoch + eval."""
    tel_ring = Telemetry()
    tr = _ring_trainer(tmp_path, mesh4, tel_ring, metrics_ring=20)
    assert tr.train_window_ring is not None
    tr.train_model(0)
    tr.test_model()

    tel_plain = Telemetry()
    tr2 = _ring_trainer(tmp_path, mesh4, tel_plain, metrics_ring=0)
    assert tr2.train_window_ring is None
    tr2.train_model(0)

    ring_steps = [r for r in tel_ring.records if r["kind"] == "step"]
    plain_steps = [r for r in tel_plain.records if r["kind"] == "step"]
    assert len(ring_steps) == len(plain_steps) == 25
    # Bitwise: both paths run the SAME scanned program; the ring only
    # observes.  Exact float equality, not approx.
    assert [s["loss"] for s in ring_steps] == \
        [s["loss"] for s in plain_steps]
    # Ring-only enrichment: reconstructed absolute indices + grad norms.
    assert [s["step_index"] for s in ring_steps] == list(range(25))
    assert all(np.isfinite(s["grad_sqnorm"]) and s["grad_sqnorm"] > 0
               for s in ring_steps)

    # The round-trip pin: ceil(25/20) = 2 window drains + 1 eval fetch,
    # and NO per-step fetches anywhere.
    trips = [r for r in tel_ring.records
             if r["kind"] == "counter" and r["name"] == "host_round_trips"]
    sites = [t["site"] for t in trips]
    assert sites.count("window_drain") == 2
    assert sites.count("eval") == 1
    assert "step_fetch" not in sites and "window_fetch" not in sites
    windows = -(-25 // 20)
    assert len(trips) <= windows + 2

    # Per-window memory gauges at the boundaries the drain creates.
    mems = [r for r in tel_ring.records
            if r["kind"] == "gauge" and r["name"] == "memory"]
    assert len(mems) == 2
    assert all(m["value"]["host_rss_peak_mib"] > 0 for m in mems)
    assert all(m["value"]["device_live_mib"] >= 0 for m in mems)


def test_metrics_ring_validation(tmp_path):
    with pytest.raises(ValueError, match=">= 0"):
        Trainer(model=tiny_cnn(), strategy="single", num_devices=1,
                global_batch=8, data_dir=str(tmp_path), log=lambda s: None,
                metrics_ring=-1)
    with pytest.raises(ValueError, match="below the scan"):
        Trainer(model=tiny_cnn(), strategy="single", num_devices=1,
                global_batch=8, data_dir=str(tmp_path), log=lambda s: None,
                metrics_ring=7)


def test_memory_gauges_skip_disabled_recorder_entirely():
    class Exploding:
        enabled = False

        def __getattr__(self, name):
            raise AssertionError(f"telemetry.{name} touched while disabled")

    emit_memory_gauges(Exploding(), epoch=0, step=20)   # must not raise
    emit_memory_gauges(NULL, epoch=0, step=20)
    tel = Telemetry()
    emit_memory_gauges(tel, epoch=1, step=40)
    (rec,) = tel.records
    assert rec["name"] == "memory" and rec["epoch"] == 1
    assert rec["value"]["host_rss_peak_mib"] > 0


# ---------------------------------------------------------------------------
# costmodel: FLOPs pinned against hand-computed values
# ---------------------------------------------------------------------------

def test_costmodel_vgg11_forward_flops_pinned():
    """Conv FLOPs of the VGG-11 forward at batch 8, hand-computed from
    the config table (3x3 SAME convs: 2*B*H^2*Cout*9*Cin per stage) plus
    the 512->10 head dot."""
    from cs744_ddp_tpu.models import vgg
    init_fn, apply_fn = vgg.VGG11()
    params, state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.float32)
    hlo = jax.jit(
        lambda p, s, xx: apply_fn(p, s, xx, train=False)[0]
    ).lower(params, state, x).compiler_ir(dialect="hlo").as_hlo_text()
    rep = costmodel.cost_report(hlo, "vgg11/fwd")

    stages = [(32, 3, 64), (16, 64, 128), (8, 128, 256), (8, 256, 256),
              (4, 256, 512), (4, 512, 512), (2, 512, 512), (2, 512, 512)]
    expected_conv = sum(2 * 8 * h * h * cout * 9 * cin
                        for h, cin, cout in stages)
    assert expected_conv == 2_444_230_656          # the hand computation
    assert rep.flops_by_op["convolution"] == float(expected_conv)
    assert rep.flops_by_op["dot"] == 2.0 * 8 * 10 * 512
    assert rep.hbm_bytes > 0 and rep.wire_bytes == 0


def test_costmodel_mlp_train_step_dots_pinned():
    """Dot FLOPs of a full 32->16->10 MLP SGD step at batch 8: forward
    (2*B*i*o per layer) + dw (same) + dx for every layer but the first
    (the input gradient is dead and DCE'd)."""
    B, I, H, O = 8, 32, 16, 10

    def loss_fn(params, x, y):
        h = jax.nn.relu(x @ params["w0"] + params["b0"])
        logits = h @ params["w1"] + params["b1"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(y, O) * logp, axis=-1))

    def train_step(params, x, y):
        grads = jax.grad(loss_fn)(params, x, y)
        return jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)

    params = {"w0": jax.ShapeDtypeStruct((I, H), jnp.float32),
              "b0": jax.ShapeDtypeStruct((H,), jnp.float32),
              "w1": jax.ShapeDtypeStruct((H, O), jnp.float32),
              "b1": jax.ShapeDtypeStruct((O,), jnp.float32)}
    hlo = jax.jit(train_step).lower(
        params, jax.ShapeDtypeStruct((B, I), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.int32)).compiler_ir(dialect="hlo").as_hlo_text()
    rep = costmodel.cost_report(hlo, "mlp/train_step")

    fwd = 2 * B * I * H + 2 * B * H * O
    dw = 2 * B * I * H + 2 * B * H * O
    dx = 2 * B * H * O                       # layer 1 only; layer 0 DCE'd
    assert fwd + dw + dx == 24_064           # the hand computation
    assert rep.flops_by_op["dot"] == float(fwd + dw + dx)


def test_costmodel_scan_trip_inference():
    def scanned(c):
        def step(c, _):
            return c * 1.5 + 1.0, None
        out, _ = jax.lax.scan(step, c, None, length=7)
        return out

    rep = costmodel.cost_report(
        jax.jit(scanned).lower(jnp.float32(0)).compiler_ir(dialect="hlo").as_hlo_text(), "scan7")
    assert max(rep.trip_counts.values()) == 7
    # The scanned body's 2 elementwise flops are charged per trip.
    assert rep.flops_by_op["elementwise"] >= 14.0


def test_costmodel_mfu_fields_single_source():
    f = costmodel.mfu_fields(1000.0, 2e9, "TPU v5 lite")
    assert f == {"tflops_per_sec": 2.0,
                 "mfu_vs_bf16_peak": round(2e12 / 197e12, 4)}
    # absent, not null: no flop count, or a device outside the peak table
    assert costmodel.mfu_fields(1000.0, None, "TPU v5 lite") == {}
    assert costmodel.mfu_fields(1000.0, 2e9, "cpu") == {"tflops_per_sec": 2.0}
    # Same rule on the attribution join.
    from cs744_ddp_tpu.obs import attribution
    rep = costmodel.CostReport("p", flops=2e9)
    assert "mfu_vs_bf16_peak" in attribution.attribute(
        rep, measured_s=1e-3, device_kind="TPU v5 lite")
    assert "mfu_vs_bf16_peak" not in attribution.attribute(
        rep, measured_s=1e-3, device_kind="cpu")


# ---------------------------------------------------------------------------
# audit: ring host-sync certification (seeded positive + real negative)
# ---------------------------------------------------------------------------

_RING_DRAIN_IN_SCAN = """\
HloModule ring_drain_in_scan

wbody {
  p = f32[4] parameter(0)
  tok = token[] after-all()
  of = token[] outfeed(p, tok), outfeed_config="ring-drain"
  ROOT r = f32[4] add(p, p)
}

wcond {
  q = f32[4] parameter(0)
  ROOT lt = pred[] constant(false)
}

ENTRY main {
  a = f32[4] parameter(0)
  w = f32[4] while(a), body=wbody, condition=wcond
  ROOT out = f32[4] add(w, w)
}
"""


def test_ring_drain_inside_scan_fails_host_sync():
    """The anti-pattern the ring exists to avoid: draining (outfeeding)
    metric rows INSIDE the scanned body is a per-step host sync and the
    audit must refuse to certify it."""
    r = auditlib.audit_program(_RING_DRAIN_IN_SCAN,
                               auditlib.ProgramContract(name="t/ring"))
    assert r.rules["host-sync"] == "fail"
    assert "wbody" in r.findings[0].message


def test_ring_write_lowering_is_host_sync_clean():
    """The REAL ring write — one dynamic-update-slice per scanned step,
    drained by the host AFTER the dispatch — lowers with no host op
    inside the while body and certifies clean."""
    def scanned(ring, xs):
        def step(r, x):
            return ringbuf.ring_write(r, (x, x * x, 1.0, x + 1.0)), None
        r, _ = jax.lax.scan(step, ring, xs)
        return r

    hlo = jax.jit(scanned).lower(
        (jax.ShapeDtypeStruct((8, ringbuf.N_METRICS), jnp.float32),
         jax.ShapeDtypeStruct((), jnp.int32)),
        jax.ShapeDtypeStruct((6,), jnp.float32)).compiler_ir(dialect="hlo").as_hlo_text()
    assert "dynamic-update-slice" in hlo
    assert "outfeed" not in hlo
    r = auditlib.audit_program(hlo, auditlib.ProgramContract(name="t/ring"))
    assert r.rules["host-sync"] == "pass", r.findings


def test_zoo_ring_raises_donation_floor_and_collects_hlo():
    """Ring-carrying windowed programs donate the two extra ring leaves
    (state floor + 2) and the collected HLO feeds zoo_attribution."""
    res = auditlib.audit_zoo(model="tiny", global_batch=64, window=3,
                             strategies=("ddp", "overlap"),
                             paths=("window",), include_eval=False,
                             num_devices=4, collect_hlo=True)
    assert res.clean, "\n".join(res.format_lines())
    by_name = {r.program: r for r in res.reports}
    n_state = by_name["train/window/ddp"].stats["donated"]
    # tiny_cnn: 6 params + 2 BN state + momentum leaves, then the ring
    # buffer + counter on top — the floor held, so donated >= leaves + 2.
    assert n_state >= 8 + 2
    assert set(res.hlo) == {"train/window/ddp", "train/window/overlap"}

    attr = auditlib.zoo_attribution(res)
    assert set(attr["programs"]) == set(res.hlo)
    ddp = attr["programs"]["train/window/ddp"]
    assert ddp["gflops"] > 0 and ddp["wire_mib"] > 0
    assert ddp["roofline_bound"] in ("compute", "bandwidth")
    ov = attr["overlap_vs_ddp"]
    assert ov["ddp_chained_bytes"] >= ov["overlap_exposed_bytes_upper_bound"]
    json.dumps(attr)                              # manifest-ready

    # No collected HLO -> a loud error, not a silent empty record.
    bare = auditlib.audit_zoo(model="tiny", global_batch=64, window=3,
                              strategies=("ddp",), paths=("window",),
                              include_eval=False, num_devices=4)
    with pytest.raises(ValueError, match="collect_hlo"):
        auditlib.zoo_attribution(bare)


def test_record_attribution_manifest_merge(tmp_path):
    class Exploding:
        enabled = False

        def __getattr__(self, name):
            raise AssertionError(f"telemetry.{name} touched while disabled")

    auditlib.record_attribution(Exploding(), {"programs": {}})  # no-op
    tel = Telemetry(str(tmp_path))
    tel.write_manifest({"model": "tiny"})
    auditlib.record_attribution(tel, {"programs": {"p": {"gflops": 1.0}}})
    manifest = json.loads((tmp_path / "manifest.json").read_text())
    assert manifest["model"] == "tiny"            # merged, not clobbered
    assert manifest["attribution"]["programs"]["p"]["gflops"] == 1.0
    tel.finalize()


# ---------------------------------------------------------------------------
# serving causality: trace ids + the latency split
# ---------------------------------------------------------------------------

def test_serving_trace_causality_and_latency_split():
    from cs744_ddp_tpu.serve import InferenceEngine, MicroBatcher
    tel = Telemetry()
    eng = InferenceEngine("tiny", buckets=(2, 4), seed=0, telemetry=tel)
    eng.startup()
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (2, 32, 32, 3), dtype=np.uint8)
    with MicroBatcher(eng, max_wait_ms=1.0, telemetry=tel) as mb:
        futs = [mb.submit(img) for _ in range(5)]
        for f in futs:
            f.result(timeout=30)

    spans = [r for r in tel.records if r["kind"] == "span"]
    enq = {s["trace"] for s in spans if s["name"] == "serve_enqueue"}
    assert len(enq) == 5                         # process-unique ids
    dispatched = set()
    for s in spans:
        if s["name"] == "serve_dispatch":
            assert s["traces"]                   # never an anonymous batch
            dispatched.update(s["traces"])
    assert enq <= dispatched                     # causality: all accounted
    fetch_traces = set()
    for s in spans:
        if s["name"] == "serve_fetch":
            fetch_traces.update(s["traces"])
    assert enq <= fetch_traces

    # Per-request decomposition: queue wait + service time = latency.
    gauges = [r for r in tel.records if r["kind"] == "gauge"]
    by_trace = {}
    for g in gauges:
        if g["name"] in ("serve_latency_ms", "serve_queue_wait_ms",
                         "serve_service_ms"):
            by_trace.setdefault(g["trace"], {})[g["name"]] = g["value"]
    assert enq <= set(by_trace)
    for t in enq:
        rec = by_trace[t]
        assert set(rec) == {"serve_latency_ms", "serve_queue_wait_ms",
                            "serve_service_ms"}
        assert rec["serve_queue_wait_ms"] >= 0
        assert rec["serve_service_ms"] >= 0
        assert rec["serve_queue_wait_ms"] + rec["serve_service_ms"] == \
            pytest.approx(rec["serve_latency_ms"], abs=0.01)


# ---------------------------------------------------------------------------
# events.jsonl rotation: size-aware, read back in order, truncated-tail
# ---------------------------------------------------------------------------

def test_events_rotation_round_trip(tmp_path):
    d = str(tmp_path / "run")
    tel = Telemetry(d, rotate_bytes=256, rotate_keep=3)
    for i in range(40):
        tel.gauge("seq", i)
    tel.finalize()

    names = sorted(os.listdir(d))
    assert "events.jsonl" in names
    assert "events.1.jsonl" in names             # rotation actually fired
    assert sum(n.startswith("events.") for n in names) <= 4  # keep bound

    events, n_bad = read_events_jsonl(os.path.join(d, "events.jsonl"))
    assert n_bad == 0
    seqs = [e["value"] for e in events if e["name"] == "seq"]
    # Oldest-first across the rotated set, ending at the newest write;
    # generations past rotate_keep are the only permitted loss.
    assert seqs == sorted(seqs)
    assert seqs[-1] == 39
    assert len(seqs) == len(set(seqs))

    # A preempted run's torn final line is tolerated, not fatal.
    with open(os.path.join(d, "events.jsonl"), "a") as f:
        f.write('{"kind": "gauge", "name": "seq", "val')
    warnings = []
    events2, n_bad2 = read_events_jsonl(os.path.join(d, "events.jsonl"),
                                        warn=warnings.append)
    assert n_bad2 == 1 and len(warnings) == 1
    assert [e["value"] for e in events2 if e["name"] == "seq"] == seqs


def test_rotation_disabled_and_validation(tmp_path):
    with pytest.raises(ValueError, match="rotate_keep"):
        Telemetry(str(tmp_path / "x"), rotate_keep=0)
    d = str(tmp_path / "run")
    tel = Telemetry(d, rotate_bytes=0)           # rotation off
    for i in range(50):
        tel.gauge("g", i)
    tel.finalize()
    assert sorted(os.listdir(d)) == ["events.jsonl", "summary.json"]


# ---------------------------------------------------------------------------
# telemetry_report: the attribution and traces sections
# ---------------------------------------------------------------------------

def _report_module(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    import telemetry_report
    return telemetry_report


def test_report_renders_attribution_section(tmp_path, monkeypatch):
    telemetry_report = _report_module(monkeypatch)
    (tmp_path / "events.jsonl").write_text("")
    (tmp_path / "manifest.json").write_text(json.dumps({
        "model": "tiny",
        "attribution": {
            "programs": {
                "train/window/ddp": {
                    "gflops": 12.5, "hbm_mib": 420.0, "wire_mib": 0.36,
                    "roofline_bound": "bandwidth",
                    "comm_compute_ratio": 1.52},
                "eval/window": {
                    "gflops": 4.1, "hbm_mib": 130.0, "wire_mib": 0.0,
                    "roofline_bound": "bandwidth",
                    "comm_compute_ratio": 0.0}},
            "measured": {"program": "train/window/ddp",
                         "images_per_sec_per_chip": 176.69,
                         "mfu_vs_bf16_peak": 1e-06,
                         "roofline_bound": "bandwidth"},
            "overlap_vs_ddp": {"overlap_exposed_bytes_upper_bound": 95080,
                               "ddp_chained_bytes": 99400,
                               "hiding_ratio_lower_bound": 1.05}},
    }))
    out = telemetry_report.render(str(tmp_path))
    assert "== attribution (static cost model) ==" in out
    assert "train/window/ddp" in out and "bandwidth" in out
    assert "measured join" in out and "176.69" in out
    assert "hiding ratio >= 1.05" in out
    # Tolerant when absent: older manifests render without the section.
    (tmp_path / "manifest.json").write_text(json.dumps({"model": "tiny"}))
    assert "attribution" not in telemetry_report.render(str(tmp_path))


def test_report_renders_traces_section(tmp_path, monkeypatch):
    telemetry_report = _report_module(monkeypatch)
    d = str(tmp_path / "run")
    tel = Telemetry(d)
    tel.write_manifest({"model": "tiny"})
    with tel.span("serve_enqueue", n=2, trace=1):
        pass
    with tel.span("serve_enqueue", n=2, trace=2):
        pass
    with tel.span("serve_dispatch", bucket=2, n=2, traces=[1, 2]):
        pass
    tel.gauge("serve_queue_wait_ms", 1.5, trace=1)
    tel.gauge("serve_queue_wait_ms", 2.5, trace=2)
    tel.gauge("serve_service_ms", 10.0, trace=1)
    tel.gauge("serve_service_ms", 12.0, trace=2)
    tel.finalize()
    out = telemetry_report.render(d)
    assert "== traces (request causality) ==" in out
    assert "traced requests        2" in out
    assert "1 carrying trace ids" in out
    assert "queue wait" in out and "service time" in out
    # A run with no serving signal renders without the section.
    d2 = str(tmp_path / "run2")
    tel2 = Telemetry(d2)
    tel2.write_manifest({"model": "tiny"})
    tel2.gauge("epoch_time_s", 1.0)
    tel2.finalize()
    assert "traces (request causality)" not in telemetry_report.render(d2)
