"""Benchmark-harness validation on the 8-virtual-device CPU mesh.

The real numbers come from the TPU run the driver performs (bench.py on the
bench host); what CI validates is the HARNESS: the strategy x model matrix
and the 1..N-device scaling sweep produce well-formed, internally-consistent
results (VERDICT r1 item 3).
"""

import json
import os

import numpy as np
import pytest

import bench
from cs744_ddp_tpu import models as model_zoo

from tinynet import tiny_cnn


def setup_module(module):
    model_zoo.register_model("tiny", tiny_cnn)


@pytest.mark.slow  # ~10 min: full matrix + sweep + convergence epochs
def test_bench_matrix_and_sweep_wellformed(tmp_path, monkeypatch):
    monkeypatch.setenv("CIFAR_DATA_DIR", str(tmp_path))
    # Shrink the synthetic dataset: the bench uses EPOCH-LENGTH windows, and
    # a 781-batch epoch per dispatch on the 1-core CPU mesh costs ~18 min of
    # wall-clock for zero extra coverage of the harness under test.
    from cs744_ddp_tpu.data import cifar10
    monkeypatch.setattr(cifar10, "TRAIN_SIZE", 64 * 12)
    monkeypatch.setattr(cifar10, "TEST_SIZE", 256)
    result = bench.run_bench(matrix=True, sweep=True, max_iters=8,
                             global_batch=64, models=("tiny",),
                             strategies=("allreduce", "ddp"),
                             deep_rows=(("tiny", "gather"),),
                             spectrum_deep_rows=(("tiny", "gather"),),
                             headline_model="tiny",
                             peak_batch_candidates=(8, 16),
                             serving_kwargs=dict(
                                 buckets=(2, 4, 8), loads=(50.0,),
                                 n_requests=20, startup_probe=False),
                             log=lambda s: None)
    # Driver contract head.
    assert result["metric"] == "cifar10_tiny_images_per_sec_per_chip"
    assert result["unit"] == "images/sec/chip"
    assert result["value"] > 0
    assert result["vs_baseline"] > 0
    assert result["num_devices"] == 8

    # Headline statistics: N runs with best/median/min, best == value.
    hs = result["headline_stats"]
    assert len(hs["runs"]) == bench.HEADLINE_RUNS
    assert hs["min"] <= hs["median"] <= hs["best"] == result["value"]

    # Strategy x model matrix: one positive entry per pair, plus the
    # deep-model rows appended beyond the cross (VERDICT r4 item 7; the
    # real run's deep_rows are vgg19/ddp and resnet34/ddp) and one bf16
    # row for the last deep pair at the parity batch.
    assert set(result["matrix"]) == {"tiny/allreduce", "tiny/ddp",
                                     "tiny/gather", "tiny/gather/bf16"}
    assert all(v["images_per_sec_per_chip"] > 0
               for v in result["matrix"].values())
    assert result["matrix"]["tiny/gather/bf16"]["precision"] == "bf16"

    # Peak entry: bf16 frontier config, well-formed and positive.
    assert result["peak"]["images_per_sec_per_chip"] > 0
    assert "bf16" in result["peak"]["config"]

    # Host-pipeline entry: chunked windowed --host-augment throughput,
    # tracked so the round-5 7.9x win cannot silently regress (BASELINE.md).
    hp = result["host_pipeline"]
    assert hp["images_per_sec_per_chip"] > 0
    assert hp["host_chunks"] >= 1
    # Chunk sweep covers the default K plus the 1/2/8 controls (K=1 is
    # round 5's whole-window staging), each a positive rate.
    assert set(hp["chunk_sweep"]) == {str(hp["host_chunks"]), "1", "2", "8"}
    assert all(v > 0 for v in hp["chunk_sweep"].values())
    # Link floor: the pure-device_put ceiling, both byte distributions
    # (real-entropy leg comes from the committed tests/assets fixture).
    lf = hp["link_floor"]
    assert lf["synthetic"]["floor_images_per_sec_per_chip"] > 0
    assert lf["real_entropy"]["floor_images_per_sec_per_chip"] > 0
    assert 0 < lf["real_entropy"]["unique_mib"] < lf["buffer_mib"]
    # Attached in-memory telemetry summary: the section trains real epochs,
    # so step events and host_augment/chunk_put/chunk_wait spans must be
    # there (chunk_put replaced prefetch_put for full batches in this PR).
    hts = hp["telemetry_summary"]
    assert hts["num_steps"] > 0
    assert "host_augment" in hts["spans"]
    assert "chunk_put" in hts["spans"]
    assert "chunk_wait" in hts["spans"]

    # Convergence entries: the reference's own correctness signal (VERDICT
    # r4 item 3).  At THIS test's shrunken 768-image scale the round-7
    # recalibrated task (data/cifar10.py) leaves accuracy near the chance
    # floor — too few samples per class/template to generalize — so here
    # only the SHAPE of the entries is checked (losses still fall).  The
    # graded LEARNING oracle runs at its calibrated 12.8k-image scale in
    # test_bench_convergence_oracle_graded below.
    conv = result["convergence"]
    assert conv["real_data"] is False   # tmp_path has no CIFAR pickles
    assert len(conv["per_epoch"]) == 3
    accs = [e["test_accuracy_pct"] for e in conv["per_epoch"]]
    losses = [e["train_loss_last"] for e in conv["per_epoch"]]
    assert all(0.0 <= a <= 100.0 for a in accs)
    assert losses[0] > losses[-1], losses  # train loss falls across epochs
    assert conv["test_accuracy_pct"] == accs[-1]
    assert conv["test_avg_loss"] > 0
    # Attached telemetry summary: 3 epochs x 12 batches of step events,
    # with steady-state percentiles ordered as percentiles must be.
    ts = conv["telemetry_summary"]
    assert ts["num_steps"] == len(conv["per_epoch"]) * 12
    if ts["num_steady_steps"]:
        stt = ts["steady_step_time_s"]
        assert stt["p50"] <= stt["p95"] <= stt["p99"] <= stt["max"]
    # Stable-lr companion: shape-checked only at this scale (see the
    # comment above conv; the >=2x-chance floor moved to the dedicated
    # oracle test at the calibrated dataset size).
    st = conv["stable_lr"]
    assert 0.0 <= st["test_accuracy_pct"] <= 100.0
    assert st["test_avg_loss"] >= 0 and st["train_loss_last"] >= 0

    # Serving section: ladder startup + per-bucket curve + open-loop
    # latency entry (full serving behavior is pinned in tests/test_serve.py;
    # here the subject is the section's shape inside the bench artifact).
    sv = result["serving"]
    assert sv["model"] == "tiny"
    assert set(sv["throughput_vs_bucket"]) == {"2", "4", "8"}
    for e in sv["throughput_vs_bucket"].values():
        assert e["images_per_sec"] > 0
        assert e["per_dispatch_ms"] > 0 and e["device_program_ms"] > 0
    assert sv["latency"]["50rps"]["completed"] > 0
    assert "serve_dispatch" in sv["telemetry_summary"]["spans"]

    # Hot-swap section (round 10): a steady row plus rolling/all-at-once
    # swap rows replaying the SAME trace while bundles land mid-stream.
    # Full swap behavior (A/B pin, torn rejection) is pinned in
    # tests/test_publish.py; here the subject is the section's shape and
    # its two CI contracts — every request answered and ZERO recompiles.
    hw = result["hotswap"]
    assert hw["model"] == "servenet" and hw["replicas"] == 2
    assert hw["steady"]["replies"] > 0 and hw["steady"]["unresolved"] == 0
    for name in ("rolling", "all_at_once"):
        row = hw[name]
        assert row["rolling"] is (name == "rolling")
        assert row["installs"] == row["publishes"] == 3
        assert row["installed_version"] == 3
        assert set(row["weights_versions"]) == {3}
        assert row["swap_samples"] == 3 * hw["replicas"]
        assert 0 < row["swap_ms_p50"] <= row["swap_ms_p99"] \
            <= row["swap_ms_max"]
        assert len(row["in_flight_at_publish"]) == 3
        assert row["recompiles"] == 0
        assert row["replies"] == hw["steady"]["replies"]
        assert row["unresolved"] == 0
        assert isinstance(row["goodput_dip_pct"], float)  # noise can be <0
    assert hw["zero_recompiles"] is True

    # Compression section (round 7): per-tier measured wall-clock, static
    # comm bytes from the audited lowering, and convergence delta vs the
    # uncompressed allreduce baseline.
    comp = result["compression"]
    assert comp["world"] == 8 and comp["baseline_tier"] == "allreduce"
    assert set(comp["per_tier"]) == set(bench.COMPRESSION_TIERS)
    for e in comp["per_tier"].values():
        assert e["wall_clock_s_best"] > 0
        assert e["images_per_sec_per_chip"] > 0
        assert e["comm_result_mib"] > 0
        assert 0.0 <= e["test_accuracy_pct"] <= 100.0
        assert -100.0 <= e["convergence_delta_pct"] <= 100.0
    ratio = {t: comp["per_tier"][t]["comm_ratio_vs_allreduce"]
             for t in comp["per_tier"]}
    # The contract floors, measured on the lowering (aux collectives — BN
    # pmeans, loss psum, int8's scale pmax — keep these just under the
    # pure-gradient 2x/4x; powersgd's analytic ratio on tiny is ~2.4x,
    # >=8x only on VGG-11-shaped leaves).
    assert ratio["allreduce"] == 1.0
    assert ratio["ddp"] >= 0.99 and ratio["overlap"] >= 0.99
    assert ratio["compress-bf16"] > 1.9
    assert ratio["compress-int8"] > 3.5
    assert ratio["powersgd"] > 1.9

    # Scaling sweep: 1,2,4,8 devices; WEAK scaling (constant per-chip
    # batch); efficiency is per-chip relative to the 1-device run and must
    # be finite/positive; 1-device eff == 1.
    sc = result["scaling"]
    assert sc["protocol"] == "weak scaling, 64 images/chip"
    assert set(sc["images_per_sec_per_chip"]) == {"1", "2", "4", "8"}
    eff = sc["efficiency_vs_1chip"]
    assert eff["1"] == 1.0
    assert all(v > 0 for v in eff.values())
    # The CPU mesh is not in the peak table: a measured path emits no MFU.
    assert "mfu_vs_bf16_peak" not in sc

    # Strong scaling: the reference's own protocol (global batch fixed),
    # reported alongside weak (ADVICE r3 item 4).
    st = sc["strong"]
    assert set(st["images_per_sec"]) == {"1", "2", "4", "8"}
    assert st["efficiency_vs_1chip"]["1"] == 1.0
    assert all(v > 0 for v in st["efficiency_vs_1chip"].values())

    # Spectrum: static collective stats from the v5e-8 AOT lowering (may be
    # absent only where the TPU AOT client is unavailable).
    if "spectrum" in result:
        per = result["spectrum"]["per_strategy"]
        assert set(per) == {"gather", "allreduce", "ddp"}
        # The tiers' cost shapes, exactly as strategies.py constructs them:
        # gather pays an all-gather per leaf; allreduce strictly more
        # collectives than ddp (fusion); gather's result bytes amplified by
        # world x vs the reduced tensors.
        assert per["gather"]["ops"]["all-gather"]["count"] >= 1
        assert per["allreduce"]["total_count"] > per["ddp"]["total_count"]
        assert per["gather"]["total_result_mib"] > \
            per["allreduce"]["total_result_mib"]
        # Deep-model rows (real run: resnet34 allreduce+ddp) ride in their
        # own sub-dict so per_strategy keeps its tier-only shape.
        deep = result["spectrum"]["deep_rows"]
        assert set(deep) == {"tiny/gather"}
        assert deep["tiny/gather"]["total_count"] >= 1
        assert deep["tiny/gather"]["grad_mib"] > 0

    # Emission contract: full payload (stdout line + sidecar) first, the
    # compact head LAST — the driver JSON-parses the final line of a
    # ~2000-byte stdout tail, which the full payload overflowed in rounds
    # 4/5 (the driver recorded "parsed": null).
    import json
    sidecar = tmp_path / "BENCH_FULL.json"
    lines = []
    head = bench.emit_result(result, str(sidecar), out=lines.append)
    assert len(lines) == 2
    assert json.loads(lines[0]) == result                 # full, first
    assert json.loads(lines[1]) == head                   # head, LAST
    assert len(lines[1]) <= bench.HEAD_LINE_BUDGET
    assert head["full_payload_file"] == "BENCH_FULL.json"
    assert head["value"] == result["value"]
    assert head["headline_stats"] == result["headline_stats"]
    assert json.loads(sidecar.read_text()) == result      # auditable copy


@pytest.mark.slow  # ~3 min: 4 tiny-model epochs at the calibrated scale
def test_bench_convergence_oracle_graded(tmp_path, monkeypatch):
    """The CI learning floor, re-derived for the round-7 recalibrated
    synthetic task (satellite of the serving PR; data/cifar10.py knob
    comments + BASELINE.md "Synthetic-task recalibration (round 7)").

    Pinned at the reference's own Part-1 semantics — ONE worker,
    ``single`` strategy — because that is where the recalibration is
    defined: under this mesh's 8-way ddp the per-shard BN batch is 8 and
    the lr-0.1 trajectory sits at chance (measured 9.77/9.77/9.77 at
    global batch 64 and 14.1/11.7/15.6 at 256), an artifact of the
    virtual mesh, not of the task.  At the calibrated 12.8k-image scale
    the reference config must show a GRADED trajectory — rising epoch
    over epoch, above chance, below the label-noise ceiling (measured:
    16.02 / 32.03 / 34.57%, losses 2.2517 / 2.0924 / 1.9515) — and the
    stable-lr companion must clear 2.5x chance in one epoch (measured:
    50.00% single / 50.39% ddp).  Floors carry ~2x margin against
    seed/toolchain drift."""
    monkeypatch.setenv("CIFAR_DATA_DIR", str(tmp_path))
    from cs744_ddp_tpu.data import cifar10
    monkeypatch.setattr(cifar10, "TRAIN_SIZE", 64 * 200)
    monkeypatch.setattr(cifar10, "TEST_SIZE", 256)
    from cs744_ddp_tpu.ops import sgd as _sgd

    # Reference config (lr 0.1, SGD 0.1/0.9/1e-4 — Trainer default),
    # 3 epochs: the graded trajectory itself.
    tr = bench._make_trainer("tiny", "single", 1, global_batch=64,
                             data_dir=str(tmp_path), log=lambda s: None)
    assert tr.real_data is False
    accs, losses = [], []
    for ep in range(3):
        timers = tr.train_model(ep)
        _, _, acc = tr.test_model()
        accs.append(acc)
        losses.append(timers.losses[-1])
    # Graded: learning is under way but NOT saturated.
    assert accs[-1] > accs[0], accs          # rises across the window
    assert accs[-1] >= 20.0, accs            # >= 2x the 10% chance floor
    assert accs[-1] <= 90.0, accs            # below the ~91% noise ceiling
    assert losses[0] > losses[-1], losses    # train loss falls too

    # Stable-lr companion (bench.py's convergence section records the
    # same pair): decisively above chance after ONE epoch.
    tr2 = bench._make_trainer("tiny", "single", 1, global_batch=64,
                              data_dir=str(tmp_path), log=lambda s: None,
                              sgd_cfg=_sgd.SGDConfig(lr=0.01))
    tr2.train_model(0)
    _, _, acc2 = tr2.test_model()
    assert acc2 >= 25.0, acc2                # half the measured 50%


def test_matrix_pairs_prunes_world1_strategy_cross():
    models = ("vgg11", "resnet18")
    strategies = ("gather", "allreduce", "ddp")
    deep = (("vgg19", "ddp"), ("resnet34", "ddp"))
    # Multi-chip: the full cross plus the deep rows, in order.
    assert bench._matrix_pairs(8, models, strategies, deep) == \
        [(m, s) for m in models for s in strategies] + list(deep)
    # world=1: every strategy's sync is a no-op, so the cross is pruned to
    # one strategy per model (BASELINE.md "1-chip strategy matrix").
    assert bench._matrix_pairs(1, models, strategies, deep) == \
        [("vgg11", "ddp"), ("resnet18", "ddp"),
         ("vgg19", "ddp"), ("resnet34", "ddp")]
    # No "ddp" on offer -> the first offered strategy is kept; deep rows
    # already in the cross are not duplicated.
    assert bench._matrix_pairs(1, ("vgg11",), ("gather",),
                               (("vgg11", "gather"),)) == \
        [("vgg11", "gather")]


def test_emit_result_contract_and_head_budget(tmp_path, capsys):
    result = {"metric": "m", "value": 1.5, "unit": "u", "vs_baseline": 2.0,
              "num_devices": 8,
              "headline_stats": {"runs": [1.5], "best": 1.5},
              "tflops_per_sec": 0.5, "mfu_vs_bf16_peak": 0.01,
              "matrix": {"big": "x" * 4000}}   # bulk the head must exclude
    sidecar = tmp_path / "FULL.json"
    head = bench.emit_result(result, str(sidecar))   # default out=print
    cap = capsys.readouterr().out.strip().splitlines()
    assert len(cap) == 2
    assert json.loads(cap[0]) == result               # full payload first
    assert json.loads(cap[-1]) == head                # compact head LAST
    assert len(cap[-1]) <= bench.HEAD_LINE_BUDGET
    assert head["full_payload_file"] == "FULL.json"
    assert "matrix" not in head
    assert json.loads(sidecar.read_text()) == result  # auditable sidecar
    # A head that cannot fit the driver's tail capture must fail loudly
    # instead of reintroducing the r04/r05 parsed-null failure.
    huge = dict(result, metric="m" * 2 * bench.HEAD_LINE_BUDGET)
    with pytest.raises(RuntimeError, match="budget"):
        bench.emit_result(huge, str(sidecar), out=lambda s: None)


def test_emit_head_budget_worst_case_with_serving(tmp_path):
    """Satellite of the serving PR: a worst-case result — every head field
    at realistic maximal width PLUS a fat ``serving`` section — must still
    emit a FINAL stdout line within the driver budget that JSON-parses
    standalone.  Pins that growing the full payload (new sections) cannot
    regress the r04/r05 parsed-null failure: bulk rides in the sidecar, the
    head's size is a function of CONTRACT_KEYS alone."""
    serving = {
        "backend": "tpu", "model": "vgg11",
        "buckets": [1, 8, 32, 128, 256], "precision": "f32",
        "ladder_startup": {"startup_s": 123.4567, "per_bucket": {
            str(b): {"seconds": 23.4567, "source": "compile"}
            for b in (1, 8, 32, 128, 256)}, "warm": False},
        "throughput_vs_bucket": {str(b): {
            "per_dispatch_ms": 104.321, "device_program_ms": 4.321,
            "images_per_sec": 59259.26, "reps": 20}
            for b in (1, 8, 32, 128, 256)},
        "latency": {f"{rps}rps": {
            "n_requests": 200, "offered_rps": rps, "completed": 200,
            "rejected": 0, "latency_ms": {
                "p50": 105.123, "p95": 230.456, "p99": 480.789,
                "mean": 131.415, "max": 512.161}}
            for rps in (5.0, 20.0, 80.0)},
        "startup": {"method": "subprocess", "cold_s": 240.1234,
                    "warm_s": 3.4567, "warm_lt_half_cold": True},
        "telemetry_summary": {"spans": {"serve_dispatch": {
            "count": 999999, "total_s": 12345.6789}},
            "padding": "x" * 2000},
    }
    result = {
        "metric": "cifar10_vgg11_images_per_sec_per_chip",
        "value": 123456.78, "unit": "images/sec/chip",
        "vs_baseline": 3173.95, "num_devices": 256,
        "headline_stats": {"runs": [123456.78, 123400.12, 123399.99],
                           "best": 123456.78, "median": 123400.12,
                           "min": 123399.99},
        "tflops_per_sec": 123.45, "mfu_vs_bf16_peak": 0.6266,
        "serving": serving,
        "matrix": {"bulk": "y" * 4000},
    }
    lines = []
    head = bench.emit_result(result, str(tmp_path / "FULL.json"),
                             out=lines.append)
    final = lines[-1]
    assert len(final.encode()) <= bench.HEAD_LINE_BUDGET
    parsed = json.loads(final)               # standalone-parseable
    assert parsed == head
    assert parsed["value"] == result["value"]
    assert "serving" not in parsed           # bulk stays in the sidecar
    assert parsed["full_payload_file"] == "FULL.json"
    assert json.loads((tmp_path / "FULL.json").read_text())["serving"] \
        == serving


def test_emit_head_budget_with_committed_serving_load(tmp_path):
    """Rounds 9/10: the committed BENCH_FULL.json now carries the fat
    ``serving_load`` section (replica-scaling rows, goodput curve,
    overload telemetry summary) and the ``hotswap`` section (swap
    latency, in-flight samples, goodput dip).  Re-emitting that REAL
    artifact must still produce a final stdout line within the driver
    budget — the new sections ride in the sidecar, never the head."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCH_FULL.json")) as f:
        result = json.load(f)
    assert "serving_load" in result
    assert "hotswap" in result
    # The committed swap rows honor the section's two CI contracts.
    assert result["hotswap"]["zero_recompiles"] is True
    for name in ("rolling", "all_at_once"):
        assert result["hotswap"][name]["unresolved"] == 0
    # Round 12: the tracing section honors ITS contracts — capacity
    # with tracing on within the 5% overhead budget, and the committed
    # two-process run reconstructed complete skew-corrected waterfalls.
    tracing = result["tracing"]
    assert tracing["capacity"]["within_budget"] is True
    assert tracing["capacity"]["overhead_frac"] <= 0.05
    two = tracing["two_process"]
    assert two["complete"] > 0
    assert any(p["skew_pairs"] > 0 for p in two["skew"].values())
    assert two["aggregate_wall_s"] < 10.0
    # Round 14: the dispatch-pipeline section honors ITS contracts —
    # pipelined capacity beats the committed round-9 figure, runtime
    # occupancy stays within the static two-slot bound, and the
    # bucket-8 dispatch tax shrank from the round-12 figure.
    pipe = result["pipeline"]
    assert pipe["capacity"]["beats_round9"] is True
    assert pipe["capacity"]["capacity_rps_on"] \
        > pipe["capacity"]["round9_capacity_rps"] == 441.6
    wf = pipe["waterfall"]
    assert wf["inflight_bound_ok"] is True
    assert wf["max_inflight"] <= 2
    b8 = wf["cost_prior"]["by_bucket"]["8"]["measured_over_prior"]
    assert b8 < 3.254          # the round-12 dispatch-tax figure
    # Round 20: the memory section honors ITS contracts — every zoo
    # program certified under the v5e budget, the compiled differential
    # clean (static >= XLA's temp+output floor, within band), and the
    # K-epoch planner table concrete and rising with the mesh.
    mem = result["memory"]
    assert mem["max_peak"]["peak_mib"] <= mem["budget_mib"]
    assert all(v <= mem["budget_mib"]
               for v in mem["peak_mib_by_program"].values())
    assert mem["compiled_check"]["clean"] is True
    assert mem["compiled_check"]["static_peak_mib"] \
        >= mem["compiled_check"]["compiled_floor_mib"]
    per_world = mem["planner"]["per_world"]
    ks = [per_world[w]["max_k"] for w in ("1", "2", "8")]
    assert ks == sorted(ks) and ks[0] > 0
    assert all(per_world[w]["mega_round_trips"] == 2 for w in per_world)
    lines = []
    head = bench.emit_result(result, str(tmp_path / "FULL.json"),
                             out=lines.append)
    final = lines[-1]
    assert len(final.encode()) <= bench.HEAD_LINE_BUDGET
    parsed = json.loads(final)
    assert parsed == head
    assert "serving_load" not in parsed
    assert "hotswap" not in parsed
    assert "tracing" not in parsed
    assert "pipeline" not in parsed
    assert "memory" not in parsed
    assert json.loads((tmp_path / "FULL.json").read_text()) == result


def test_bench_require_real_data_gate(tmp_path, monkeypatch):
    # No pickle batches under the data dir -> refuse before measuring.
    monkeypatch.setenv("CIFAR_DATA_DIR", str(tmp_path))
    with pytest.raises(SystemExit, match="require-real-data"):
        bench.main(["--require-real-data"])
    # The committed CIFAR fixture satisfies the gate; with run_bench
    # stubbed, main() emits per contract into --full-out.
    monkeypatch.setenv("CIFAR_DATA_DIR",
                       os.path.join(os.path.dirname(__file__), "assets"))
    monkeypatch.setattr(bench, "run_bench", lambda **kw: {
        "metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
        "num_devices": 1, "headline_stats": {"runs": [1.0]}})
    monkeypatch.setattr(bench, "_enable_compilation_cache", lambda: None)
    out = tmp_path / "SIDE.json"
    bench.main(["--require-real-data", "--full-out", str(out)])
    assert json.loads(out.read_text())["metric"] == "m"


def test_measure_link_floor_both_legs():
    """Fast harness check on the CPU mesh: both byte-distribution legs
    present and positive (the numbers only mean something on tpu — the
    backend label records that)."""
    lf = bench.measure_link_floor(lambda s: None, global_batch=64, ndev=8,
                                  trials=1)
    assert lf["backend"] == "cpu"
    assert lf["synthetic"]["floor_images_per_sec_per_chip"] > 0
    assert lf["synthetic"]["mib_per_s"] > 0
    real = lf["real_entropy"]   # committed tests/assets fixture
    assert real["floor_images_per_sec_per_chip"] > 0
    assert 0 < real["unique_mib"] < lf["buffer_mib"]


@pytest.mark.slow  # ~60s: two full-model cost analyses
def test_step_flops_per_image_is_world_invariant(tmp_path, mesh1, mesh8):
    """FLOPs/image must not depend on the mesh size: cost_analysis()
    reports the PER-DEVICE SPMD partition, so dividing by the global batch
    under-reports by ~world x (caught in round-3 review; on a real v5e-8
    this would have printed ~4% MFU instead of ~31%)."""
    from cs744_ddp_tpu.train.loop import Trainer

    def flops(mesh, strategy):
        tr = Trainer(model=tiny_cnn(), strategy=strategy, mesh=mesh,
                     global_batch=64, data_dir=str(tmp_path), augment=False,
                     log=lambda s: None)
        return tr.step_flops_per_image()

    f1 = flops(mesh1, "single")
    f8 = flops(mesh8, "ddp")
    if f1 is None or f8 is None:
        import pytest
        pytest.skip("backend offers no cost analysis")
    # Collectives/layout differ slightly between the programs; the bug this
    # pins was a factor-of-world (8x) error, far outside this band.
    assert 0.5 < f8 / f1 < 2.0, (f1, f8)


def test_bench_full_sidecar_carries_elastic_section_slot():
    """BENCH_FULL.json (the bulk sidecar) parses and remains a dict — the
    run_elastic section merges there on the next bench run."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCH_FULL.json")) as f:
        full = json.load(f)
    assert isinstance(full, dict) and full


# -- run_elastic: the elastic bench section is well-formed --------------------

def test_run_elastic_section_wellformed(tmp_path, monkeypatch):
    import cs744_ddp_tpu.train.loop as looplib
    from cs744_ddp_tpu.utils import metrics
    monkeypatch.setattr(looplib, "WINDOW", 3)
    monkeypatch.setattr(metrics, "WINDOW", 3)

    out = bench.run_elastic(lambda s: None, headline_model="tiny", ndev=2,
                            global_batch=64, data_dir=str(tmp_path),
                            max_iters=6)
    assert out["protocol"] == "strong"
    assert out["microshards"] == 4
    assert out["world"] == 2 and out["global_batch"] == 64

    sh = out["shrink"]
    assert (sh["from_world"], sh["to_world"]) == (2, 1)
    assert sh["death_step"] == 3                   # lim//2 on the WINDOW grid
    # Strong scaling: the step counter carries over, so only the
    # interrupted window is re-executed.
    assert sh["steps_lost"] == 0
    assert sh["coordinator_recovery_s"] >= 0
    assert sh["total_run_s"] > 0

    assert out["grow"]["to_world"] == 2
    assert out["grow"]["resume_run_s"] > 0

    dt = out["degraded_throughput"]
    assert dt["world1_images_per_sec"] > 0
    assert dt["world2_images_per_sec"] > 0
    assert dt["degraded_fraction"] > 0
