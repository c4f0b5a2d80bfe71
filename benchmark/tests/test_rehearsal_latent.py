"""The `train-tokens-latent` driver end to end without a chip: the tiny
latent-attention decoder (`xing4-tiny`: hidden 64, 4 streams, a dense layer
then two expert layers, 4 heads of 16 + 8 beside values of 16, 8
sigmoid-routed experts of 32 of which 2 are held, top-2, a shared expert, L
= 32, vocabulary 64) on the CPU mesh.  A sound run must come out correct;
the timed path broken underneath, the lower-precision control and each
fault planted in the reference must not.  And the new readers on a
synthetic run.
"""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4, "grad1_leaf": 1e-3,
          "dparam3_leaf": 1e-3, "eval_loss3": 1e-4}
CONFIG = json.load(open(os.path.join(HERE, "tiny-xing4-f32.json")))
TRAFFIC = json.load(open(os.path.join(os.path.dirname(HERE), "traffic",
                                      "train-causal-4k.json")))
CELL = "xing4-ep8-causal4k-train-1chip"


def ctx(chips=1, seed=3, **over):
    import tempfile
    import time
    from benchmark.window import Phases
    manifest = {"configs": [{"name": "tiny-xing4-f32",
                             "file": "benchmark/tests/tiny-xing4-f32.json"}],
                "workloads": [], "end_to_end": [], "per_layer": []}
    out = {"manifest": manifest,
           "cell": {"name": "tiny-xing4-cpu", "config": "tiny-xing4-f32",
                    "traffic": "rehearsal", "chips": chips},
           "config": CONFIG,
           # the cell's own traffic file: what the driver reads of the model
           "traffic": dict(TRAFFIC, chips=chips, warmup_units=1,
                           stream_units=512, trace_seconds=0.2),
           "seed": seed, "seconds": 0.2, "trace": False,
           "t_start": time.perf_counter(),
           "phases": Phases(time.perf_counter(), time.perf_counter),
           "out_dir": tempfile.mkdtemp(prefix="bench-rehearsal-"),
           "limits": LIMITS}
    out.update(over)
    return out


@pytest.mark.parametrize("chips", [1, 2])
def test_sound_run_is_correct(chips):
    from benchmark.drivers import train_tokens_latent
    r = train_tokens_latent.run(ctx(chips, seed=2 ** 31 + 11))
    assert r["correct"], r["compared"]
    units = r["window"].units
    assert r["attempted"] == 4 * len(units) and r["failed"] == 0
    assert all(u["images"] == 16 * chips for u in units)
    # the stream: every unit its own epoch, its own routed rows
    assert [u["epoch"] for u in units] == list(range(1, 1 + len(units)))
    assert all(u["moe_rows_local"] > 0 and 0 <= u["mhc_res_gap"] < 1e-4
               and u["tokens_predicted"] == 16 * chips * 31 for u in units)
    assert r["counters"]["compiles_in_window"] == 0


def test_the_share_is_read_from_the_traffic_file():
    from benchmark.drivers import train_tokens_latent as ttl
    assert ttl.share(CONFIG, TRAFFIC) == dict(
        layers=3, dense_layers=1, held=(0, 1), vocab=64, seq_len=32)
    with pytest.raises(KeyError):
        ttl.share(CONFIG, dict(TRAFFIC, share={"layers": "no_such_key"}))


def test_other_weights_seed_other_weights_same_verdict():
    from benchmark.drivers import train_tokens_latent
    c = ctx()
    c["traffic"]["weights_seed"] = 5
    r = train_tokens_latent.run(c)
    assert r["correct"], r["compared"]


class Broken:
    """The trainer's window callable with a fault planted in what it
    returns (`lower`, which the trainer's warm-up calls, passes through)."""

    def __init__(self, window, fault):
        self.window, self.fault = window, fault
        self.lower = window.lower

    def __call__(self, state, ring, *rest):
        import jax
        kept = jax.tree.map(lambda x: x + 0, state)     # state is donated
        new_state, (buf, cnt) = self.window(state, ring, *rest)
        if self.fault == "freeze":
            return kept, (buf, cnt)
        return new_state, (buf.at[:, 0].multiply(1.01), cnt)  # loss_altered


@pytest.mark.parametrize("fault", ["freeze", "loss_altered"])
def test_broken_timed_path_is_not_correct(fault):
    from benchmark.drivers import train_tokens_latent as ttl

    def build(config, traffic, seed, telemetry, data_dir):
        t = ttl.build_trainer(config, traffic, seed, telemetry, data_dir)
        t.train_window_ring = Broken(t.train_window_ring, fault)
        return t
    r = ttl.run(ctx(build_trainer=build))
    assert not r["correct"], r["compared"]


SOUND = {}          # per_chip_batch -> the sound reference's record


def reference_against_itself(per_chip_batch=4, **faults):
    from benchmark import correct
    from benchmark.drivers import train_tokens_latent as ttl
    c = ctx()
    c["config"] = dict(c["config"], per_chip_batch=per_chip_batch)
    train, heldout = ttl.make_data(c["seed"], c["config"], c["traffic"], 1)
    args = (c["manifest"], c["cell"], c["config"], c["traffic"], c["seed"],
            train, heldout)
    if per_chip_batch not in SOUND:
        SOUND[per_chip_batch] = ttl.reference_record(*args)
    nums = correct.numbers(ttl.reference_record(*args, **faults),
                           SOUND[per_chip_batch])["numbers"]
    return correct.decide(nums, LIMITS) + (nums,)


@pytest.mark.parametrize("fault", [
    "sinkhorn_1", "no_res_mix", "rope_on_all", "no_yarn_scale",
    "softmax_route", "no_route_scale", "drop_half", "freeze"])
def test_fault_in_the_reference_reads_past_the_limits(fault):
    """One Sinkhorn iteration, H_res = I, rotary over the whole key, the
    softmax scale without YaRN's, softmax scores, weights not doubled, half
    of every step's sequences left out, a state left unchanged: planted in
    the reference put in the program's place."""
    ok, table, _ = reference_against_itself(**{fault: True})
    assert not ok, table


def test_drop_half_at_one_sequence_a_step_drops_half_its_positions():
    """The real cell trains on ONE sequence a step: there `drop_half` is
    the second half of the sequence's predicted positions left out."""
    ok, table, nums = reference_against_itself(per_chip_batch=1,
                                               drop_half=True)
    assert not ok and nums["grad1_leaf"] > 0.05, table


def test_an_unknown_fault_is_refused():
    from benchmark.reference import latent_hc_causal as ref
    with pytest.raises(TypeError, match="no_such_fault"):
        ref.follow(CONFIG, seed=0, weights_seed=0, world=1, per_chip_batch=4,
                   train=None, heldout=None, no_such_fault=True)


def test_reference_in_the_precision_below_is_not_correct():
    """The control: the plain reference computed in bfloat16 throughout
    (weights and the optimizer's state too)."""
    ok, table, nums = reference_against_itself(dtype="bfloat16")
    assert not ok and nums["dparam3_leaf"] > 0.5, table


def test_calibration_reads_the_faults_off_the_reference_module():
    from benchmark import calibrate_latent, manifest as mf
    from benchmark.reference import latent_hc_causal as ref
    manifest = mf.load()
    faults = calibrate_latent.reference_faults(manifest,
                                               mf.cell(manifest, CELL))
    assert faults == ("drop_half", "freeze") + ref.FAULTS
    assert len(set(faults)) == 8


# -- the readers on a synthetic run ---------------------------------------------

HLO = """
HloModule jit_window

%fused_a (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %m = f32[8] divide(%p, %p), metadata={op_name="jit(window)/checkpoint/mhc_mix/mhc_sinkhorn/div"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_a
  %mix.2 = f32[8] add(%a, %a), metadata={op_name="jit(window)/transpose(jvp())/checkpoint/mhc_mix/add_any"}
  %proj.3 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/checkpoint/attn_mla/dot_general"}
  %splash_mqa_fwd.4 = f32[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(window)/checkpoint/attn_mla/mla_core/attn_causal/pallas_call"}
  %splash_mqa_dq.5 = f32[8] custom-call(%a), custom_call_target="tpu_custom_call"
  %cast.6 = bf16[8] convert(%a), metadata={op_name="jit(window)/checkpoint/attn_mla/convert_element_type"}
  %dense.7 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/checkpoint/mlp_dense/dot_general"}
  %shared.8 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/moe_shared/dot_general"}
  %gmm.9 = f32[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(window)/moe_experts/gmm"}
  ROOT %other.10 = f32[8] add(%a, %a), metadata={op_name="jit(window)/add"}
}
"""


def synthetic_run(scope_seconds, busy=10.0, units=(), **counters):
    from benchmark import manifest as mf
    window = types.SimpleNamespace(
        total=lambda k: {"images": 16.0, "steps": 16.0}[k], units=list(units))
    return types.SimpleNamespace(
        window=window, chips=1,
        config=mf.load_config(mf.load(), "xing4.0-29b-a4b-ep8-f32"),
        counters=dict(counters, scope_seconds=scope_seconds),
        trace={"train_module_busy_s": busy} if busy else {},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_instructions_are_classed_by_the_traffic_files_scopes():
    from benchmark.readers import lm, lm_latent
    own = lm_latent.scope_instructions(HLO, tuple(TRAFFIC["scopes"]),
                                       TRAFFIC["kernel_scopes"])
    assert own == {"m": "mhc_sinkhorn", "fusion.1": "mhc_sinkhorn",
                   "mix.2": "mhc_mix", "proj.3": "attn_mla",
                   "splash_mqa_fwd.4": "mla_core",
                   "splash_mqa_dq.5": "mla_core",       # by its name alone
                   "cast.6": "attn_mla", "dense.7": "mlp_dense",
                   "shared.8": "moe_shared", "gmm.9": "moe_experts"}
    # without the traffic file's kernels a nameless kernel is nobody's
    assert "splash_mqa_dq.5" not in lm_latent.scope_instructions(
        HLO, tuple(TRAFFIC["scopes"]), {})
    # the kernels count as the model's matrix products, as the other
    # decoders' do
    assert {"splash_mqa_fwd.4", "splash_mqa_dq.5", "gmm.9"} \
        <= lm.matmul_instructions(HLO)


def test_readers_read_shares_under_100_and_none_when_a_scope_is_absent():
    from benchmark import latent_flops
    from benchmark.readers import lm_latent as r
    secs = {"attn_mla": 2.5, "mla_core": 1.5, "mhc_mix": 1.6,
            "mhc_sinkhorn": 0.4, "mlp_dense": 0.5, "moe_route": 0.2,
            "moe_experts": 1.0, "moe_shared": 0.3, "lm_head": 0.5}
    run = synthetic_run(secs, units=[{"mhc_res_gap": 1.1e-6},
                                     {"mhc_res_gap": 1.3e-6}])
    assert r.mla_share(run) == pytest.approx(40.0)
    assert r.mhc_share(run) == pytest.approx(20.0)
    assert r.ffn_share(run) == pytest.approx(20.0)
    assert r.res_gap(run) == 1.3e-6
    # the causal products of 16 sequences x 5 layers: 1,920 FLOPs a pair a
    # head; 1.5 s under the kernels
    flops = 1920 * (4096 * 4097 // 2) * 32 * 5
    assert latent_flops.mla_attention_train_flops_per_sequence(run.config) \
        == flops
    assert r.mla_attn_roofline(run) == pytest.approx(
        100 * 16 * flops / 197e12 / 1.5)
    assert 0 < r.mla_attn_roofline(run) < 100
    # the mixers: memory-bound as counted, 1.4 ms a sublayer of a sequence
    per_sublayer = latent_flops.mhc_train_bytes_per_sequence(run.config) \
        / 10 / 819e9
    assert 1.3e-3 < per_sublayer < 1.6e-3
    assert latent_flops.mhc_train_flops_per_sequence(run.config) / 197e12 \
        < latent_flops.mhc_train_bytes_per_sequence(run.config) / 819e9
    assert r.mhc_roofline(run) == pytest.approx(
        100 * 16 * 10 * per_sublayer / 2.0)
    assert 0 < r.mhc_roofline(run) < 100
    # nothing to read: an untraced run, a program without the scopes (the
    # parent), a scope that read no time, units without the column
    for empty in (synthetic_run({}), synthetic_run(None),
                  synthetic_run({"lm_head": 1.0}),
                  synthetic_run({"mla_core": 0.0, "mhc_mix": 0.0}),
                  synthetic_run({"lm_head": 1.0},
                                units=[{"moe_rows_local": 5.0}])):
        for reader in (r.mla_share, r.mhc_share, r.ffn_share,
                       r.mla_attn_roofline, r.mhc_roofline, r.res_gap):
            assert reader(empty) is None
    assert r.mla_share(synthetic_run(secs, busy=None)) is None


@pytest.mark.parametrize("name", [
    "model.mla_share", "model.mhc_share", "model.ffn_share",
    "kernel.mla_attn_roofline", "kernel.mhc_roofline", "mhc.res_gap"])
def test_the_cells_metrics_resolve_to_their_readers(name):
    from benchmark import manifest as mf
    metric, = [m for m in mf.load()["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "train_img_s_chip"
    reader = mf.load_reader(name)
    assert reader(synthetic_run({})) is None
    run = synthetic_run({"attn_mla": 1.0, "mla_core": 1.0, "mhc_mix": 1.0,
                         "moe_experts": 1.0}, units=[{"mhc_res_gap": 2e-6}])
    assert reader(run) > 0


def test_required_work_follows_the_configuration():
    from benchmark import flops, latent_flops, manifest as mf
    config = mf.load_config(mf.load(), "xing4.0-29b-a4b-ep8-f32")
    assert latent_flops.causal_pairs(4) == 10
    # 5 x 235 MB of streams + 3 x 1.4 MB of Phi a sublayer, ten sublayers
    streams, phi = 4 * 4096 * 4 * 3584, 4 * 4 * 3584 * 24
    assert latent_flops.mhc_train_bytes_per_sequence(config) \
        == 10 * (5 * streams + 3 * phi)
    # the whole step by the layer table: 11.70 TFLOP a trained sequence,
    # of which the causal products are 22% and the mixers 1%
    whole = flops.train_flops_per_image(config["layer_table"])
    assert whole == pytest.approx(11.698e12, rel=1e-3)
    assert latent_flops.mla_attention_train_flops_per_sequence(config) \
        / whole == pytest.approx(0.22, abs=0.005)
    assert latent_flops.mhc_train_flops_per_sequence(config) / whole \
        == pytest.approx(0.009, abs=0.001)
    # the file's own count is the program's, and its cut is the five keys
    assert config["counted"]["all"] == 759_346_446
    entry = mf.config_entry(mf.load(), "xing4.0-29b-a4b-ep8-f32")
    assert entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert [config[k] for k in entry["reduced"]] == [5, 1, 8, 16384, 0]
    assert config["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
