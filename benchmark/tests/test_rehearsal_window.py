"""The `train-tokens-latent` driver end to end without a chip on the tiny
window-and-full decoder (`mellum2-tiny`: hidden 64, three sliding layers
under a window of 8 keys then a full one, 4 query heads of 16 over 2
key/value heads, 8 softmax-routed experts of 32 of which 2 are held, top-2,
L = 32, vocabulary 64) on the CPU mesh, from its own traffic file.  A sound
run must come out correct; the timed path broken underneath, the
lower-precision control and each fault planted in the reference must not.
And the new readers: the splash kernels of both rules told apart by their
mask tables, the shares on a synthetic run, the window's pair count.
"""

import json
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4, "grad1_leaf": 1e-3,
          "dparam3_leaf": 1e-3, "eval_loss3": 1e-4}
CONFIG = json.load(open(os.path.join(HERE, "tiny-mellum2-f32.json")))
TRAFFIC = json.load(open(os.path.join(os.path.dirname(HERE), "traffic",
                                      "train-swa-8k.json")))
CELL = "mellum2-ep8-swa8k-train-1chip"
REAL = "mellum2-12b-a2.5b-ep8-f32"


def ctx(chips=1, seed=3, **over):
    import tempfile
    import time
    from benchmark.window import Phases
    manifest = {"configs": [{"name": "tiny-mellum2-f32",
                             "file": "benchmark/tests/tiny-mellum2-f32.json"}],
                "workloads": [], "end_to_end": [], "per_layer": []}
    out = {"manifest": manifest,
           "cell": {"name": "tiny-mellum2-cpu", "config": "tiny-mellum2-f32",
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
    r = train_tokens_latent.run(ctx(chips, seed=2 ** 31 + 13))
    assert r["correct"], r["compared"]
    units = r["window"].units
    assert r["attempted"] == 4 * len(units) and r["failed"] == 0
    assert all(u["images"] == 16 * chips for u in units)
    assert [u["epoch"] for u in units] == list(range(1, 1 + len(units)))
    assert all(u["moe_rows_local"] > 0
               and u["tokens_predicted"] == 16 * chips * 31 for u in units)
    assert r["counters"]["compiles_in_window"] == 0


def test_the_share_is_read_from_the_traffic_file():
    from benchmark.drivers import train_tokens_latent as ttl
    assert ttl.share(CONFIG, TRAFFIC) == dict(
        layers=4, held=(0, 1), vocab=64, seq_len=32)


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


SOUND = {}


def reference_against_itself(**faults):
    from benchmark import correct
    from benchmark.drivers import train_tokens_latent as ttl
    c = ctx()
    train, heldout = ttl.make_data(c["seed"], c["config"], c["traffic"], 1)
    args = (c["manifest"], c["cell"], c["config"], c["traffic"], c["seed"],
            train, heldout)
    if not SOUND:
        SOUND["record"] = ttl.reference_record(*args)
    nums = correct.numbers(ttl.reference_record(*args, **faults),
                           SOUND["record"])["numbers"]
    return correct.decide(nums, LIMITS) + (nums,)


@pytest.mark.parametrize("fault", [
    "no_window", "yarn_on_all", "no_yarn", "no_attn_factor", "drop_half",
    "freeze"])
def test_fault_in_the_reference_reads_past_the_limits(fault):
    """The sliding layers causal, YaRN on every layer, YaRN on none, the
    full layers' factor left out, half of every step's sequences left out,
    a state left unchanged: planted in the reference put in the program's
    place."""
    ok, table, _ = reference_against_itself(**{fault: True})
    assert not ok, table


def test_reference_in_the_precision_below_is_not_correct():
    """The control: the plain reference computed in bfloat16 throughout."""
    ok, table, nums = reference_against_itself(dtype="bfloat16")
    assert not ok and nums["dparam3_leaf"] > 0.5, table


def test_an_unknown_fault_is_refused():
    from benchmark.reference import window_causal as ref
    with pytest.raises(TypeError, match="no_such_fault"):
        ref.follow(CONFIG, seed=0, weights_seed=0, world=1, per_chip_batch=4,
                   train=None, heldout=None, no_such_fault=True)


def test_calibration_reads_the_faults_off_the_reference_module():
    from benchmark import calibrate_latent, manifest as mf
    from benchmark.reference import window_causal as ref
    manifest = mf.load()
    faults = calibrate_latent.reference_faults(manifest,
                                               mf.cell(manifest, CELL))
    assert faults == ("drop_half", "freeze") + ref.FAULTS
    assert len(set(faults)) == 6


# -- the readers on module text and on a synthetic run ------------------------

def splash(name, table, op):
    """A splash kernel's instruction as the TPU compiler prints it: its
    operands' shapes in `operand_layout_constraints`, the mask tables
    first, and its op_name on a line of its own after the kernel's
    metadata."""
    return (f"  %{name} = (f32[4,1024,128]{{2,1,0:T(8,128)}}, bf16[4,8,8192,"
            f"128]{{3,2,1,0}}) custom-call(%constant.1, %constant.2, "
            f"%bitcast.3), custom_call_target=\"tpu_custom_call\", "
            f"operand_layout_constraints={{{table}{{2,1,0}}, {table}{{2,1,0}}"
            f", bf16[4,8,8192,128]{{3,2,1,0}}}}, frontend_attributes={{"
            f"kernel_metadata={{\n\"splash\"\n}}, "
            f"metadata={{op_name=\"{op}\"}}")


# At P = 8192: the window's tables hold 2 key blocks a row of 1024 x 1024
# tiles (forward; dkv the transpose) and 3 of 512 x 512 (dq), the causal
# ones every block of a row.
HLO = "\n".join([
    "HloModule jit_window", "",
    "ENTRY %main (a: f32[8]) -> f32[8] {",
    "  %a = f32[8] parameter(0)",
    splash("splash_mqa_fwd_residuals.1", "s8[1,8,2]",
           "jit(window)/checkpoint/attn_window/window_core/pallas_call"),
    splash("splash_mqa_dkv_no_residuals.2", "s8[1,2,8]",
           "jit(window)/transpose(jvp())/attn_window/window_core/x"),
    splash("splash_mqa_dq_no_residuals.3", "s32[1,16,3]",
           "jit(window)/transpose(jvp())/attn_window/window_core/x"),
    splash("splash_mqa_fwd_residuals.4", "s8[1,8,8]",
           "jit(window)/checkpoint/attn_global/attn_causal/pallas_call"),
    splash("splash_mqa_dkv_no_residuals.5", "s8[1,8,8]",
           "jit(window)/transpose(jvp())/attn_global/attn_causal/x"),
    splash("splash_mqa_dq_no_residuals.6", "s32[1,16,16]",
           "jit(window)/transpose(jvp())/attn_global/attn_causal/x"),
    '  %proj.7 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/'
    'checkpoint/attn_window/dot_general"}',
    '  %cast.8 = bf16[8] convert(%a), metadata={op_name="jit(window)/'
    'checkpoint/attn_window/window_core/convert_element_type"}',
    '  %proj.9 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/'
    'checkpoint/attn_global/dot_general"}',
    '  %gmm.10 = f32[8] custom-call(%a), custom_call_target="tpu_custom_call"'
    ', metadata={op_name="jit(window)/moe_experts/gmm"}',
    '  %head.11 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/'
    'lm_head/dot_general"}',
    '  ROOT %other.12 = f32[8] add(%a, %a), metadata={op_name="jit(window)/'
    'add"}',
    "}"])


def test_splash_kernels_are_classed_by_their_mask_tables():
    from benchmark.readers import lm, lm_window
    own = lm_window.scope_instructions(HLO, tuple(TRAFFIC["scopes"]),
                                       TRAFFIC["kernel_scopes"])
    assert own == {
        "splash_mqa_fwd_residuals.1": "window_core",
        "splash_mqa_dkv_no_residuals.2": "window_core",
        "splash_mqa_dq_no_residuals.3": "window_core",
        "splash_mqa_fwd_residuals.4": "attn_causal",
        "splash_mqa_dkv_no_residuals.5": "attn_causal",
        "splash_mqa_dq_no_residuals.6": "attn_causal",
        "proj.7": "attn_window", "cast.8": "window_core",
        "proj.9": "attn_global", "gmm.10": "moe_experts",
        "head.11": "lm_head"}
    # by op_name alone the kernels are nobody's: it is not on their line
    assert not any(k.startswith("splash_")
                   for k in lm.scope_instructions(HLO, TRAFFIC["scopes"]))
    # a kernel known by name in the traffic file takes that name's scope
    named = lm_window.scope_instructions(HLO, tuple(TRAFFIC["scopes"]),
                                         {"splash_mqa_dq": "attn_causal"})
    assert named["splash_mqa_dq_no_residuals.3"] == "attn_causal"
    assert lm_window.splash_rule("%splash_mqa_fwd.1 = f32[8] custom-call()") \
        is None


def synthetic_run(scope_seconds, busy=10.0):
    from benchmark import manifest as mf
    window = types.SimpleNamespace(
        total=lambda k: {"images": 16.0, "steps": 4.0}[k], units=[])
    return types.SimpleNamespace(
        window=window, chips=1, config=mf.load_config(mf.load(), REAL),
        counters={"scope_seconds": scope_seconds},
        trace={"train_module_busy_s": busy} if busy else {},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_readers_read_shares_under_100_and_none_when_a_scope_is_absent():
    from benchmark import swa_flops
    from benchmark.readers import lm_window as r
    secs = {"attn_window": 1.5, "window_core": 2.5, "attn_global": 1.0,
            "attn_causal": 2.0, "moe_experts": 2.0, "lm_head": 0.5}
    run = synthetic_run(secs)
    assert r.window_attn_share(run) == pytest.approx(40.0)
    # 16 sequences, 2.32 TFLOP a sequence over the six sliding layers
    flops = swa_flops.window_attention_train_flops_per_sequence(run.config)
    assert flops == 12 * 7_864_832 * 32 * 128 * 6
    assert flops == pytest.approx(2.32e12, rel=1e-3)
    assert r.window_attn_roofline(run) == pytest.approx(
        100 * 16 * flops / 197e12 / 2.5)
    assert 0 < r.window_attn_roofline(run) < 100
    for empty in (synthetic_run({}), synthetic_run(None),
                  synthetic_run({"attn_causal": 1.0}),
                  synthetic_run({"window_core": 0.0, "attn_window": 0.0})):
        assert r.window_attn_share(empty) is None
        assert r.window_attn_roofline(empty) is None
    assert r.window_attn_share(synthetic_run(secs, busy=None)) is None


@pytest.mark.parametrize("name", ["model.window_attn_share",
                                  "kernel.window_attn_roofline"])
def test_the_cells_metrics_resolve_to_their_readers(name):
    from benchmark import manifest as mf
    metric, = [m for m in mf.load()["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "train_img_s_chip"
    reader = mf.load_reader(name)
    assert reader(synthetic_run({})) is None
    assert reader(synthetic_run({"window_core": 1.0})) > 0


@pytest.mark.parametrize("n,window", [(32, 8), (40, 1), (17, 17), (9, 30),
                                      (256, 64)])
def test_window_pairs_are_the_brute_force_count(n, window):
    from benchmark import swa_flops
    gap = np.arange(n)[:, None] - np.arange(n)[None, :]
    assert swa_flops.window_pairs(n, window) == int(
        ((gap >= 0) & (gap < window)).sum())


def test_required_work_follows_the_configuration():
    from benchmark import flops, hybrid_flops, manifest as mf, swa_flops
    config = mf.load_config(mf.load(), REAL)
    assert swa_flops.window_pairs(8192, 1024) == 7_864_832
    assert swa_flops.sliding_layers(config) == 6
    # the whole step by the layer table: 363 M multiply-adds a position,
    # 17.85 TFLOP a trained sequence, the window's products 13% of it and
    # the two full layers' causal products 18.5%
    whole = flops.train_flops_per_image(config["layer_table"])
    assert flops.forward_macs_per_image(config["layer_table"]) / 8192 \
        == pytest.approx(363.2e6, rel=1e-3)
    assert whole == pytest.approx(17.852e12, rel=1e-3)
    assert swa_flops.window_attention_train_flops_per_sequence(config) \
        / whole == pytest.approx(0.13, abs=0.005)
    causal = 12 * hybrid_flops.causal_pairs(8192) * 32 * 128 * 2
    assert causal / whole == pytest.approx(0.185, abs=0.005)
    # the file's own count is the program's, and its cut is the three keys
    assert config["counted"]["all"] == 624_072_960
    entry = mf.config_entry(mf.load(), REAL)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert [config[k] for k in entry["reduced"]] == [8, 8, 12288]
    assert config["published"] == {"num_hidden_layers": 28,
                                   "num_experts": 64, "vocab_size": 98304}
