"""The `train-tokens-latent` traffic kind end to end without a chip on the
tiny dense hybrid decoder (`olmo-hybrid-tiny`: hidden 64, three linear
layers of 2 heads, keys of 8 and values of 16, beta = 2 sigmoid(b), a full
layer of 4 heads of 16 without rotary, post-norm, dense MLPs of 96, L = 32,
vocabulary 64) on the CPU mesh, from its own traffic file.  A sound run
must come out correct; the timed path broken underneath (a frozen state, an
altered loss, betas of sigmoid(b): no negative eigenvalues), the
lower-precision control and each fault planted in the reference must not.
And the new readers: the delta rule's and the front's kernels classed by
name, the shares on a synthetic run, the required work.
"""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4, "grad1_leaf": 1e-3,
          "dparam3_leaf": 1e-3, "eval_loss3": 1e-4}
CONFIG = json.load(open(os.path.join(HERE, "tiny-olmo-hybrid-f32.json")))
TRAFFIC = json.load(open(os.path.join(os.path.dirname(HERE), "traffic",
                                      "train-dense-hybrid-8k.json")))
CELL = "olmohybrid-pp8-gdn8k-train-1chip"
REAL = "olmo-hybrid-7b-pp8-f32"
METRICS = ["model.negdelta_share", "kernel.negdelta_roofline",
           "kernel.negdelta_front_roofline", "gdn.neg_eig_share"]


def ctx(chips=1, seed=3, **over):
    import tempfile
    import time
    from benchmark.window import Phases
    manifest = {"configs": [{"name": "tiny-olmo-hybrid-f32",
                             "file": "benchmark/tests/"
                                     "tiny-olmo-hybrid-f32.json"}],
                "workloads": [], "end_to_end": [], "per_layer": []}
    out = {"manifest": manifest,
           "cell": {"name": "tiny-olmo-hybrid-cpu",
                    "config": "tiny-olmo-hybrid-f32",
                    "traffic": "rehearsal", "chips": chips},
           "config": CONFIG,
           # the cell's own traffic file: what the run reads of the model
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
    r = train_tokens_latent.run(ctx(chips, seed=2 ** 31 + 17))
    assert r["correct"], r["compared"]
    units = r["window"].units
    assert r["attempted"] == 4 * len(units) and r["failed"] == 0
    assert all(u["images"] == 16 * chips for u in units)
    assert all(u["tokens_predicted"] == 16 * chips * 31
               and u["gdn_beta_entries"] == 16 * chips * 32 * 2 * 3
               and 0.3 < u["gdn_beta_over_one"] / u["gdn_beta_entries"]
               < 0.7 for u in units)
    assert r["counters"]["compiles_in_window"] == 0


def test_the_share_is_read_from_the_traffic_file():
    from benchmark.drivers import train_tokens_latent as ttl
    assert ttl.share(CONFIG, TRAFFIC) == dict(layers=4, vocab=64, seq_len=32)


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


@pytest.mark.parametrize("fault", ["freeze", "loss_altered",
                                   "beta_sigmoid"])
def test_broken_timed_path_is_not_correct(fault):
    """A frozen state and an altered loss in what the window returns; and
    the program's own linear layers at beta = sigmoid(b), the published
    negative eigenvalues dropped, under the timed path."""
    from benchmark.drivers import train_tokens_latent as ttl
    from cs744_ddp_tpu.models import olmo_hybrid

    def build(config, traffic, seed, telemetry, data_dir):
        if fault != "beta_sigmoid":
            t = ttl.build_trainer(config, traffic, seed, telemetry, data_dir)
            t.train_window_ring = Broken(t.train_window_ring, fault)
            return t
        # the Trainer as build_trainer makes it, on the model at scale 1
        from cs744_ddp_tpu import models
        real = models.get_model
        try:
            models.get_model = lambda name, **share: olmo_hybrid.make(
                olmo_hybrid.TINY._replace(beta_scale=1.0, **share))
            return ttl.build_trainer(config, traffic, seed, telemetry,
                                     data_dir)
        finally:
            models.get_model = real
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
    "beta_sigmoid", "pre_norm", "rope_on_full", "qk_norm_per_head",
    "drop_half", "freeze"])
def test_fault_in_the_reference_reads_past_the_limits(fault):
    """Betas of sigmoid(b), the norms on the sublayers' inputs, rotary on
    the full layers, their q / k norms per head, half of every step's
    sequences left out, a state left unchanged: planted in the reference
    put in the program's place."""
    ok, table, _ = reference_against_itself(**{fault: True})
    assert not ok, table


def test_reference_in_the_precision_below_is_not_correct():
    """The control: the plain reference computed in bfloat16 throughout."""
    ok, table, nums = reference_against_itself(dtype="bfloat16")
    assert not ok and nums["dparam3_leaf"] > 0.1, table


def test_an_unknown_fault_is_refused():
    from benchmark.reference import dense_hybrid_causal as ref
    with pytest.raises(TypeError, match="no_such_fault"):
        ref.follow(CONFIG, seed=0, weights_seed=0, world=1, per_chip_batch=4,
                   train=None, heldout=None, no_such_fault=True)


def test_calibration_reads_the_faults_off_the_reference_module():
    from benchmark import calibrate_latent, manifest as mf
    from benchmark.reference import dense_hybrid_causal as ref
    manifest = mf.load()
    faults = calibrate_latent.reference_faults(manifest,
                                               mf.cell(manifest, CELL))
    assert faults == ("drop_half", "freeze") + ref.FAULTS
    assert len(set(faults)) == 6


# -- the readers on module text and on a synthetic run ------------------------

def kernel(name, op):
    """A Pallas kernel's instruction as the TPU compiler prints it."""
    return (f"  %{name} = f32[8192,5760]{{1,0}} custom-call(%a), "
            f"custom_call_target=\"tpu_custom_call\", "
            f"metadata={{op_name=\"{op}\"}}")


HLO = "\n".join([
    "HloModule jit_window", "",
    "ENTRY %main (a: f32[8]) -> f32[8] {",
    "  %a = f32[8] parameter(0)",
    kernel("gdn_chunks_fwd.1", "jit(window)/checkpoint/attn_gdn/"
           "gdn_recurrence/gdn_chunks_fwd/pallas_call"),
    kernel("gdn_chunks_bwd.2", "jit(window)/transpose(jvp())/attn_gdn/"
           "gdn_recurrence/gdn_chunks_bwd/pallas_call"),
    kernel("gdn_front_fwd.3", "jit(window)/checkpoint/attn_gdn/gdn_conv/"
           "gdn_front_fwd/pallas_call"),
    kernel("gdn_front_bwd.4", "jit(window)/transpose(jvp())/attn_gdn/"
           "gdn_conv/gdn_front_bwd/pallas_call"),
    '  %pad.5 = f32[8] pad(%a), metadata={op_name="jit(window)/attn_gdn/'
    'gdn_recurrence/pad"}',
    '  %proj.6 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/'
    'checkpoint/attn_gdn/dot_general"}',
    '  %mlp.7 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/'
    'checkpoint/mlp_dense/dot_general"}',
    '  %head.8 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/'
    'lm_head/dot_general"}',
    '  ROOT %other.9 = f32[8] add(%a, %a), metadata={op_name="jit(window)/'
    'add"}',
    "}"])


def test_the_linear_mixers_kernels_are_classed_by_name():
    from benchmark.readers import lm_dense_hybrid as r
    own = r.scope_instructions(HLO, tuple(TRAFFIC["scopes"]),
                               TRAFFIC["kernel_scopes"])
    assert own == {
        "gdn_chunks_fwd.1": "gdn_chunks", "gdn_chunks_bwd.2": "gdn_chunks",
        "gdn_front_fwd.3": "gdn_front", "gdn_front_bwd.4": "gdn_front",
        "pad.5": "gdn_recurrence", "proj.6": "attn_gdn",
        "mlp.7": "mlp_dense", "head.8": "lm_head"}


def synthetic_run(scope_seconds, busy=10.0, counters=None):
    from benchmark import manifest as mf
    window = types.SimpleNamespace(
        total=lambda k: {"images": 8.0, "steps": 8.0}[k], units=[])
    return types.SimpleNamespace(
        window=window, chips=1, config=mf.load_config(mf.load(), REAL),
        counters=dict(counters or {}, scope_seconds=scope_seconds),
        trace={"train_module_busy_s": busy} if busy else {},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_readers_read_shares_under_100_and_none_when_a_scope_is_absent():
    from benchmark import dense_hybrid_flops as f
    from benchmark.readers import lm_dense_hybrid as r
    secs = {"attn_gdn": 1.0, "gdn_conv": 0.2, "gdn_recurrence": 0.3,
            "gdn_chunks": 2.0, "gdn_front": 0.5, "attn_causal": 0.5,
            "mlp_dense": 5.0, "lm_head": 0.5}
    run = synthetic_run(secs)
    assert r.negdelta_share(run) == pytest.approx(40.0)
    config = run.config
    # the recurrence at the published heads: 3 x 96 x 192 multiply-adds a
    # head a position, forward and backward; q, k, v, g, beta and o
    assert f.recurrence_train_flops_per_sequence(config) == \
        3 * 2 * 3 * 30 * 96 * 192 * 8192 * 3
    assert f.recurrence_train_bytes_per_sequence(config) == \
        2 * 4 * (2 * 2880 + 2 * 5760 + 60) * 8192 * 3
    assert f.front_train_bytes_per_sequence(config) == \
        2 * 4 * 2 * 11520 * 8192 * 3
    least = max(8 * f.recurrence_train_flops_per_sequence(config) / 197e12,
                8 * f.recurrence_train_bytes_per_sequence(config) / 819e9)
    assert r.negdelta_roofline(run) == pytest.approx(100 * least / 2.0)
    assert r.negdelta_front_roofline(run) == pytest.approx(
        100 * 8 * f.front_train_bytes_per_sequence(config) / 819e9 / 0.5)
    assert 0 < r.negdelta_roofline(run) < 100
    assert 0 < r.negdelta_front_roofline(run) < 100
    for empty in (synthetic_run({}), synthetic_run(None),
                  synthetic_run({"attn_causal": 1.0}),
                  synthetic_run({"gdn_chunks": 0.0, "gdn_front": 0.0})):
        assert r.negdelta_share(empty) is None
        assert r.negdelta_roofline(empty) is None
        assert r.negdelta_front_roofline(empty) is None
    assert r.negdelta_share(synthetic_run(secs, busy=None)) is None


def test_the_negative_eigenvalue_share_is_the_counters_ratio():
    from benchmark.readers import lm_dense_hybrid as r
    run = synthetic_run({}, counters={"gdn_beta_over_one": 491_520.0,
                                      "gdn_beta_entries": 983_040.0})
    assert r.neg_eig_share(run) == 0.5
    # a program without the two counters: the run reads 0 for both
    assert r.neg_eig_share(synthetic_run({}, counters={
        "gdn_beta_over_one": 0, "gdn_beta_entries": 0})) is None
    assert r.neg_eig_share(synthetic_run({})) is None


@pytest.mark.parametrize("name", METRICS)
def test_the_cells_metrics_resolve_to_their_readers(name):
    from benchmark import manifest as mf
    metric, = [m for m in mf.load()["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "train_img_s_chip"
    reader = mf.load_reader(name)
    assert reader(synthetic_run({})) is None
    assert reader(synthetic_run(
        {"gdn_chunks": 1.0, "gdn_front": 1.0},
        counters={"gdn_beta_over_one": 1.0, "gdn_beta_entries": 2.0})) > 0


def test_required_work_follows_the_configuration():
    """The step by the layer table: the three linear mixers 30% of the
    products, the MLPs 55%, the causal products 3.4%, the head 5%; the
    configuration's own count is the program's and its cut the two keys."""
    from benchmark import flops, hybrid_flops, manifest as mf
    config = mf.load_config(mf.load(), REAL)
    table = config["layer_table"]
    whole = flops.forward_macs_per_image(table)
    linear = sum(flops.forward_macs_per_image(table[i * 11:i * 11 + 8])
                 for i in range(3))
    mlp = 4 * flops.forward_macs_per_image(table[8:11])
    causal = hybrid_flops.causal_pairs(8192) * 3840 * 2
    head = flops.forward_macs_per_image(table[-1:])
    assert linear / whole == pytest.approx(0.30, abs=0.01)
    assert mlp / whole == pytest.approx(0.55, abs=0.01)
    assert causal / whole == pytest.approx(0.034, abs=0.002)
    assert head / whole == pytest.approx(0.05, abs=0.005)
    assert config["counted"]["all"] == 928_862_196
    entry = mf.config_entry(mf.load(), REAL)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert [config[k] for k in entry["reduced"]] == [4, 12544]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 100352}
