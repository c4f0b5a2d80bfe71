"""The `train-tokens-causal` driver end to end without a chip: the tiny
hybrid decoder (`qwen3-next-tiny`: hidden 64, linear, linear, linear, full;
2 key / 4 value linear heads of 8, 4 query / 2 key-value heads of 16, 8
experts of 32 of which 2 are held, top-2, a shared expert, L = 32,
vocabulary 64) on the CPU mesh.  A sound run must come out correct; the
timed path broken underneath, the lower-precision control and each fault
planted in the reference must not.  And the new readers on a synthetic run.
"""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4, "grad1_leaf": 1e-3,
          "dparam3_leaf": 1e-3, "eval_loss3": 1e-4}
CONFIG = json.load(open(os.path.join(HERE, "tiny-qwen3next-f32.json")))


def ctx(chips=1, seed=3, **over):
    import tempfile
    import time
    from benchmark.window import Phases
    manifest = {"configs": [{"name": "tiny-qwen3next-f32",
                             "file": "benchmark/tests/tiny-qwen3next-f32.json"}],
                "workloads": [], "end_to_end": [], "per_layer": []}
    out = {"manifest": manifest,
           "cell": {"name": "tiny-qwen3next-cpu",
                    "config": "tiny-qwen3next-f32", "traffic": "rehearsal",
                    "chips": chips},
           "config": CONFIG,
           "traffic": {"kind": "train-tokens-causal", "strategy": "ddp",
                       "chips": chips, "warmup_units": 1,
                       "stream_units": 512, "weights_seed": 0,
                       "trace_seconds": 0.2, "trace_min_units": 2,
                       "modules": {"train": ["jit_window"],
                                   "eval": ["jit_evaluate"]}},
           "seed": seed, "seconds": 0.2, "trace": False,
           "t_start": time.perf_counter(),
           "phases": Phases(time.perf_counter(), time.perf_counter),
           "out_dir": tempfile.mkdtemp(prefix="bench-rehearsal-"),
           "limits": LIMITS}
    out.update(over)
    return out


@pytest.mark.parametrize("chips", [1, 2])
def test_sound_run_is_correct(chips):
    from benchmark.drivers import train_tokens_causal
    r = train_tokens_causal.run(ctx(chips, seed=2 ** 31 + 11))
    assert r["correct"], r["compared"]
    units = r["window"].units
    assert r["attempted"] == 4 * len(units) and r["failed"] == 0
    assert all(u["images"] == 16 * chips for u in units)
    # the stream: every unit its own epoch, its own routed rows
    assert [u["epoch"] for u in units] == list(range(1, 1 + len(units)))
    assert all(u["moe_rows_local"] > 0
               and u["tokens_predicted"] == 16 * chips * 31 for u in units)
    assert r["counters"]["compiles_in_window"] == 0


def test_other_weights_seed_other_weights_same_verdict():
    from benchmark.drivers import train_tokens_causal
    c = ctx()
    c["traffic"]["weights_seed"] = 5
    r = train_tokens_causal.run(c)
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
    from benchmark.drivers import train_tokens_causal as ttc

    def build(config, traffic, seed, telemetry, data_dir):
        t = ttc.build_trainer(config, traffic, seed, telemetry, data_dir)
        t.train_window_ring = Broken(t.train_window_ring, fault)
        return t
    r = ttc.run(ctx(build_trainer=build))
    assert not r["correct"], r["compared"]


def reference_against_itself(**faults):
    from benchmark import correct
    from benchmark.drivers import train_tokens_causal as ttc
    c = ctx()
    train, heldout = ttc.make_data(c["seed"], c["config"], c["traffic"], 1)
    args = (c["manifest"], c["cell"], c["config"], c["traffic"], c["seed"],
            train, heldout)
    nums = correct.numbers(ttc.reference_record(*args, **faults),
                           ttc.reference_record(*args))["numbers"]
    return correct.decide(nums, LIMITS) + (nums,)


@pytest.mark.parametrize("fault", ["no_decay", "no_shared_gate", "drop_half",
                                   "freeze"])
def test_fault_in_the_reference_reads_past_the_limits(fault):
    """The decay left out of the recurrence, the shared expert's gate left
    out, half of every step's sequences left out, a state left unchanged:
    planted in the reference put in the program's place."""
    ok, table, _ = reference_against_itself(**{fault: True})
    assert not ok, table


def test_reference_in_the_precision_below_is_not_correct():
    """The control: the plain reference computed in bfloat16 throughout
    (weights, the recurrent state and the optimizer's state too)."""
    ok, table, nums = reference_against_itself(dtype="bfloat16")
    assert not ok and nums["dparam3_leaf"] > 0.5, table


def test_program_bf16_path_is_not_correct():
    """The program's own bf16 path in the program's place, here, where a
    float32 matmul is exact (benchmark/tests/test_rehearsal_tokens.py)."""
    from benchmark.drivers import train_tokens_causal as ttc

    def bf16(config, traffic, seed, telemetry, data_dir):
        return ttc.build_trainer(config, traffic, seed, telemetry, data_dir,
                                 precision="bf16")
    r = ttc.run(ctx(build_trainer=bf16))
    assert not r["correct"], r["compared"]


# -- the readers on a synthetic run ---------------------------------------------

HLO = """
HloModule jit_window

%fused_a (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(window)/attn_gdn/gdn_recurrence/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_a
  %conv.2 = f32[8] add(%a, %a), metadata={op_name="jit(window)/attn_gdn/gdn_conv/add"}
  %proj.3 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/transpose(jvp(attn_gdn))/dot_general"}
  %splash_mqa_fwd.4 = f32[8] custom-call(%a), custom_call_target="tpu_custom_call"
  %shared.5 = f32[8] dot(%a, %a), metadata={op_name="jit(window)/moe_shared/dot_general"}
  %gmm.6 = f32[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(window)/moe_experts/gmm"}
  ROOT %other.7 = f32[8] add(%a, %a), metadata={op_name="jit(window)/add"}
}
"""


def synthetic_run(scope_seconds, busy=10.0, units=(), **counters):
    window = types.SimpleNamespace(
        total=lambda k: {"images": 32.0, "steps": 8.0}[k], units=list(units))
    config = dict(CONFIG, seq_len=8192, num_hidden_layers=4,
                  experts_held=list(range(32)),
                  linear_num_key_heads=16, linear_num_value_heads=32,
                  linear_key_head_dim=128, linear_value_head_dim=128,
                  num_attention_heads=16, head_dim=256)
    return types.SimpleNamespace(
        window=window, chips=1, config=config,
        counters=dict(counters, scope_seconds=scope_seconds),
        trace={"train_module_busy_s": busy} if busy else {},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_instructions_are_classed_by_scope_inner_scopes_first():
    from benchmark.drivers.train_tokens_causal import SCOPES
    from benchmark.readers import lm, lm_hybrid
    own = lm_hybrid.scope_instructions(HLO, SCOPES)
    assert own == {"m": "gdn_recurrence", "fusion.1": "gdn_recurrence",
                   "conv.2": "gdn_conv", "proj.3": "attn_gdn",
                   "splash_mqa_fwd.4": "attn_causal", "shared.5": "moe_shared",
                   "gmm.6": "moe_experts"}
    # the kernels count as the model's matrix products, as the first
    # decoder's do
    assert {"splash_mqa_fwd.4", "gmm.6"} <= lm.matmul_instructions(HLO)


def test_readers_read_shares_under_100_and_none_when_a_scope_is_absent():
    from benchmark import hybrid_flops
    from benchmark.readers import lm_hybrid as r
    secs = {"attn_gdn": 2.0, "gdn_conv": 0.5, "gdn_recurrence": 2.5,
            "attn_causal": 1.0, "moe_route": 0.2, "moe_experts": 1.5,
            "moe_shared": 0.3, "lm_head": 0.5}
    run = synthetic_run(secs, moe_rows_local=32 * 4 * 32 * 161.5)
    assert r.linear_attn_share(run) == pytest.approx(50.0)
    assert r.full_attn_share(run) == pytest.approx(10.0)
    assert r.sparse_moe_share(run) == pytest.approx(20.0)
    assert r.rows_per_held_expert(run) == pytest.approx(161.5)
    # the recurrence + convolution of 32 sequences x 3 layers: memory-bound
    # as counted, 2.4 ms a layer of a sequence; 3 s under the two scopes
    per_seq_layer = hybrid_flops.gdn_train_bytes_per_sequence(run.config) \
        / 3 / 819e9
    assert 2.0e-3 < per_seq_layer < 2.8e-3
    assert hybrid_flops.gdn_train_flops_per_sequence(run.config) / 197e12 \
        < hybrid_flops.gdn_train_bytes_per_sequence(run.config) / 819e9
    assert r.gdn_roofline(run) == pytest.approx(
        100 * 32 * 3 * per_seq_layer / 3.0)
    assert 0 < r.gdn_roofline(run) < 100
    # the causal products: 6 x 2 x L (L + 1) / 2 x 4096 FLOPs a sequence
    flops = 12 * (8192 * 8193 // 2) * 4096
    assert hybrid_flops.causal_attention_train_flops_per_sequence(
        run.config) == flops
    assert r.causal_attn_roofline(run) == pytest.approx(
        100 * 32 * flops / 197e12 / 1.0)
    assert 0 < r.causal_attn_roofline(run) < 100
    # nothing to read: an untraced run, a program without the scopes (the
    # parent), a scope that read no time
    for empty in (synthetic_run({}), synthetic_run(None),
                  synthetic_run({"lm_head": 1.0}),
                  synthetic_run({"attn_causal": 0.0, "gdn_conv": 0.0})):
        for reader in (r.linear_attn_share, r.full_attn_share,
                       r.sparse_moe_share, r.gdn_roofline,
                       r.causal_attn_roofline, r.rows_per_held_expert):
            assert reader(empty) is None
    assert r.linear_attn_share(synthetic_run(secs, busy=None)) is None


UNITS = [{"moe_rows_local": 328_000.0, "moe_rows_touched": 1_310_720.0,
          "moe_rows_max_expert": 229.0},
         {"moe_rows_local": 327_360.0, "moe_rows_touched": 1_310_720.0,
          "moe_rows_max_expert": 231.0}]
ROWS = 328_000.0 + 327_360.0


def cell_metric_reader(name):
    """The reader of one of the cell's metrics, found as run.py finds it."""
    from benchmark import manifest as mf
    metric, = [m for m in mf.load()["per_layer"] if m["name"] == name]
    assert metric["workloads"] == ["qwen3next-ep16-causal-train-1chip"]
    return mf.load_reader(name)


@pytest.mark.parametrize("name, expected", [
    ("model.vocab_head_share", 100 * 0.4 / 12.5),
    # 18 x 2048 x 512 FLOPs a row forward + backward over the peak, against
    # 32 experts' weights of 4 layers three times a step for 8 steps
    ("kernel.held_expert_roofline",
     100 * max(18 * 2048 * 512 * ROWS / 197e12,
               3 * 32 * 3 * 2048 * 512 * 4 * 4 * 8 / 819e9) / 1.8),
    ("moe.held_load_max_over_mean", 231 / (ROWS / (32 * 4 * 32))),
    ("moe.prefix_touched_over_live", 2 * 1_310_720 / ROWS),
])
def test_first_decoders_readers_read_this_cell_under_its_own_names(
        name, expected):
    """Head share, grouped products' roofline, load max over mean, touched
    over live: `readers/lm.py` and `readers/moe_prefix.py` on this cell's
    configuration, counters and unit records (the numbers of PR 33's traced
    run, rounded), and None where there is nothing to read."""
    from benchmark import manifest as mf
    reader = cell_metric_reader(name)
    config = mf.load_config(mf.load(), "qwen3-next-80b-a3b-ep16-f32")
    run = synthetic_run({"lm_head": 0.4, "moe_experts": 1.8}, busy=12.5,
                        units=UNITS, moe_rows_local=ROWS)
    run.config = config
    assert reader(run) == pytest.approx(expected)
    if name.endswith(("share", "roofline")):
        assert 0 < reader(run) < 100
    empty = synthetic_run({})
    empty.config = config
    assert reader(empty) is None


def test_required_work_follows_the_configuration():
    from benchmark import hybrid_flops, manifest as mf
    config = mf.load_config(mf.load(), "qwen3-next-80b-a3b-ep16-f32")
    assert hybrid_flops.layers_of_each_kind(config) == (3, 1)
    # a value head a position: 6 x 128 x 128 FLOPs forward; a channel 8
    per_position = 32 * 6 * 128 * 128 + 8 * 8192
    assert hybrid_flops.gdn_train_flops_per_sequence(config) \
        == 3 * per_position * 8192 * 3
    assert hybrid_flops.causal_pairs(4) == 10
