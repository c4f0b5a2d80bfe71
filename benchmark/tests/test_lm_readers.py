"""The decoder's readers on plain data: instructions classed by named scope
from a module's text, the traced operations' time summed by scope, and the
seven metrics from a run's counters.  No JAX."""

import json
import os
import types

from benchmark import lm_flops, manifest as mf
from benchmark.readers import lm

SCOPES = ("attn_blockdiff", "moe_route", "moe_experts", "lm_head")

HLO = '''HloModule jit_window

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  ROOT %exp.1 = f32[8,8] exponential(%p0), metadata={op_name="jit(window)/while/body/moe_route/exp"}
}

%fused_computation.2 (p0: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8] parameter(0)
  ROOT %dot.7 = f32[8,8] dot(%p0.1, %p0.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(window)/while/body/transpose(jvp(lm_head))/dot_general"}
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %fusion.1 = f32[8,8] fusion(%a), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8,8] fusion(%a), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(window)/while/body/transpose(jvp(lm_head))/dot_general"}
  %gmm.3 = f32[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(window)/while/body/checkpoint/moe_experts/jit(gmm)/pallas_call"}
  %splash_mqa_fwd_residuals.4 = (f32[8,8], f32[8,8]) custom-call(%a), custom_call_target="tpu_custom_call"
  %other.5 = f32[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(window)/elsewhere/pallas_call"}
  ROOT %add.6 = f32[8,8] add(%fusion.1, %fusion.2)
}
'''


def test_instructions_are_classed_by_scope():
    got = lm.scope_instructions(HLO, SCOPES)
    assert got["fusion.1"] == "moe_route"       # from its fused computation
    assert got["fusion.2"] == "lm_head"         # backward keeps the scope
    assert got["gmm.3"] == "moe_experts"
    assert got["splash_mqa_fwd_residuals.4"] == "attn_blockdiff"   # by name
    assert "other.5" not in got and "add.6" not in got


def test_kernels_of_the_two_scopes_count_as_matrix_products():
    got = lm.matmul_instructions(HLO)
    assert {"fusion.2", "dot.7", "gmm.3",
            "splash_mqa_fwd_residuals.4"} <= got
    assert "other.5" not in got and "fusion.1" not in got


def test_scope_seconds_takes_the_train_modules_of_device_0():
    trace = {"devices": {0: {
        "modules": [("jit_window(1)", 0, 1000), ("jit_evaluate(2)", 2000, 500)],
        "async": [],
        "ops": [("%fusion.1 = f32[8,8] fusion(...)", 10, 100),
                ("gmm.3", 200, 300), ("splash_mqa_fwd_residuals.4", 600, 50),
                ("add.6", 700, 10),
                ("gmm.3", 2100, 300)]}},        # in the eval module: not counted
        "host": []}
    by_module = {"jit_window": lm.scope_instructions(HLO, SCOPES),
                 "jit_evaluate": lm.scope_instructions(HLO, SCOPES)}
    got = lm.scope_seconds(trace, by_module, ["jit_window"])
    assert got == {"moe_route": 100e-9, "moe_experts": 300e-9,
                   "attn_blockdiff": 50e-9}
    assert lm.scope_seconds({"devices": {}}, by_module, ["jit_window"]) == {}


def run_of(counters, trace, units):
    config = mf.load_json(os.path.join(
        mf.HERE, "configs", "sdar-30b-a3b-ep8-f32.json"))
    window = types.SimpleNamespace(
        units=units, total=lambda k: sum(u[k] for u in units))
    return types.SimpleNamespace(
        counters=counters, trace=trace, window=window, chips=1,
        config=config, peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_readers_return_none_without_scopes_or_counters():
    """An untraced run, and a traced run of a program that has neither the
    scopes nor the counters (the parent)."""
    units = [{"images": 16, "steps": 4}]
    for run in (run_of({}, {}, units),
                run_of({"host_round_trips": 4},
                       {"train_module_busy_s": 5.0}, units)):
        for reader in (lm.attn_share, lm.moe_share, lm.head_share,
                       lm.attn_roofline, lm.expert_roofline,
                       lm.local_rows_share, lm.load_max_over_mean):
            assert reader(run) is None


def test_readers_on_a_plain_run():
    units = [{"images": 16, "steps": 4, "moe_rows_max_expert": 1100.0},
             {"images": 16, "steps": 4, "moe_rows_max_expert": 1200.0}]
    rows = 2 * 16 * 8192 * 6 * 1.02
    run = run_of({"scope_seconds": {"attn_blockdiff": 3.0, "moe_route": 0.2,
                                    "moe_experts": 1.8, "lm_head": 0.5},
                  "moe_rows_local": rows,
                  "moe_rows_expected": rows / 1.02},
                 {"train_module_busy_s": 10.0}, units)
    assert lm.attn_share(run) == 30.0 and lm.head_share(run) == 5.0
    assert abs(lm.moe_share(run) - 20.0) < 1e-9
    assert abs(lm.local_rows_share(run) - 1.02) < 1e-9
    # 4.95 TFLOP a sequence over the allowed quarter, 32 sequences, 3 s
    flops = lm_flops.attention_train_flops_per_sequence(run.config)
    assert abs(flops - 6 * 2 * (4096 * 4 + 4096 ** 2) * 4096 * 6) < 1
    assert abs(lm.attn_roofline(run)
               - 100 * flops * 32 / 197e12 / 3.0) < 1e-9
    assert 0 < lm.attn_roofline(run) < 100
    assert 0 < lm.expert_roofline(run) < 100
    # mean rows of a held expert in a layer of a sequence: 8192 * 8 / 128
    assert abs(lm.load_max_over_mean(run) - 1200 / (512 * 1.02)) < 1e-9


def test_layer_table_counts_the_published_step():
    """12.9 TFLOP a trained sequence, and the attention rows are the
    kernel's own count."""
    from benchmark import flops
    config = mf.load_json(os.path.join(
        mf.HERE, "configs", "sdar-30b-a3b-ep8-f32.json"))
    total = flops.train_flops_per_image(config["layer_table"])
    assert 12.8e12 < total < 13.0e12
    attn = [r for r in config["layer_table"] if r[3] == 2050]
    assert len(attn) == 2 * config["num_hidden_layers"]
    assert abs(flops.train_flops_per_image(attn)
               / lm_flops.attention_train_flops_per_sequence(config) - 1) \
        < 1e-3
    assert config["num_experts"] == len(config["experts_held"]) == 16
    assert json.dumps(config["published"]) and \
        "8 chips share each layer" in config["deployment"]
