"""The trace reduction on a small synthetic trace: interval arithmetic,
busy and idle time, gap attribution, the kinds of operation."""

import pytest

from benchmark import trace as tr


def test_union_complement_intersect():
    u = tr.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert u == [[0, 3], [5, 8]] and tr.span(u) == 6
    assert tr.complement(u, 0, 10) == [[3, 5], [8, 10]]
    assert tr.complement(u, 1, 6) == [[3, 5]]
    assert tr.intersect(u, [[2, 6]]) == 2
    assert tr.union([]) == [] and tr.complement([], 0, 4) == [[0, 4]]


@pytest.mark.parametrize("name,inst,opcode", [
    ("%fusion.445 = f32[8,8]{1,0} fusion(f32[8] %p), kind=kOutput",
     "fusion.445", "fusion"),
    ("select-and-scatter.64", "select-and-scatter.64", "select-and-scatter"),
    ("%while.3 = (s32[]) while((s32[]) %t), body=%b", "while.3", "while"),
    ("all-reduce-start.2", "all-reduce-start.2", "all-reduce-start"),
    ("%convolution.12 = f32[1]{0} convolution(f32[1] %a, f32[1] %b)",
     "convolution.12", "convolution"),
])
def test_op_of(name, inst, opcode):
    assert tr.op_of(name) == (inst, opcode)


KINDS = {"train": ["jit_window", "jit_step"], "eval": ["jit_evaluate"]}


def synthetic():
    ms = 1_000_000
    ops = [
        ("%while.1 = () while(() %x)", 0, 100 * ms),           # container
        ("%fusion.1 = f32[] fusion()", 0, 30 * ms),
        ("%all-reduce.1 = f32[] all-reduce()", 30 * ms, 10 * ms),
        ("%fusion.2 = f32[] fusion()", 35 * ms, 15 * ms),
        ("%copy-start.1 = () copy-start()", 35 * ms, 1 * ms),  # marker
        # idle 50..60 (inside train_model), then eval
        ("%fusion.1 = f32[] fusion()", 60 * ms, 20 * ms),
        # idle 80..90 (between the annotations)
        ("%fusion.2 = f32[] fusion()", 90 * ms, 10 * ms),
    ]
    modules = [("jit_window(123)", 0, 50 * ms), ("jit_evaluate(9)", 60 * ms,
                                                 20 * ms),
               ("jit_window(123)", 90 * ms, 10 * ms)]
    host = [("train_model", 0, 58 * ms), ("test_model", 58 * ms, 23 * ms),
            ("train_model", 88 * ms, 12 * ms)]
    return {"devices": {0: {"ops": ops, "modules": modules, "async": [
        ("%copy-start.1 = () copy-start()", 35 * ms, 20 * ms)]}},
        "host": host}


HLO = """HloModule jit_window, is_scheduled=true

%fused_computation.7 (param_0: f32[8,8], param_1: f32[3,3,8,8]) -> f32[8,8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  %param_1 = f32[3,3,8,8]{3,2,1,0} parameter(1)
  %convolution.3 = f32[8,8]{1,0} convolution(f32[8,8]{1,0} %param_0, f32[3,3,8,8]{3,2,1,0} %param_1), window={size=3x3}
  ROOT %maximum.1 = f32[8,8]{1,0} maximum(f32[8,8]{1,0} %convolution.3, f32[8,8]{1,0} %param_0)
}

%fused_computation.9 (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  ROOT %add.1 = f32[8,8]{1,0} add(f32[8,8]{1,0} %p, f32[8,8]{1,0} %p)
}

%body (t: (f32[8,8])) -> (f32[8,8]) {
  %t = (f32[8,8]{1,0}) parameter(0)
  %fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %x, f32[3,3,8,8]{3,2,1,0} %w), kind=kOutput, calls=%fused_computation.7
  %fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.1), kind=kLoop, calls=%fused_computation.9
  %dot.4 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %fusion.2, f32[8,8]{1,0} %fusion.2), lhs_contracting_dims={1}
  ROOT %tuple = (f32[8,8]{1,0}) tuple(f32[8,8]{1,0} %dot.4)
}

ENTRY %main.1 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  ROOT %while.1 = (f32[8,8]{1,0}) while((f32[8,8]{1,0}) %a), condition=%cond, body=%body
}
"""


def test_matmul_instructions_from_the_module_text():
    assert tr.matmul_instructions(HLO) == {"fusion.1", "dot.4",
                                           "convolution.3"}
    assert tr.module_name("jit_window(13450855693301201896)") == "jit_window"


def test_summarize_busy_idle_and_kinds():
    known = {"jit_window": {"fusion.1"}, "jit_evaluate": {"fusion.1"}}
    s = tr.summarize(synthetic(), known, KINDS)
    assert s["busy_s"] == pytest.approx(0.080)          # union, no container
    assert s["ops_s"] == pytest.approx(0.085)
    assert s["classed_s"] == pytest.approx(0.075)       # not the collective
    assert s["matmul_s"] == pytest.approx(0.050)
    assert s["matmul_train_s"] == pytest.approx(0.030)
    assert s["train_module_busy_s"] == pytest.approx(0.060)
    assert s["eval_module_busy_s"] == pytest.approx(0.020)
    assert s["collective_s_dev0"] == pytest.approx(0.010)
    assert s["collective_exposed_s_dev0"] == pytest.approx(0.005)
    assert s["gap_total_s_dev0"] == pytest.approx(0.020)


def test_gaps_are_named_by_what_the_host_was_doing():
    s = tr.summarize(synthetic(), None, KINDS)
    gaps = s["breakdown"]["idle_gaps"]
    assert sorted(gaps) == [["between_units", pytest.approx(0.010)],
                            ["train_model", pytest.approx(0.010)]]
    top = dict(s["breakdown"]["device_ops"])
    assert top["fusion.1"] == pytest.approx(0.050)
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_an_asynchronous_collective_is_read_from_its_own_line():
    t = synthetic()
    ms = 1_000_000
    t["devices"][0]["async"].append(
        ("%all-reduce-start.7 = f32[] all-reduce-start()", 45 * ms, 10 * ms))
    s = tr.summarize(t)
    # 10 ms synchronous + 10 ms asynchronous; the asynchronous one runs
    # under fusion.2 for 5 ms and through the idle gap for 5 ms
    assert s["collective_s_dev0"] == pytest.approx(0.020)
    assert s["collective_exposed_s_dev0"] == pytest.approx(0.010)
    assert s["busy_s"] == pytest.approx(0.080)      # a marker is not busy


def test_a_trace_without_device_operations_summarizes_to_nothing():
    assert tr.summarize({"devices": {}, "host": []}) == {"devices": []}
    assert not tr.summarize({"devices": {0: {"ops": [], "modules": []}},
                             "host": []}).get("busy_s")


def test_without_the_modules_text_nothing_is_classed():
    s = tr.summarize(synthetic())
    assert s["classed_s"] == 0 and s["matmul_s"] == 0 and s["busy_s"] > 0
