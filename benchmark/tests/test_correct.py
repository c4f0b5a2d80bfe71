"""The comparison's arithmetic: worst leaf by the gap of norms, the floor
of the median leaf, leaves left out by the rule on the reference's
gradient, limits."""

import math

import pytest

from benchmark import correct


def record(scale=1.0, **over):
    leaves = {"a": 1.0, "b": 2.0, "c": 4.0, "bias": 1e-7}
    rec = {"loss": [2.5, 2.3, 2.2],
           "momentum1_norms": {k: v * scale for k, v in leaves.items()},
           "dparam_norms": {k: 0.01 * v * scale for k, v in leaves.items()},
           "eval_loss": 2.30,
           "grad_norms": [dict(leaves)]}
    rec.update(over)
    return rec


def test_identical_records_read_zero():
    n = correct.numbers(record(), record())
    assert all(v == 0 for v in n["numbers"].values())
    assert n["leaves_left_out"] == ["bias"]


def test_worst_leaf_is_a_gap_of_norms_over_the_leaf_or_the_median_leaf():
    prog = record()
    prog["momentum1_norms"]["c"] = 4.4          # 10% of its own norm
    prog["momentum1_norms"]["bias"] = 0.1       # tiny leaf: over the median
    n = correct.numbers(prog, record())
    # median reference leaf norm is 1.5: the bias reads 0.1/1.5, c 0.1
    assert n["numbers"]["grad1_leaf"] == pytest.approx(0.1)
    assert n["worst_leaves"]["grad1_leaf"] == "c"


def test_leaves_with_no_gradient_are_left_out_of_the_change_only():
    prog = record()
    prog["dparam_norms"]["bias"] = 1.0          # moved by round-off alone
    n = correct.numbers(prog, record())
    assert n["numbers"]["dparam3_leaf"] == 0
    prog["dparam_norms"]["a"] = 0.0             # a leaf that did not move
    n = correct.numbers(prog, record())
    assert n["numbers"]["dparam3_leaf"] == pytest.approx(0.01 / 0.015)


def test_a_state_left_unchanged_reads_one():
    still = record(momentum1_norms={k: 0.0 for k in "a b c bias".split()},
                   dparam_norms={k: 0.0 for k in "a b c bias".split()})
    n = correct.numbers(still, record())["numbers"]
    assert n["grad1_leaf"] == pytest.approx(1.0)
    assert n["dparam3_leaf"] == pytest.approx(1.0)


def test_losses_are_relative_gaps():
    n = correct.numbers(record(loss=[2.5, 2.3 * 1.01, 2.2]), record())
    assert n["numbers"]["loss2"] == pytest.approx(0.01)
    assert n["numbers"]["loss1"] == 0 and n["numbers"]["loss3"] == 0


def test_decide_holds_each_number_to_its_own_limit():
    nums = {"loss1": 1e-6, "loss2": 5e-5, "extra": 9.0}
    ok, table = correct.decide(nums, {"loss1": 1e-5, "loss2": 1e-4})
    assert ok and table == {"loss1": [1e-6, 1e-5], "loss2": [5e-5, 1e-4]}
    ok, _ = correct.decide(nums, {"loss1": 1e-5, "loss2": 1e-5})
    assert not ok
    ok, _ = correct.decide({"loss1": math.nan}, {"loss1": 1.0})
    assert not ok
    with pytest.raises(KeyError):
        correct.decide(nums, {"loss9": 1.0})


def test_leaves_that_differ_are_an_error():
    prog = record()
    del prog["momentum1_norms"]["a"]
    with pytest.raises(KeyError):
        correct.numbers(prog, record())
