"""The comparison that decides `correct` for a training cell.  No JAX here.

Both sides hand in the same record (reference/common.py `follow` makes the
reference's; the driver reads the program's off the timed path):

  loss              [l1, l2, l3]   loss of each of the first three steps
  momentum1_norms   {leaf: norm}   the first gradient as the optimizer gets
                                   it: the velocity after one step
  dparam_norms      {leaf: norm}   parameters' change after the three steps
  eval_loss         float          test-set loss of the state after them
  grad_norms        [{leaf: norm}] (reference only) raw gradient per step

A leaf's number is the gap between the program's norm and the reference's
(not the norm of a difference), over the reference's norm of that leaf or of
the median leaf, whichever is larger: some gradients are all but zero.  A
metric takes the worst leaf.  Leaves whose raw reference gradient is under a
thousandth of the median leaf's (a convolution's bias in front of
BatchNorm) move by round-off alone and are left out of the change.
"""

from __future__ import annotations

import math
import statistics

ZERO_GRAD_SHARE = 1e-3


def _worst_leaf(prog: dict, ref: dict, skip=()) -> tuple:
    if set(prog) != set(ref):
        raise KeyError(f"leaves differ: program-only "
                       f"{sorted(set(prog) - set(ref))[:3]}, reference-only "
                       f"{sorted(set(ref) - set(prog))[:3]}")
    floor = statistics.median(ref.values())
    worst, where = 0.0, None
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        gap = abs(prog[leaf] - r) / max(r, floor)
        if not gap <= worst:            # NaN counts as worst
            worst, where = gap, leaf
    return worst, where


def numbers(prog: dict, ref: dict) -> dict:
    """{name: value} of every number compared, plus `worst_leaves` naming
    where the per-leaf ones were read."""
    out = {}
    for k in range(3):
        out[f"loss{k + 1}"] = abs(prog["loss"][k] - ref["loss"][k]) \
            / abs(ref["loss"][k])
    g1 = ref["grad_norms"][0]
    cut = ZERO_GRAD_SHARE * statistics.median(g1.values())
    still = {leaf for leaf, g in g1.items() if g < cut}
    out["grad1_leaf"], w1 = _worst_leaf(
        prog["momentum1_norms"], ref["momentum1_norms"])
    out["dparam3_leaf"], w3 = _worst_leaf(
        prog["dparam_norms"], ref["dparam_norms"], skip=still)
    out["eval_loss3"] = abs(prog["eval_loss"] - ref["eval_loss"]) \
        / abs(ref["eval_loss"])
    return {"numbers": out, "worst_leaves": {"grad1_leaf": w1,
                                            "dparam3_leaf": w3},
            "leaves_left_out": sorted(still)}


def decide(nums: dict, limits: dict) -> tuple:
    """(correct, {name: [value, limit]}) over the numbers that have a
    limit (one that the chip study found no upper reading for has none and
    is recorded, not compared).  Every limit must find its number; a NaN
    fails."""
    missing = set(limits) - set(nums)
    if missing:
        raise KeyError(f"limits without a number: {sorted(missing)}")
    table = {k: [nums[k], limits[k]] for k in sorted(limits)}
    ok = all(math.isfinite(v) and v <= lim for v, lim in table.values())
    return ok, table
