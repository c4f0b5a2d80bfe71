"""The readings a `train-tokens-latent` cell's limits are set from, in one
process on the chip: `calibrate_tokens.py`'s study (its rule for a limit,
`derive_limits`, and its six numbers are imported from there) for a cell
whose driver reads its model from the traffic file and whose reference
plants its own faults:

    python3 benchmark/calibrate_latent.py <cell> --seeds 6 --control-seeds 1
                                          [--weight-seeds 2] [--limits]

For each seed the program's numbers exactly as a run takes them against the
plain reference: the LOWER readings.  On the first `--control-seeds` seeds
the UPPER readings, each the plain reference with something planted put in
the program's place: the control (`reference_bf16`: the reference computed
in bfloat16 throughout) and, unless `--no-faults`, `drop_half`, `freeze`
and every fault the reference module lists (`FAULTS` there).  Then
`--weight-seeds` further sound runs on OTHER weights than the traffic
file's `weights_seed`.  The program's own bf16 path is not read: on this
chip it reads like a sound run and sets no limit (PERF.md section 2).
Writes chiprun_out/calibrate/<cell><--tag>.json; `--limits` reads every
such file of the cell back (and every sets file of the cell under
chiprun_out/sets/) and prints the limits; with `--write` that output
becomes benchmark/limits/<cell>.json, so the committed limits are what this
tool derives and nothing else.

The rule is `calibrate_tokens.derive_limits` and nothing besides: a limit
sits 60% of the way from the largest sound reading to the least qualifying
upper reading in log scale, at most 10x the reading, so every limit lies
between readings of this cell.
"""

import argparse
import gc
import glob
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.calibrate_tokens import NUMBERS, derive_limits    # noqa: E402

CONTROL = "reference_bf16"
COMMON_FAULTS = ("drop_half", "freeze")


def reference_faults(manifest, cell) -> tuple:
    """The faults the cell's reference can plant: the two every reference
    has and its own module's `FAULTS`."""
    from benchmark import manifest as mf
    follow = mf.load_module_from_path(
        mf.reference_path(manifest, cell["config"]),
        "calibrate_reference").follow
    return COMMON_FAULTS + tuple(sys.modules[follow.__module__].FAULTS)


def program_numbers(config, traffic, seed, train, heldout):
    from benchmark import manifest as mf
    from benchmark.drivers import train_epochs as base
    from benchmark.drivers import train_tokens_latent as ttl
    from cs744_ddp_tpu.obs import NULL

    scratch = os.path.join(mf.HERE, "out", "calibrate")
    os.makedirs(scratch, exist_ok=True)
    trainer = ttl.trainer_on(ttl.build_trainer, config, traffic, seed, NULL,
                             train, heldout, scratch)
    program = base.first_steps(trainer)
    unit = ttl.make_unit(trainer, 0, traffic["stream_units"])
    program["loss"] = unit(0)["first_losses"]
    del unit, trainer
    gc.collect()
    return program


def limits_main(cell: str, write: bool = False) -> None:
    rows = []
    for path in sorted(glob.glob(os.path.join("chiprun_out", "calibrate",
                                              cell + "*.json"))):
        rows += json.load(open(path))["rows"]   # every study, any --tag
    lower = {n: 0.0 for n in NUMBERS}
    upper = {}
    seeds = 0

    def sound(nums):
        for n in NUMBERS:
            lower[n] = max(lower[n], nums[n])
    for row in rows:
        if "sound" in row:
            seeds += 1
            sound(row["sound"]["numbers"])
        for kind, read in row.get("upper", {}).items():
            for n in NUMBERS:
                slot = upper.setdefault(n, {})
                slot[kind] = min(slot.get(kind, math.inf),
                                 read["numbers"][n])
    for path in glob.glob(os.path.join("chiprun_out", "sets",
                                       cell + "-*s-units", "*.json")):
        nums = json.load(open(path)).get("compared_detail", {}).get("numbers")
        if nums:
            seeds += 1
            sound(nums)
    readings = derive_limits(lower, upper)
    out = {"lower_seeds": seeds,
           "limits": {n: r["limit"] for n, r in readings.items()
                      if "limit" in r},
           "readings": readings}
    print(json.dumps(out, indent=1))
    if write:       # the cell's file is this output under its `_doc`
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "limits", cell + ".json")
        doc = json.load(open(path)).get("_doc", "") \
            if os.path.exists(path) else ""
        with open(path, "w") as f:
            json.dump({"_doc": doc, **out}, f, indent=1)
            f.write("\n")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--weight-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--limits", action="store_true")
    ap.add_argument("--write", action="store_true",
                    help="with --limits: write benchmark/limits/<cell>.json")
    ap.add_argument("--no-faults", action="store_true",
                    help="the control only on the control seeds")
    ap.add_argument("--tag", default="",
                    help="suffix of the study's file, to keep an older one")
    args = ap.parse_args(argv)
    if args.limits:
        return limits_main(args.cell, args.write)

    import jax
    from benchmark import correct, manifest as mf
    from benchmark.drivers import train_tokens_latent as ttl
    from benchmark.run import device_or_exit
    from cs744_ddp_tpu.utils import compcache

    manifest = mf.load()
    cell = mf.cell(manifest, args.cell)
    config = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    faults = () if args.no_faults else reference_faults(manifest, cell)
    device = device_or_exit(cell["chips"])
    compcache.enable_persistent_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out_dir = os.path.join("chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    rows = []

    def save():
        with open(os.path.join(out_dir, args.cell + args.tag + ".json"),
                  "w") as f:
            json.dump({"cell": args.cell, "device": device, "rows": rows},
                      f, indent=1)

    def leaves(rec):            # what the per-leaf numbers are made from
        out = {k: rec[k] for k in ("loss", "momentum1_norms",
                                   "dparam_norms", "eval_loss")}
        if "grad_norms" in rec:
            out["grad1_norms"] = rec["grad_norms"][0]
        return out

    for i in range(args.seeds + args.weight_seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        tr = dict(traffic)
        if i >= args.seeds:         # other weights, sound runs only
            tr["weights_seed"] = traffic["weights_seed"] + 1 + i - args.seeds
        train, heldout = ttl.make_data(seed, config, tr, cell["chips"])
        reference = lambda **planted: ttl.reference_record(
            manifest, cell, config, tr, seed, train, heldout, **planted)
        program = program_numbers(config, tr, seed, train, heldout)
        ref = reference()
        row = {"seed": seed, "weights_seed": tr["weights_seed"],
               "sound": correct.numbers(program, ref),
               "loss_prog": program["loss"], "loss_ref": ref["loss"],
               "loss_single": program["loss_single_steps"],
               "eval": [program["eval_loss"], ref["eval_loss"],
                        program["eval_correct"], ref["eval_correct"]],
               "leaves": {"sound": leaves(program),
                          "reference": leaves(ref)}}
        rows.append(row)
        if i < args.control_seeds:
            planted = [(CONTROL, dict(dtype="bfloat16"))] \
                + [(fault, {fault: True}) for fault in faults]
            row["upper"] = {}
            for kind, kwargs in planted:
                row["upper"][kind] = correct.numbers(reference(**kwargs), ref)
                save()              # a study cut short keeps what it read
        row["seconds"] = time.time() - t0
        save()
        print(json.dumps({"seed": seed, "weights_seed": tr["weights_seed"],
                          "seconds": row["seconds"],
                          "sound": row["sound"]["numbers"],
                          **{k: v["numbers"]
                             for k, v in row.get("upper", {}).items()}}),
              flush=True)


if __name__ == "__main__":
    main()
