"""The readings a token-traffic cell's limits are set from, in one process
on the chip (`calibrate.py` stages images and knows the image faults):

    python3 benchmark/calibrate_tokens.py <cell> --seeds 12 --control-seeds 3
                                          [--weight-seeds 3] [--limits]

For each seed: the program's numbers (exactly as a run takes them: the first
three steps on the trainer's own window callable, the evaluation, the timed
window program's first three losses) against the plain reference: the LOWER
readings.  On the first `--control-seeds` seeds the UPPER readings: the
control (`reference_bf16`: the plain reference computed in bfloat16
throughout, weights and optimizer state too, in the program's place), the
program's own bf16 path (`control_bf16`: float32 weights, bfloat16
activations; it reads like a sound run on this chip and so sets no limit)
and, unless `--no-faults`, each fault planted in the reference put in the
program's place: `drop_half`, `freeze`, and this model's own `causal_mask`
and `drop_rows`.  Each row keeps the per-leaf norms it was made from
(`leaves`).  Then `--weight-seeds` further sound runs on OTHER weights than
the traffic file's `weights_seed`, so that the limits are not tuned to one
set of weights.  Writes
chiprun_out/calibrate/<cell><--tag>.json; `--limits` reads every such file
of the cell back (and every sets file of the cell under chiprun_out/sets/)
and prints the limits by the rule of PERF.md section 2; with `--write` that
output becomes benchmark/limits/<cell>.json, so the committed limits are
what this tool derives and nothing else.
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

FAULTS = ("drop_half", "freeze", "causal_mask", "drop_rows")
CONTROLS = ("reference_bf16", "control_bf16")
NUMBERS = ("loss1", "loss2", "loss3", "grad1_leaf", "dparam3_leaf",
           "eval_loss3")


def program_numbers(config, traffic, seed, train, heldout, precision=None):
    from benchmark import manifest as mf
    from benchmark.drivers import train_epochs as base
    from benchmark.drivers import train_tokens as tt
    from cs744_ddp_tpu.obs import NULL

    def build(config, traffic, seed, telemetry, data_dir):
        return tt.build_trainer(config, traffic, seed, telemetry, data_dir,
                                precision=precision)
    scratch = os.path.join(mf.HERE, "out", "calibrate")
    os.makedirs(scratch, exist_ok=True)
    trainer = tt.trainer_on(build, config, traffic, seed, NULL, train,
                            heldout, scratch)
    program = base.first_steps(trainer)
    unit = tt.make_unit(trainer, 0, traffic["stream_units"])
    program["loss"] = unit(0)["first_losses"]
    del unit, trainer
    gc.collect()
    return program


def derive_limits(lower: dict, upper: dict) -> dict:
    """{number: {lower, upper, upper_from, limit}}: 60% of the way from the
    largest sound reading to the least qualifying upper reading in log
    scale, at most 10x the lower.  An upper reading qualifies at 3x the
    lower (the control, `freeze`) or 10x (the other faults)."""
    out = {}
    for name in NUMBERS:
        lo = lower[name]
        ok = {k: v for k, v in upper.get(name, {}).items()
              if v >= lo * (3 if k in CONTROLS + ("freeze",) else 10)}
        row = {"lower": lo, "all_upper": upper.get(name, {})}
        if ok:
            src = min(ok, key=ok.get)
            limit = min(lo * (ok[src] / lo) ** 0.6, 10 * lo)
            digits = 1 - int(math.floor(math.log10(limit)))
            row.update(upper=ok[src], upper_from=src,
                       limit=round(limit, digits))
        out[name] = row
    return out


def limits_main(cell: str, write: bool = False) -> None:
    rows = []
    for path in sorted(glob.glob(os.path.join("chiprun_out", "calibrate",
                                              cell + "*.json"))):
        rows += json.load(open(path))["rows"]   # every study, any --tag
    lower = {n: 0.0 for n in NUMBERS}
    upper = {}
    seeds = 0
    for row in rows:
        if "sound" in row:
            seeds += 1
            for n in NUMBERS:
                lower[n] = max(lower[n], row["sound"]["numbers"][n])
        for kind in CONTROLS + FAULTS:
            if kind in row:
                for n in NUMBERS:
                    v = row[kind]["numbers"][n]
                    slot = upper.setdefault(n, {})
                    slot[kind] = min(slot.get(kind, math.inf), v)
    for path in glob.glob(os.path.join("chiprun_out", "sets",
                                       cell + "-*s-units", "*.json")):
        rec = json.load(open(path))
        nums = rec.get("compared_detail", {}).get("numbers")
        if nums:
            seeds += 1
            for n in NUMBERS:
                lower[n] = max(lower[n], nums[n])
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--weight-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--limits", action="store_true")
    ap.add_argument("--write", action="store_true",
                    help="with --limits: write benchmark/limits/<cell>.json")
    ap.add_argument("--no-faults", action="store_true",
                    help="the controls only on the control seeds")
    ap.add_argument("--tag", default="",
                    help="suffix of the study's file, to keep an older one")
    args = ap.parse_args(argv)
    if args.limits:
        return limits_main(args.cell, args.write)

    import jax
    from benchmark import correct, manifest as mf
    from benchmark.drivers import train_tokens as tt
    from benchmark.run import device_or_exit
    from cs744_ddp_tpu.utils import compcache

    manifest = mf.load()
    cell = mf.cell(manifest, args.cell)
    config = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
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
        train, heldout = tt.make_data(seed, config, tr, cell["chips"])
        program = program_numbers(config, tr, seed, train, heldout)
        control = None
        if i < args.control_seeds:
            control = program_numbers(config, tr, seed, train, heldout,
                                      precision="bf16")
        reference = tt.reference_record(manifest, cell, config, tr, seed,
                                        train, heldout)
        row = {"seed": seed, "weights_seed": tr["weights_seed"],
               "sound": correct.numbers(program, reference),
               "loss_prog": program["loss"], "loss_ref": reference["loss"],
               "loss_single": program["loss_single_steps"],
               "eval": [program["eval_loss"], reference["eval_loss"],
                        program["eval_correct"],
                        reference["eval_correct"]]}
        row["leaves"] = {"sound": leaves(program),
                         "reference": leaves(reference)}
        if control is not None:
            row["control_bf16"] = correct.numbers(control, reference)
            low = tt.reference_record(manifest, cell, config, tr, seed,
                                      train, heldout, dtype="bfloat16")
            row["reference_bf16"] = correct.numbers(low, reference)
            row["leaves"].update(control_bf16=leaves(control),
                                 reference_bf16=leaves(low))
            for fault in () if args.no_faults else FAULTS:
                faulty = tt.reference_record(manifest, cell, config, tr,
                                             seed, train, heldout,
                                             **{fault: True})
                row[fault] = correct.numbers(faulty, reference)
        row["seconds"] = time.time() - t0
        rows.append(row)
        save()
        print(json.dumps({k: (v["numbers"] if isinstance(v, dict)
                              and "numbers" in v else v)
                          for k, v in row.items() if k != "leaves"}),
              flush=True)


if __name__ == "__main__":
    main()
