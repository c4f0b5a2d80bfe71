"""The readings the limits are set from, in one process on the chip:

    python3 benchmark/calibrate.py <cell> --seeds 12 --control-seeds 3

For each seed: the program's numbers (exactly as a run takes them: the
first three steps on the trainer's own window callable, the evaluation,
the timed window program's first three losses) against the plain
reference — the LOWER readings.  Then, on the first `--control-seeds`
seeds, the UPPER readings: the control (the program's own bf16 path in the
program's place) and each fault a training cell can have, planted in the
reference put in the program's place: half of the batch left out with the
mean taken over the rest, a step that returns its state unchanged (which
reads 1 on the per-leaf numbers by construction; the run reads the losses),
and (across chips) the exchange left out.  Needs no measured window.  Writes chiprun_out/calibrate/<cell>.json.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def program_numbers(config, traffic, seed, train, test, precision=None):
    from benchmark import manifest as mf
    from benchmark.drivers import train_epochs as tr
    from cs744_ddp_tpu.obs import NULL
    def build(config, traffic, seed, telemetry, data_dir):
        return tr.build_trainer(config, traffic, seed, telemetry, data_dir,
                                precision=precision)
    scratch = os.path.join(mf.HERE, "out", "calibrate")
    os.makedirs(scratch, exist_ok=True)
    trainer = tr.trainer_on(build, config, traffic, seed, NULL, train, test,
                            scratch)
    program = tr.first_steps(trainer)
    unit = tr.make_unit(trainer, tr.images_per_epoch(trainer))
    program["loss"] = unit(0)["first_losses"]
    del unit, trainer
    gc.collect()
    return program


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--faults-only", action="store_true",
                    help="the reference and its faults alone (no program "
                         "run): the upper readings of the faults")
    args = ap.parse_args(argv)

    import jax
    from benchmark import correct, manifest as mf
    from benchmark.drivers import train_epochs as tr
    from benchmark.run import device_or_exit
    from cs744_ddp_tpu.utils import compcache

    manifest = mf.load()
    cell = mf.cell(manifest, args.cell)
    config = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    device = device_or_exit(cell["chips"])
    compcache.enable_persistent_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        train, test = tr.make_data(seed, config, cell["chips"])
        reference = tr.reference_record(manifest, cell, config, seed,
                                        train, test)
        row = {"seed": seed}
        if not args.faults_only:
            program = program_numbers(config, traffic, seed, train, test)
            row.update({
                "sound": correct.numbers(program, reference),
                "loss_prog": program["loss"], "loss_ref": reference["loss"],
                "loss_single": program["loss_single_steps"],
                "eval": [program["eval_loss"], reference["eval_loss"],
                         program["eval_correct"],
                         reference["eval_correct"]]})
        if i < args.control_seeds:
            if not args.faults_only:
                control = program_numbers(config, traffic, seed, train,
                                          test, precision="bf16")
                row["control_bf16"] = correct.numbers(control, reference)
            faults = ["drop_half", "freeze"] + (
                ["skip_sync"] if cell["chips"] > 1 else [])
            for fault in faults:
                faulty = tr.reference_record(manifest, cell, config, seed,
                                             train, test, **{fault: True})
                row[fault] = correct.numbers(faulty, reference)
        row["seconds"] = time.time() - t0
        rows.append(row)
        print(json.dumps({k: (v["numbers"] if isinstance(v, dict)
                              and "numbers" in v else v)
                          for k, v in row.items()}), flush=True)
    out = os.path.join("chiprun_out", "calibrate")
    os.makedirs(out, exist_ok=True)
    name = args.cell + ("-faults" if args.faults_only else "") + ".json"
    with open(os.path.join(out, name), "w") as f:
        json.dump({"cell": args.cell, "device": device, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
