"""The sets of runs a bound is set from, as the driver's check makes them:
separate processes of `run.py`, one after another on one machine, sharing
one compile cache.

    python3 benchmark/sets.py <cell> [--seconds S] [--runs 6] [--sets 2]
                              [--traced 3] [--first-seed N]

One cold run first (it compiles; its set-up is recorded apart), then
`--sets` sets of `--runs` runs with the same seeds in every set, then
`--traced` runs with `--trace 1`.  Prints each metric's spread per set (the
distance between the first and third quartile, `statistics.quantiles(n=4)`,
over the median) and writes chiprun_out/sets/<cell>-<s>s.json beside a copy
of every run's per-unit file.  This process never touches JAX: every run gets
the chip to itself.
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(cell, seed, seconds, trace, tag):
    """One process of run.py; its per-unit file is copied under `tag`
    (the sets use the same seeds, so the next set would overwrite it)."""
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    wall = time.time() - t0
    lines = p.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        line = None
    if p.returncode or line is None:
        print(f"run failed rc={p.returncode}\n{p.stdout[-2000:]}\n"
              f"{p.stderr[-3000:]}", flush=True)
    units = os.path.join(HERE, "out", cell, f"seed{seed}-trace{trace}.json")
    if os.path.isfile(units):
        dest = os.path.join(ROOT, "chiprun_out", "sets",
                            f"{cell}-{seconds}s-units")
        os.makedirs(dest, exist_ok=True)
        shutil.copy(units, os.path.join(dest, f"{tag}-seed{seed}.json"))
    return {"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": wall,
            "line": line, "earlier": lines[:-1][-4:]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_100_000_023)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    seeds = [args.first_seed + 104_729 * i for i in range(args.runs)]
    out = {"cell": args.cell, "seconds": seconds, "seeds": seeds}

    def show(tag, r):
        m = (r["line"] or {}).get("metrics", {})
        print(tag, "seed", r["seed"], "rc", r["rc"],
              "correct", (r["line"] or {}).get("correct"),
              "wall %.0f" % r["wall_s"],
              {k: round(v["value"], 4) for k, v in m.items()}, flush=True)

    out["cold"] = one_run(args.cell, args.first_seed - 1, seconds, 0, "cold")
    show("cold", out["cold"])
    out["sets"] = []
    for s in range(args.sets):
        runs = [one_run(args.cell, seed, seconds, 0, f"set{s}")
                for seed in seeds]
        for r in runs:
            show(f"set{s}", r)
        out["sets"].append(runs)
    out["traced"] = [one_run(args.cell, args.first_seed + 7 + i, seconds, 1,
                             "traced") for i in range(args.traced)]
    for r in out["traced"]:
        show("traced", r)

    summary = {}
    for s, runs in enumerate(out["sets"]):
        lines = [r["line"] for r in runs if r["line"]]
        for name in (lines[0]["metrics"] if lines else {}):
            vals = [l["metrics"][name]["value"] for l in lines]
            if len(vals) >= 2:
                summary.setdefault(name, []).append(
                    {"set": s, "median": statistics.median(vals),
                     "spread": spread(vals), "values": vals})
    out["summary"] = summary
    for name, rows in summary.items():
        for row in rows:
            print(f"{name} set{row['set']}: median {row['median']:.6g} "
                  f"spread {100 * row['spread']:.3f}%", flush=True)
    dest = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"{args.cell}-{seconds}s.json"), "w") as f:
        json.dump(out, f, indent=1)
    bad = [r for runs in out["sets"] for r in runs
           if r["rc"] or not (r["line"] or {}).get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
