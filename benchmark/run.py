"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by the names in BENCHMARK.json, refuses anything
but the TPU the cell asks for, hands the run to the traffic kind's driver
(set-up, warm-up, the measured window of whole units, the comparison with
the plain reference), reads the metrics with their readers and prints one
JSON object as the last line of standard output.  Earlier lines carry the
set-up breakdown and the per-unit times; the same go to
`benchmark/out/<cell>/seed<n>-trace<t>.json`.
"""

import time

_T_START = time.perf_counter()      # before any import that costs time

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest as mf          # noqa: E402
from benchmark.window import Phases           # noqa: E402

EXIT_NO_DEVICE = 4


class Run:
    """What the metric readers see of one run."""

    def __init__(self, result: dict, config: dict, peak: dict, trace):
        self.window = result["window"]
        self.chips = result["chips"]
        self.setup_s = result["setup_s"]
        self.counters = result["counters"]
        self.memory_peak_bytes = result["memory_peak_bytes"]
        self.config = config
        self.peak = peak
        self.trace = trace or {}    # readers find nothing in an untraced run


def device_or_exit(chips: int) -> dict:
    """The device as JAX reports it; exit (no result line) unless it is a
    TPU in the peak table with the chips the cell asks for."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        sys.exit(EXIT_NO_DEVICE)
    d0 = devices[0]
    peaks = mf.load_peaks()
    if d0.platform != "tpu" or d0.device_kind not in peaks \
            or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s) of a kind in "
              f"benchmark/peaks.json {sorted(peaks)}; JAX reports "
              f"{len(devices)} x {d0.platform} {d0.device_kind!r}",
              file=sys.stderr)
        sys.exit(EXIT_NO_DEVICE)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def read_metrics(manifest: dict, cell_name: str, kind: str, run: Run) -> dict:
    out = {}
    for m in mf.cell_metrics(manifest, cell_name, kind):
        value = mf.load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(result: dict, metrics: dict, device: dict, trace) -> dict:
    """Exactly the contract's keys; `compared` (each number beside its
    limit) comes last."""
    line = {"correct": bool(result["correct"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = trace["breakdown"]
    line["compared"] = result["compared"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    phases = Phases(_T_START, time.perf_counter)
    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    config = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if traffic["chips"] != cell["chips"]:
        raise mf.ManifestError(f"{cell['name']}: traffic file says "
                               f"{traffic['chips']} chips, cell {cell['chips']}")
    phases.mark("interpreter_and_manifest")
    device = device_or_exit(cell["chips"])
    phases.mark("jax_import_and_device_init")
    peak = mf.load_peaks()[device["kind"]]

    out_dir = os.path.join(mf.HERE, "out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    result = mf.driver_module(traffic["kind"]).run({
        "manifest": manifest, "cell": cell, "config": config,
        "traffic": traffic, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "t_start": _T_START, "phases": phases,
        "out_dir": out_dir})

    trace = None
    if result["trace_dir"]:
        from benchmark import trace as tracelib
        t0 = time.perf_counter()
        trace = tracelib.summarize(
            tracelib.read_xplane(tracelib.find_xplane(result["trace_dir"])),
            result["matmul_by_module"], traffic.get("modules"))
        shutil.rmtree(result["trace_dir"], ignore_errors=True)
        result["trace_read_s"] = time.perf_counter() - t0
        if not trace.get("busy_s"):
            print("benchmark: the trace holds no device operation",
                  file=sys.stderr)
            return 5
    run = Run(result, config, peak, trace)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(manifest, cell["name"], kind, run)
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = result["window"].seconds

    record = {
        "workload": cell["name"], "seed": args.seed, "trace": args.trace,
        "seconds_asked": args.seconds,
        "setup_breakdown": result["setup_breakdown"],
        "reference_s": result["reference_s"],
        "trace_read_s": result.get("trace_read_s"),
        "counters": result["counters"], "memory": result["memory"],
        "warmup_units": result["warmup"],
        "window": result["window"].to_json(),
        "program": result["program"], "reference": result["reference"],
        "compared_detail": result["compared_detail"],
        "trace_summary": None if not trace else
        {k: v for k, v in trace.items() if k != "devices"},
    }
    line = result_line(result, metrics, device, trace)
    record["result"] = line
    with open(os.path.join(
            out_dir, f"seed{args.seed}-trace{args.trace}.json"), "w") as f:
        json.dump(record, f, indent=1)
    print("benchmark: setup " + json.dumps(
        {k: round(v, 3) for k, v in result["setup_breakdown"]}))
    print("benchmark: units " + json.dumps(
        [[round(u["train_s"], 4), round(u["eval_s"], 4)]
         for u in result["window"].units]))
    print("benchmark: counters " + json.dumps(result["counters"]))
    compared = " ".join(f"{k}={v:.3g}/{lim:.3g}"
                        for k, (v, lim) in result["compared"].items())
    print(f"benchmark: correct={line['correct']} compared(value/limit): "
          f"{compared}", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
