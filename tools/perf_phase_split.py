"""Reproduce the BASELINE.md forward/backward split artifact.

The reference times forward and backward+sync+step separately
(``/root/reference/src/Part 1/main.py:33-43``).  A per-step timer
charges every phase one host dispatch + fetch, so the on-chip split is
``Trainer.measure_phase_split``'s two-window-size slope (see its
docstring).  This tool runs the committed table's measurement
configuration (VGG-11, f32, batch 256, W=100, 3 interleaved windows),
prints one JSON line per trial to stderr, and emits the across-trials
slope (mins over every trial's window totals) as the final stdout line —
the statistic BASELINE.md records.

Run:  python tools/perf_phase_split.py [--model vgg11] [--trials 3]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="vgg11")
    p.add_argument("--global-batch", type=int, default=256)
    p.add_argument("--window-iters", type=int, default=100)
    p.add_argument("--windows", type=int, default=3)
    # 3 trials: a single slow dispatch among one trial's six window
    # totals visibly skews a lone within-trial slope (observed); three
    # trials of mins pin the across-trials slope.
    p.add_argument("--trials", type=int, default=3)
    args = p.parse_args(argv)
    if args.trials < 1:
        p.error("--trials must be >= 1")

    from cs744_ddp_tpu.train.loop import Trainer
    from cs744_ddp_tpu.utils.compcache import \
        enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
    trainer = Trainer(model=args.model, strategy="single", num_devices=1,
                      global_batch=args.global_batch,
                      data_dir=os.environ.get("CIFAR_DATA_DIR", "./data"),
                      log=lambda s: None)
    best = {}
    w = half = None
    for _ in range(args.trials):
        split = trainer.measure_phase_split(
            window_iters=args.window_iters, windows=args.windows)
        w, half = split["window_iters"], split["window_iters"] // 2
        for k, v in split["window_totals_ms"].items():
            best[k] = min(best.get(k, float("inf")), v)
        print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                          for k, v in split.items() if k != "window_totals_ms"},
                         ), file=sys.stderr)
    # Across-trials slope: mins over every trial's windows — one contended
    # half-window min within a single trial cannot skew this estimate.
    span = w - half
    fwd = (best[f"fwd_{w}"] - best[f"fwd_{half}"]) / span
    step = (best[f"step_{w}"] - best[f"step_{half}"]) / span
    print(json.dumps({"model": args.model, "protocol":
                      f"two-size slope W={w}/{half}, "
                      f"best of {args.trials}x{args.windows} windows",
                      "forward_ms_per_iter": round(fwd, 4),
                      "backward_ms_per_iter": round(step - fwd, 4),
                      "step_ms_per_iter": round(step, 4)}))


if __name__ == "__main__":
    main()
