"""Perf attribution experiments for the VGG-11/f32/batch-256 headline config.

Times steady-state throughput of controlled variants on the real chip to
attribute the gap to the v5e ceiling (VERDICT r2 weak #2): augmentation,
BatchNorm, precision, batch size.  Not part of the bench contract — a
builder's tool; results inform BASELINE.md and optimization work.

Run (on the TPU chip): python tools/perf_attribution.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def throughput(**kw):
    """(img/s, MFU fields) for one variant.  MFU arithmetic lives in
    analysis/costmodel.mfu_fields — the one copy of the v5e peak constant
    (round 8); this tool only measures."""
    from cs744_ddp_tpu.analysis.costmodel import mfu_fields
    from cs744_ddp_tpu.train.loop import Trainer
    defaults = dict(model="vgg11", strategy="single", num_devices=1,
                    global_batch=256, data_dir="./data", log=lambda s: None)
    defaults.update(kw)
    tr = Trainer(**defaults)
    _, ips = tr.steady_state_throughput(max_iters=100)
    return ips, mfu_fields(ips, tr.step_flops_per_image(),
                           tr.mesh.devices.flat[0].device_kind)


def main():
    from cs744_ddp_tpu.utils.compcache import \
        enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    results = {}
    experiments = [
        ("baseline_f32_b256", {}),
        ("no_augment", {"augment": False}),
        ("bf16_b256", {"precision": "bf16"}),
        ("f32_b1024", {"global_batch": 1024}),
        ("bf16_b1024", {"global_batch": 1024, "precision": "bf16"}),
        ("bf16_b2048", {"global_batch": 2048, "precision": "bf16"}),
        ("bf16_b4096", {"global_batch": 4096, "precision": "bf16"}),
    ]
    for name, kw in experiments:
        t0 = time.time()
        ips, mfu = throughput(**kw)
        results[name] = {"images_per_sec": round(ips, 1), **mfu}
        print(f"{name:22s} {ips:10.1f} img/s  "
              f"mfu {mfu.get('mfu_vs_bf16_peak', '-')}  "
              f"(wall {time.time()-t0:.0f}s)", file=sys.stderr)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
