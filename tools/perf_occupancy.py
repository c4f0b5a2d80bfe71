"""Per-time-slice TensorCore/DMA occupancy account from an XPlane trace.

VERDICT r4 item 1 asked whether the step leaves recoverable idle time —
the additive (no-overlap) roofline in BASELINE.md conceded ~45% of the
bf16 step to *un-overlapped* memory time, which would make DMA/compute
overlap the obvious lever (microbatch pipelining etc.).  This tool answers
from the trace the framework already collects (``--profile-dir``):

  * window span of the LAST ``jit_window`` module dispatch (steady state:
    earlier dispatches carry compile/warmup),
  * TensorCore busy = union of leaf "XLA Ops" events (the ``while`` scan
    wrapper, ``*-start`` markers excluded) — on TPU this line is the
    serialized TC execution, so window − union is TRUE TC idle,
  * DMA busy = union of "Async XLA Ops" events (async copies overlapped
    by the scheduler),
  * recoverable := both-idle + TC-idle-during-DMA — the only time any
    scheduling change (pipelining, reordering, prefetching) could win,
  * TC busy split MXU-class vs other: each event name is mapped into the
    freshly compiled window HLO (same config + persistent compilation
    cache => same module) and classed MXU if its fusion's computation
    contains a ``convolution(`` / `` dot(`` — giving the kernel-efficiency
    ceiling: were every non-conv op free, the step could not run faster
    than the conv-fusion time.

Run (on the TPU chip):
  python tools/perf_occupancy.py                     # bf16/b1536 peak config
  python tools/perf_occupancy.py --precision f32 --global-batch 256
"""

import argparse
import collections
import glob
import json
import os
import re

import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")


def build_mxu_map(model, global_batch, precision, window):
    """{instruction_name: True if its computation runs on the MXU} from the
    compiled window program's final HLO text."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cs744_ddp_tpu.models import get_model
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.parallel import get_strategy, mesh as meshlib
    from cs744_ddp_tpu.train import step as steplib

    mesh = meshlib.make_mesh(1)
    init_fn, apply_fn = get_model(model)
    state = steplib.init_train_state(init_fn, jax.random.PRNGKey(0))
    state = meshlib.put_global_tree(state, meshlib.replicated(mesh))
    win = steplib.make_train_window(
        apply_fn, get_strategy("single"), mesh, sgd.SGDConfig(),
        augment=True,
        compute_dtype=jnp.bfloat16 if precision == "bf16" else None)
    from jax.sharding import NamedSharding, PartitionSpec as P
    esh = NamedSharding(mesh, P(None, meshlib.DATA_AXIS))
    nb = window
    args = (state, jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((nb, global_batch, 32, 32, 3), jnp.uint8,
                                 sharding=esh),
            jax.ShapeDtypeStruct((nb, global_batch), jnp.int32, sharding=esh),
            jnp.int32(0), jnp.zeros((window,), jnp.int8))
    txt = win.lower(*args).compile().as_text()

    # Computations containing MXU work.
    comp_mxu = {}
    cur = None
    for line in txt.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*"
                     r"(?:->[^{]*)?\{\s*$", line)
        if m and line.rstrip().endswith("{") and "=" not in line:
            cur = m.group(1)
            comp_mxu.setdefault(cur, False)
            continue
        if cur and (" convolution(" in line or " dot(" in line):
            comp_mxu[cur] = True
    # Instructions: direct convs are MXU; fusions inherit their called
    # computation's class.
    instr_mxu = {}
    for line in txt.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if not m:
            continue
        name = m.group(1)
        instr_mxu.setdefault(name, False)  # every instruction classifies
        if " convolution(" in line or " dot(" in line:
            instr_mxu[name] = True
        cm = re.search(r"calls=%?([\w.\-]+)", line)
        if cm:
            instr_mxu[name] = instr_mxu.get(name, False) or \
                comp_mxu.get(cm.group(1), False)
    return instr_mxu


def union(intervals):
    intervals = sorted(intervals)
    out = []
    for s, t in intervals:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def span(intervals):
    return sum(t - s for s, t in intervals)


def intersect(a, b):
    """Total overlap between two interval unions."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        t = min(a[i][1], b[j][1])
        if t > s:
            tot += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def complement(intervals, t0, t1):
    out = []
    prev = t0
    for s, t in intervals:
        if s > prev:
            out.append([prev, s])
        prev = max(prev, t)
    if t1 > prev:
        out.append([prev, t1])
    return out


def analyze(trace_file, mxu_map, window_iters):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    xs = xplane_pb2.XSpace()
    with open(trace_file, "rb") as f:
        xs.ParseFromString(f.read())
    tpu = [p for p in xs.planes if p.name == "/device:TPU:0"][0]
    md = tpu.event_metadata
    lines = {l.name: l for l in tpu.lines}
    wins = [e for e in lines["XLA Modules"].events
            if "window" in md[e.metadata_id].name]
    if not wins:
        raise RuntimeError("no jit_window module event in trace")
    w = wins[-1]
    t0, t1 = w.offset_ps, w.offset_ps + w.duration_ps

    tc, per_op = [], collections.Counter()
    mxu_time = other_time = unknown_time = 0
    for e in lines["XLA Ops"].events:
        if not (t0 <= e.offset_ps < t1):
            continue
        name = md[e.metadata_id].name
        inst = re.match(r"%?([\w.\-]+)\s*=", name)
        inst = inst.group(1) if inst else name
        op = re.search(r"=\s*[^=]*?\s([a-z][\w\-]*)\(", name)
        op = op.group(1) if op else "?"
        if op in ("while", "copy-start", "async-start", "all-reduce-start"):
            continue  # containers/markers, not TC execution time
        tc.append([e.offset_ps, e.offset_ps + e.duration_ps])
        per_op[(inst, op)] += e.duration_ps
        if op in ("convolution", "dot"):
            mxu_time += e.duration_ps
        elif inst in mxu_map:
            if mxu_map[inst]:
                mxu_time += e.duration_ps
            else:
                other_time += e.duration_ps
        else:
            unknown_time += e.duration_ps

    dma = [[e.offset_ps, e.offset_ps + e.duration_ps]
           for e in lines["Async XLA Ops"].events
           if t0 <= e.offset_ps < t1]

    tc_u, dma_u = union(tc), union(dma)
    tc_idle = complement(tc_u, t0, t1)
    win_ps = t1 - t0
    tc_busy = span(tc_u)
    idle_during_dma = intersect(tc_idle, dma_u)
    both_idle = span(tc_idle) - idle_during_dma
    top = [{"op": f"{i} [{o}]", "ms": round(d / 1e9, 3),
            "class": ("mxu" if (o in ("convolution", "dot")
                                or mxu_map.get(i, False)) else "other")}
           for (i, o), d in per_op.most_common(12)]
    return {
        "window_ms": round(win_ps / 1e9, 3),
        "iters": window_iters,
        "per_iter_ms": round(win_ps / 1e9 / window_iters, 3),
        "tc_busy_ms": round(tc_busy / 1e9, 3),
        "tc_busy_pct": round(100 * tc_busy / win_ps, 2),
        "dma_busy_ms": round(span(dma_u) / 1e9, 3),
        "dma_busy_pct": round(100 * span(dma_u) / win_ps, 2),
        "tc_idle_during_dma_ms": round(idle_during_dma / 1e9, 3),
        "both_idle_ms": round(both_idle / 1e9, 3),
        "recoverable_pct": round(
            100 * (idle_during_dma + both_idle) / win_ps, 2),
        "tc_mxu_class_ms": round(mxu_time / 1e9, 3),
        "tc_other_class_ms": round(other_time / 1e9, 3),
        "tc_unclassified_ms": round(unknown_time / 1e9, 3),
        "mxu_class_pct_of_busy": round(100 * mxu_time / max(tc_busy, 1), 2),
        "top_ops": top,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="vgg11")
    ap.add_argument("--global-batch", type=int, default=1536)
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--window", type=int, default=20)
    ap.add_argument("--trace", help="existing .xplane.pb (skip measurement)")
    args = ap.parse_args()

    from cs744_ddp_tpu.utils.compcache import \
        enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    mxu_map = build_mxu_map(args.model, args.global_batch, args.precision,
                            args.window)
    trace = args.trace
    if trace is None:
        import jax
        from cs744_ddp_tpu.data import cifar10
        from cs744_ddp_tpu.train.loop import Trainer
        # Size the synthetic epoch to exactly two full windows so the LAST
        # window dispatch has args.window iterations (per_iter_ms correct).
        cifar10.TRAIN_SIZE = 2 * args.window * args.global_batch
        tr = Trainer(model=args.model, strategy="single", num_devices=1,
                     global_batch=args.global_batch,
                     precision=args.precision,
                     data_dir=tempfile.mkdtemp(), log=lambda s: None,
                     limit_train_batches=2 * args.window)
        tr.train_model(0)  # compile/warm outside the trace
        prof = tempfile.mkdtemp(prefix="occupancy_")
        with jax.profiler.trace(prof):
            tr.train_model(0)
        traces = glob.glob(prof + "/**/*.xplane.pb", recursive=True)
        trace = traces[0]
    result = {"config": f"{args.model}/{args.precision}/"
                        f"b{args.global_batch}/W{args.window}",
              **analyze(trace, mxu_map, args.window)}
    print(json.dumps(result))


if __name__ == "__main__":
    main()
