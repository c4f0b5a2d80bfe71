"""Per-stage ISOLATED-OP roofline probe for the VGG-11 train step — with
a measured validity limit, kept on the record (VERDICT r3 item 1):

    Isolation is only honest for tensors LARGER than VMEM.  For stages
    whose activations fit (everything past 32x32x64 at batch 256), the
    measurement scan keeps the tensor VMEM-resident across iterations and
    the measured time lands BELOW the analytic HBM bound — not a
    measurement error but a different memory system than the real step,
    where the tensor round-trips HBM between layers.  Round 4 therefore
    attributes the whole step from per-op profiler traces instead
    (BASELINE.md "Single-chip performance work"); this tool remains valid
    for the >VMEM stage-0 ops (where it confirmed pool backward at ~100%
    of its bandwidth bound, and BN backward between its 3-pass and 5-pass
    formulations) and as the recorded methodological negative result.

Each stage's forward and backward is measured in isolation on the chip and
compared against its compute bound (197 TFLOP/s v5e bf16 peak — f32 convs
run bf16 multiply passes at JAX's default precision) and its HBM bandwidth
bound (~819 GB/s v5e).

Method: scanned-K measurement (see tools/perf_pieces.py — the fixed
per-dispatch host cost demands in-program repetition), with the
carry threaded through each iteration's input (`x + 0.0*f(y)` — float
semantics forbid XLA from folding 0*x, so the chain is sequential and
nothing is DCE'd or hoisted).  Backward = (fwd+bwd) − fwd, both measured.

Bytes model (f32=4, bf16=2 bytes/elem), minimum HBM traffic:
  conv fwd : read x, w       ; write y
  conv bwd : read dy, x, w   ; write dx, dw
  bn   fwd : read x (2 passes: centered stats, then normalize); write y
  bn   bwd : read xhat, dy (x2: two fused reduction+apply passes); write dx
  pool fwd : read x; write y (y is 1/4 of x)
  pool bwd : read x, dy; write dx   (select-and-scatter re-derives argmax)

Run:  python tools/perf_stage_roofline.py [--precision f32] [--batch 256]
Results recorded in BASELINE.md ("Per-stage roofline").
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cs744_ddp_tpu.analysis.costmodel import (  # noqa: E402
    V5E_BF16_PEAK_FLOPS as V5E_PEAK_FLOPS,
    V5E_HBM_BYTES_PER_S as V5E_HBM_BYTES)

R = 3            # timed dispatches (min taken; first extra dispatch warms)
TARGET_MS = 300  # device work per dispatch: >> the ~±10 ms dispatch jitter

# VGG-11 conv stages at 32x32 input: (H=W, Cin, Cout); pool after stages
# marked in POOL_AFTER (reference model.py:3-8, cfg 'VGG11').
STAGES = [(32, 3, 64), (16, 64, 128), (8, 128, 256), (8, 256, 256),
          (4, 256, 512), (4, 512, 512), (2, 512, 512), (2, 512, 512)]
POOL_AFTER = {0, 1, 3, 5, 7}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--precision", choices=("f32", "bf16"), default="f32")
    args = p.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax

    from cs744_ddp_tpu.models import layers
    from cs744_ddp_tpu.utils.compcache import \
        enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    B = args.batch
    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    esize = 2 if args.precision == "bf16" else 4
    rng = np.random.default_rng(0)

    def bench_total(body, carry, k, *consts):
        """min-of-R TOTAL seconds for a K-iteration scan of `body`.

        The program returns a SCALAR reduction of the final carry: fetching
        the carry itself would copy megabytes to the host per fence (a
        67 MB activation) and its variance swamped the measurement in the
        first version of this tool; the scalar still transitively fences
        the whole chain."""
        def scanned(carry, *cs):
            def one(c, i):
                return body(c, i, *cs), ()
            c, _ = lax.scan(one, carry, jnp.arange(k))
            return jnp.mean(c.astype(jnp.float32))
        fn = jax.jit(scanned)
        np.asarray(fn(carry, *consts))               # compile+warm fence
        ts = []
        for _ in range(R):
            t0 = time.time()
            out = fn(carry, *consts)
            np.asarray(out)                          # value-fetch fence
            ts.append(time.time() - t0)
        return min(ts)

    # One dispatch's fixed cost: a trivial scan.
    null_total = bench_total(lambda c, i: c + 1.0, jnp.float32(0), 50)

    def bench_body(body, carry, est_roof_ms, *consts):
        """Per-iteration ms, K sized so device work is ~TARGET_MS per
        dispatch (the dispatch jitter is then a few % of signal), minus
        the dispatch's fixed cost."""
        k = int(min(max(TARGET_MS / max(est_roof_ms, 1e-3), 100), 20000))
        total = bench_total(body, carry, k, *consts)
        return max(total - null_total, 0.0) / k * 1e3

    def report(name, measured_ms, flops, bytes_):
        t_flops = flops / V5E_PEAK_FLOPS * 1e3
        t_bytes = bytes_ / V5E_HBM_BYTES * 1e3
        roof = max(t_flops, t_bytes)
        bound = "MXU" if t_flops >= t_bytes else "HBM"
        print(json.dumps({
            "stage": name, "measured_ms": round(measured_ms, 4),
            "compute_ms": round(t_flops, 4), "hbm_ms": round(t_bytes, 4),
            "roofline_ms": round(roof, 4), "bound": bound,
            "pct_of_roofline": round(100 * roof / measured_ms, 1)
            if measured_ms > 0 else None}))
        return measured_ms, roof

    totals = {"measured": 0.0, "roof": 0.0}

    for si, (H, Cin, Cout) in enumerate(STAGES):
        x = jnp.asarray(rng.normal(size=(B, H, H, Cin)), dtype)
        conv_p = {k: v for k, v in layers.conv2d_init(
            jax.random.PRNGKey(si), Cin, Cout).items()}
        dy = jnp.asarray(rng.normal(size=(B, H, H, Cout)), dtype)

        def conv_fwd(c, i, x, w, b):
            y = layers.conv2d_apply({"w": w, "b": b}, c)
            return x + 0.0 * jnp.mean(y)          # sequential, no DCE

        def conv_fwd_bwd(c, i, x, w, b, dy):
            def f(xx, ww):
                return layers.conv2d_apply({"w": ww, "b": b}, xx)
            y, vjp = jax.vjp(f, c, w)
            dx, dw = vjp(dy)
            return x + 0.0 * (jnp.mean(y) + jnp.mean(dx) + jnp.mean(dw))

        nhw = B * H * H
        wbytes = 9 * Cin * Cout * 4               # master weights stay f32
        f_flops = 2 * nhw * 9 * Cin * Cout
        f_bytes = nhw * Cin * esize + wbytes + nhw * Cout * esize
        b_flops = 2 * f_flops                     # dx conv + dw correlation
        b_bytes = (nhw * Cout * esize + nhw * Cin * esize + wbytes
                   + nhw * Cin * esize + wbytes)
        est_f = max(f_flops / V5E_PEAK_FLOPS, f_bytes / V5E_HBM_BYTES) * 1e3
        est_b = max(b_flops / V5E_PEAK_FLOPS, b_bytes / V5E_HBM_BYTES) * 1e3
        t_f = bench_body(conv_fwd, x, est_f, x, conv_p["w"], conv_p["b"])
        t_fb = bench_body(conv_fwd_bwd, x, est_f + est_b, x, conv_p["w"],
                          conv_p["b"], dy)
        m, r = report(f"conv{si} {H}x{H} {Cin}->{Cout} fwd", t_f,
                      f_flops, f_bytes)
        totals["measured"] += m
        totals["roof"] += r
        m, r = report(f"conv{si} {H}x{H} {Cin}->{Cout} bwd", t_fb - t_f,
                      b_flops, b_bytes)
        totals["measured"] += m
        totals["roof"] += r

        # BatchNorm after every conv.
        bn_p, _ = layers.batchnorm_init(Cout)

        def bn_fwd(c, i, dy_unused, g, b):
            y, _, _ = layers._bn_train_norm(c, g, b)
            return c + 0.0 * jnp.mean(y)

        def bn_fwd_bwd(c, i, dy, g, b):
            def f(xx):
                y, m_, v_ = layers._bn_train_norm(xx, g, b)
                return y
            y, vjp = jax.vjp(f, c)
            (dx,) = vjp(dy)
            return c + 0.0 * (jnp.mean(y) + jnp.mean(dx))

        act = jnp.asarray(rng.normal(size=(B, H, H, Cout)), dtype)
        abytes = B * H * H * Cout * esize
        # fwd: read x twice (centered stats), write y = 3 passes.
        # bwd: the dx formula depends on full-batch sums, so the minimum
        # is pass 1 read (xhat, dy) + pass 2 read (xhat, dy) + write dx
        # = 5 activation passes (matching the bytes model above).
        est_bn_f = 3 * abytes / V5E_HBM_BYTES * 1e3
        est_bn_b = 5 * abytes / V5E_HBM_BYTES * 1e3
        t_f = bench_body(bn_fwd, act, est_bn_f, dy, bn_p["gamma"],
                         bn_p["beta"])
        t_fb = bench_body(bn_fwd_bwd, act, est_bn_f + est_bn_b, dy,
                          bn_p["gamma"], bn_p["beta"])
        m, r = report(f"bn{si} ({Cout}ch @{H}) fwd", t_f,
                      0, 3 * abytes)
        totals["measured"] += m
        totals["roof"] += r
        m, r = report(f"bn{si} ({Cout}ch @{H}) bwd", t_fb - t_f,
                      0, 5 * abytes)
        totals["measured"] += m
        totals["roof"] += r

        if si in POOL_AFTER:
            def pool_fwd(c, i):
                y = layers.maxpool2x2(c)
                return c + 0.0 * jnp.mean(y)

            def pool_fwd_bwd(c, i, dyp):
                y, vjp = jax.vjp(layers.maxpool2x2, c)
                (dx,) = vjp(dyp)
                return c + 0.0 * (jnp.mean(y) + jnp.mean(dx))

            dyp = jnp.asarray(
                rng.normal(size=(B, H // 2, H // 2, Cout)), dtype)
            est_p = 1.25 * abytes / V5E_HBM_BYTES * 1e3
            t_f = bench_body(pool_fwd, act, est_p)
            t_fb = bench_body(pool_fwd_bwd, act, 3 * est_p, dyp)
            m, r = report(f"pool{si} ({Cout}ch @{H}) fwd", t_f,
                          0, abytes + abytes // 4)
            totals["measured"] += m
            totals["roof"] += r
            m, r = report(f"pool{si} ({Cout}ch @{H}) bwd", t_fb - t_f,
                          0, 2 * abytes + abytes // 4)
            totals["measured"] += m
            totals["roof"] += r

    print(json.dumps({
        "stage": "TOTAL (conv+bn+pool, fwd+bwd)",
        "measured_ms": round(totals["measured"], 3),
        "roofline_ms": round(totals["roof"], 3),
        "pct_of_roofline": round(
            100 * totals["roof"] / totals["measured"], 1),
        "batch": B, "precision": args.precision}))


if __name__ == "__main__":
    main()
