"""The linear mixer's front alone (ops/gdn.py `front`: the causal convolution,
`silu`, the split into q, k, v, the l2 norms of the key heads), at one
layer of one sequence of the hybrid decoder's cell (the projection [8192,
12288] -> q, k [8192, 2048], v [8192, 4096] by default): ms forward and ms
of what the layer's backward pass asks of it (the forward recomputed, its
outputs kept for the recurrence, and the pull of dq, dk, dv back to the
projection and the weight), for the jax.numpy form and for the Pallas
kernels at each swept shape (positions a grid step : lanes a turn of the
loop : strips of 8 positions a turn), with GB/s on the bytes the front must
move:

    forward   read the 2 kd + vd convolved channels, write q, k, v   2 A
    backward  read dq, dk, dv and the input, write the input's       3 A
              cotangent
    a layer   forward + (forward + backward), the layer's checkpoint 7 A

(A = P x (2 kd + vd) x 4 bytes, 268 MB at the cell's widths; both forms also
write z's channels of the cotangent, zeros here: A / 2 more, not counted).
Errors of the kernels' q, k, v and of both cotangents against the jax.numpy
form: the largest absolute gap over the largest entry.

    chiprun -- python3 tools/gdn_front_alone.py          # on the chip
    JAX_PLATFORMS=cpu python3 tools/gdn_front_alone.py --length 256 \\
        --key-heads 2 --value-width 256 --kernels 64:256:2 --repeats 1
                                                         # a rehearsal

Off the TPU the kernels run in Pallas' interpreter and the times mean
nothing; the record says which device it ran on.  Output: one JSON line a
row, and all rows in `--out` (default chiprun_out/pr38/gdn_front_alone.json).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from cs744_ddp_tpu.analysis.costmodel import V5E_HBM_BYTES_PER_S  # noqa
from cs744_ddp_tpu.ops import gdn                           # noqa: E402


def timed(f, x, repeats):
    out = jax.block_until_ready(f(*x))          # compiles
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = f(*x)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / repeats, out


def gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--length", type=int, default=8192)
    ap.add_argument("--key-heads", type=int, default=16)
    ap.add_argument("--dk", type=int, default=128)
    ap.add_argument("--value-width", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--kernels", default="128:128:1,128:256:2,128:512:1,"
                    "128:256:4,256:256:2,64:256:2")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "pr38", "gdn_front_alone.json"))
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    kd, vd, P = args.key_heads * args.dk, args.value_width, args.length
    channels = 2 * kd + vd
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    # the projection of a normed stream by weights of std 0.02 over 2048
    # rows is about unit-normal; the taps as the model draws them
    x = jax.random.normal(ks[0], (P, channels + vd))
    w = 0.02 * jax.random.normal(ks[1], (4, channels))
    cots = tuple(jax.random.normal(k, s) for k, s in
                 zip(ks[2:], ((P, kd), (P, kd), (P, vd))))
    A = P * channels * 4

    rows, want = [], None
    variants = [dict(path="jax.numpy")] + [
        dict(path="kernel", **dict(zip(("tile", "lanes", "strips"),
                                       map(int, v.split(":")))))
        for v in args.kernels.split(",") if v]
    for row in variants:
        kernel = row["path"] == "kernel"
        if kernel:
            gdn.FRONT_TILE, gdn.FRONT_LANES, gdn.FRONT_STRIPS = \
                row["tile"], row["lanes"], row["strips"]
        front = lambda x, w: gdn.front(
            x, w, kd, args.dk, kernels=kernel,
            interpret=kernel and not on_tpu)[:3]

        def recomputed_and_back(x, w, cots):
            out, pull = jax.vjp(front, x, w)
            return out, pull(cots)
        row.update(device=device.device_kind, platform=device.platform,
                   length=P, key_width=kd, dk=args.dk, value_width=vd)
        try:
            fwd_ms, out = timed(jax.jit(front), (x, w), args.repeats)
            both_ms, (_, grads) = timed(jax.jit(recomputed_and_back),
                                        (x, w, cots), args.repeats)
            row.update(
                forward_ms=fwd_ms, forward_backward_ms=both_ms,
                layer_ms=fwd_ms + both_ms,
                forward_gb_s=2 * A / fwd_ms / 1e6,
                backward_gb_s=3 * A / max(both_ms - fwd_ms, 1e-9) / 1e6,
                layer_gb_s=7 * A / (fwd_ms + both_ms) / 1e6,
                layer_share_of_peak=100.0 * 7 * A / V5E_HBM_BYTES_PER_S
                / ((fwd_ms + both_ms) / 1e3))
            if want is None:
                want = (out, grads)
            else:
                row["output_error"] = {
                    n: gap(a, b) for n, a, b in zip("qkv", out, want[0])}
                row["gradient_error"] = {
                    n: gap(a, b) for n, a, b in zip(("x", "w"), grads,
                                                     want[1])}
        except Exception as e:                  # a variant Mosaic refuses
            row["error"] = repr(e)[:400]
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
