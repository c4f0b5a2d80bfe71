"""The gated delta rule alone (ops/gdn.py `delta_rule`), at one layer of one
sequence of the hybrid decoder's cell (32 heads on 16 key heads x 8192 x
128 by default, positions first as the mixer hands them over):
ms forward, ms forward + backward, and the error of the output and of the
five gradients against the float32 token-by-token loop (the benchmark
reference's `recurrence`), for the jax.numpy form and for the Pallas
kernels over the swept chunks and heads a grid step.  Every error is the
largest absolute gap over the largest entry of the loop's array.

    chiprun -- python3 tools/gdn_alone.py            # the sweep, on the chip
    JAX_PLATFORMS=cpu python3 tools/gdn_alone.py --heads 4 --key-heads 2 \
        --length 256 --kernel-chunks 64 --kernel-heads 2 --repeats 1
                                                     # a rehearsal

Off the TPU the kernels run in Pallas' interpreter and the times mean
nothing; the record says which device it ran on.  Output: one JSON line a
row, and all rows in `--out` (default chiprun_out/pr34/gdn_alone.json).
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
from jax import lax                                         # noqa: E402

from benchmark.reference import hybrid_causal as ref       # noqa: E402
from cs744_ddp_tpu.ops import gdn                           # noqa: E402

NAMES = ("q", "k", "v", "g", "beta")


def inputs(heads, key_heads, length, dim, seed):
    """What the mixer hands the recurrence, positions first: unit keys,
    queries of norm dk^-1/2, decays from `A_log` = log U(0.001, 16) and a
    softplus."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = unit(jax.random.normal(ks[0], (length, key_heads, dim))) * dim ** -0.5
    k = unit(jax.random.normal(ks[1], (length, key_heads, dim)))
    v = jax.random.normal(ks[2], (length, heads, dim))
    A = jax.random.uniform(ks[3], (heads,), minval=1e-3, maxval=16.0)
    g = -A * jax.nn.softplus(
        1.0 + 0.5 * jax.random.normal(ks[4], (length, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (length, heads)))
    cotangent = jax.random.normal(ks[6], (length, heads, dim))
    return (q, k, v, g, beta), cotangent


def by_token(q, k, v, g, beta):
    group = v.shape[1] // q.shape[1]
    return ref.recurrence(jnp.repeat(q, group, 1), jnp.repeat(k, group, 1),
                          v, g, beta)


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
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--key-heads", type=int, default=16)
    ap.add_argument("--length", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--kernel-chunks", default="64,128")
    ap.add_argument("--kernel-heads", default="1,2,4,8")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "pr34", "gdn_alone.json"))
    args = ap.parse_args(argv)
    ints = lambda s: [int(a) for a in s.split(",") if a]
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    x, co = inputs(args.heads, args.key_heads, args.length, args.dim,
                   args.seed)
    grads = lambda f: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * co), argnums=tuple(range(5))))
    want_o = jax.jit(by_token)(*x)
    want_g = grads(by_token)(*x)
    jax.block_until_ready((want_o, want_g))

    variants = [dict(path="jax.numpy", chunk=gdn.CHUNK, segment=gdn.SEGMENT)]
    variants += [dict(path="kernel", chunk=c, heads_a_step=h)
                 for c in ints(args.kernel_chunks)
                 for h in ints(args.kernel_heads)]
    rows = []
    for row in variants:
        kernel = row["path"] == "kernel"
        if kernel:
            gdn.KERNEL_CHUNK, gdn.KERNEL_HEADS = row["chunk"], \
                row["heads_a_step"]
        f = lambda *a: gdn.delta_rule(*a, kernels=kernel,
                                      interpret=kernel and not on_tpu)
        row.update(device=device.device_kind, platform=device.platform,
                   heads=args.heads, key_heads=args.key_heads,
                   length=args.length, dim=args.dim)
        try:
            row["forward_ms"], o = timed(jax.jit(f), x, args.repeats)
            row["forward_backward_ms"], g = timed(grads(f), x, args.repeats)
            row["output_error"] = gap(o, want_o)
            row["gradient_error"] = {
                n: gap(a, b) for n, a, b in zip(NAMES, g, want_g)}
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
