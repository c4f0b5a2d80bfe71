"""One hyper-connection alone (ops/hyper.py `connect`), at a sublayer of one
sequence of the latent decoder's cell (4 streams x 4096 x 3584, Phi
[14336, 24] by default) around a stand-in sublayer of one product (y = h *
w, a gain a channel): ms forward, ms forward + backward, each kernel's own
ms, and the error of X' and of the five gradients (x, the sublayer's w,
phi, bias, alpha) against the position-by-position loop in float64 on the
host, for the jax.numpy form and for the Pallas kernels over the swept
tiles of positions and strips.  Every error is the largest absolute gap
over the largest entry of the loop's array.

    chiprun -- python3 tools/mhc_alone.py            # the sweep, on the chip
    JAX_PLATFORMS=cpu python3 tools/mhc_alone.py --positions 256 \
        --width 128 --tiles 128 --strips 8 --repeats 1
                                                     # a rehearsal

Off the TPU the kernels run in Pallas' interpreter and the times mean
nothing; the record says which device it ran on.  Output: one JSON line a
row, and all rows in `--out` (default chiprun_out/pr36/mhc_alone.json).
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
import numpy as np                                          # noqa: E402
from jax import lax                                         # noqa: E402

from cs744_ddp_tpu.models import xing4                      # noqa: E402
from cs744_ddp_tpu.ops import hyper                         # noqa: E402

HC = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0))
NAMES = ("x", "w", "phi", "bias", "alpha")


def inputs(n, positions, width, seed):
    """Streams of unit scale that differ, a mixer as the model starts it
    (`xing4.init_params`: Phi normal 0.002, alpha 0.01, `hc_bias`) with its
    scales raised to 0.3 so that every coefficient moves with the
    position, a gain near 1, and the cotangent of X'."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = n * n + 2 * n
    x = jax.random.normal(ks[0], (n, positions, width))
    p = {"phi": 10 * xing4.HC_PHI_STD * jax.random.normal(
             ks[1], (n * width, k)),
         "bias": xing4.hc_bias(n), "alpha": jnp.full((3,), 0.3)}
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (width,))
    return (x, w, p), jax.random.normal(ks[3], x.shape)


def by_position(x, w, p, co):
    """(X', the gradients of sum(X' co) in `NAMES`' order), a position at
    a time in float64 on the host: numpy arrays."""
    n = x.shape[0]
    lo, hi = HC["clamp"]

    def position(xt, w, phi, bias, alpha):                  # xt [n, C]
        u = xt.reshape(-1)
        m = (u / jnp.sqrt(jnp.mean(u * u) + HC["eps"])) @ phi
        pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n])
        post = 2 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n])
        mat = jnp.exp(jnp.clip(alpha[2] * m[2 * n:] + bias[2 * n:], lo, hi)
                      ).reshape(n, n)
        for _ in range(HC["iters"]):
            mat = mat / (mat.sum(0, keepdims=True) + HC["eps"])
            mat = mat / (mat.sum(1, keepdims=True) + HC["eps"])
        y = (pre @ xt) * w
        return mat @ xt + post[:, None] * y[None, :]

    def turn(sums, at):
        xt, ct = at
        out, pull = jax.vjp(position, xt, w, p["phi"], p["bias"], p["alpha"])
        dx, *rest = pull(ct)
        return tuple(a + b for a, b in zip(sums, rest)), (out, dx)
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        f64 = lambda a: jnp.asarray(np.asarray(a), jnp.float64)
        x, w, co = f64(x), f64(w), f64(co)
        p = {name: f64(a) for name, a in p.items()}
        zero = tuple(jnp.zeros_like(a) for a in
                     (w, p["phi"], p["bias"], p["alpha"]))
        sums, (out, dx) = jax.jit(lambda x, co: lax.scan(
            turn, zero, (jnp.moveaxis(x, 1, 0), jnp.moveaxis(co, 1, 0))))(
                x, co)
        positions_first = lambda a: np.moveaxis(np.asarray(a), 0, 1)
        return positions_first(out), \
            (positions_first(dx),) + tuple(np.asarray(a) for a in sums)


def timed(f, x, repeats):
    out = jax.block_until_ready(f(*x))          # compiles
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = f(*x)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / repeats, out


def gap(got, want):
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def kernels_alone(st, x, w, p, co, repeats):
    """{kernel: ms} of the four kernels, each jitted alone on the arrays
    the others leave it; a kernel Mosaic refuses reads its error."""
    n, _, width = x.shape
    small = hyper._small(p, n, width)
    out = {}

    def one(name, f, *args):
        try:
            out[name], got = timed(jax.jit(f), args, repeats)
            return got
        except Exception as e:
            out[name] = repr(e)[:300]
    kept = one("mhc_read_fwd", lambda x, *s: hyper._run_read(st, x, *s),
               x, *small)
    if kept is None:
        return out
    h, rows, cols = kept
    y = h * w
    one("mhc_write_fwd", lambda *a: hyper._run_write(st, *a), x, y, cols)
    one("mhc_write_bwd", lambda *a: hyper._run_dy(st, *a), co, cols)
    one("mhc_read_bwd", lambda *a: hyper._run_back(st, *a),
        co, x, y, h, cols, rows, *small)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--positions", type=int, default=4096)
    ap.add_argument("--width", type=int, default=3584)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--strips", default="8,16,32")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "pr36", "mhc_alone.json"))
    args = ap.parse_args(argv)
    ints = lambda s: [int(a) for a in s.split(",") if a]
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    (x, w, p), co = inputs(args.streams, args.positions, args.width,
                           args.seed)
    want_o, want_g = by_position(x, w, p, co)

    def connection(kernel):
        return lambda x, w, p: hyper.connect(
            lambda h: (h * w, ()), x, p, kernels=kernel,
            interpret=kernel and not on_tpu, **HC)[0]
    variants = [dict(path="jax.numpy")]
    variants += [dict(path="kernel", tile=t, strip=s)
                 for t in ints(args.tiles) for s in ints(args.strips)]
    rows = []
    for row in variants:
        kernel = row["path"] == "kernel"
        if kernel:
            hyper.KERNEL_TILE, hyper._STRIP = row["tile"], row["strip"]
        f = connection(kernel)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * co),
                                 argnums=(0, 1, 2)))
        row.update(device=device.device_kind, platform=device.platform,
                   streams=args.streams, positions=args.positions,
                   width=args.width)
        try:
            if kernel:
                st = hyper._Static(row["tile"], HC["iters"], HC["eps"],
                                   HC["clamp"], not on_tpu)
                row["kernel_ms"] = kernels_alone(st, x, w, p, co,
                                                 args.repeats)
            row["forward_ms"], o = timed(jax.jit(f), (x, w, p), args.repeats)
            row["forward_backward_ms"], (gx, gw, gp) = timed(
                grads, (x, w, p), args.repeats)
            row["output_error"] = gap(o, want_o)
            got = (gx, gw, gp["phi"], gp["bias"], gp["alpha"])
            row["gradient_error"] = {
                name: gap(a, b) for name, a, b in zip(NAMES, got, want_g)}
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
