"""Attribute the backward pass: full VGG vs BN-free vs per-stage truncation.

Builder's tool.  Scanned-K measurement (see perf_pieces.py) of
value_and_grad over model variants at the headline config, to locate the
fwd+bwd time (measured ~2.7 ms/iter vs ~0.53 ms fwd-only).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 100


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax

    from cs744_ddp_tpu.models import vgg, layers
    from cs744_ddp_tpu.ops.loss import cross_entropy
    from cs744_ddp_tpu.utils.compcache import \
        enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    B = 256
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, (B,)), jnp.int32)

    def bench_scan(body, carry, *consts):
        def scanned(carry, *cs):
            def one(c, i):
                return body(c, i, *cs), ()
            c, _ = lax.scan(one, carry, jnp.arange(K))
            return c
        fn = jax.jit(scanned)
        out = fn(carry, *consts)
        np.asarray(jax.tree.leaves(out)[0])
        ts = []
        for _ in range(3):
            t0 = time.time()
            out = fn(carry, *consts)
            np.asarray(jax.tree.leaves(out)[0])
            ts.append(time.time() - t0)
        return min(ts) / K * 1e3

    null = bench_scan(lambda c, i: c + 1.0, jnp.float32(0))
    print(f"null               {null:7.3f} ms")

    def apply_nobn(params, state, xx, *, train):
        # VGG-11 with BN replaced by identity (same convs/pools/fc).
        cfg = vgg.CFG["VGG11"]
        i = 0
        h = xx
        for c in cfg:
            if c == "M":
                h = layers.maxpool2x2(h)
            else:
                h = layers.conv2d_apply(params["conv"][i], h)
                h = layers.relu(h)
                i += 1
        h = h.reshape(h.shape[0], -1)
        return layers.linear_apply(params["fc1"], h), state

    variants = {}
    params, bn_state = vgg.init(jax.random.PRNGKey(0), "VGG11")
    variants["full vgg11"] = (vgg.apply, params, bn_state)
    variants["no-BN vgg11"] = (apply_nobn, params, bn_state)

    for name, (apply_fn, p0, s0) in variants.items():
        def gbody(carry, i, xx, labels, apply_fn=apply_fn, s0=s0):
            p = carry

            def loss_fn(pp):
                logits, _ = apply_fn(pp, s0, xx, train=True)
                return cross_entropy(logits, labels)

            g = jax.grad(loss_fn)(p)
            return jax.tree.map(lambda a, b: a + 0.0 * b, p, g)

        t = bench_scan(gbody, p0, x, labels) - null
        print(f"grad {name:14s} {t:7.3f} ms")

        def fbody(carry, i, xx, labels, apply_fn=apply_fn, s0=s0):
            p = carry
            logits, _ = apply_fn(p, s0, xx, train=True)
            return jax.tree.map(
                lambda a: a + 0.0 * jnp.sum(logits), p)

        t = bench_scan(fbody, p0, x, labels) - null
        print(f"fwd  {name:14s} {t:7.3f} ms")


if __name__ == "__main__":
    main()
