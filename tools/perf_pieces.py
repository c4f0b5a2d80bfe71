"""Time isolated pieces of the train step to find the fixed per-step cost.

Builder's tool (see tools/perf_attribution.py).  A dispatch's fixed host
cost is not small next to a sub-millisecond piece, so each piece is
measured INSIDE one compiled program: ``lax.scan`` chains K iterations of
the piece (outputs
feed the carry so nothing is DCE'd), and the per-iteration time is the
fenced dispatch time / K, with the scan's own overhead calibrated out by a
null scan.  Headline config: VGG-11, f32, batch 256, one chip.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 100     # scan iterations per dispatch
R = 3       # dispatches (first excluded as warmup)


def bench(make_scanned, *args):
    import jax
    import numpy as np
    fn = jax.jit(make_scanned)
    out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0])          # compile+warm fence
    times = []
    for _ in range(R):
        t0 = time.time()
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0])      # value-fetch fence
        times.append(time.time() - t0)
    return min(times) / K * 1e3


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax

    from cs744_ddp_tpu.data import augment as aug
    from cs744_ddp_tpu.models import vgg
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.ops.loss import cross_entropy
    from cs744_ddp_tpu.utils.compcache import \
        enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    B = 256
    params, bn_state = vgg.init(jax.random.PRNGKey(0), "VGG11")
    opt = sgd.init(params)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.integers(0, 256, (B, 32, 32, 3)), jnp.uint8)
    labels = jnp.asarray(rng.integers(0, 10, (B,)), jnp.int32)
    x = jnp.asarray(rng.normal(size=(B, 32, 32, 3)), jnp.float32)
    key = jax.random.PRNGKey(1)

    def scan_of(body, carry):
        def scanned(carry, *consts):
            def one(c, i):
                return body(c, i, *consts), ()
            c, _ = lax.scan(one, carry, jnp.arange(K))
            return c
        return scanned, carry

    def null_body(c, i):
        return c + 1.0

    def full_body(carry, i, images, labels):
        params, bn_state, opt = carry
        k = jax.random.fold_in(key, i)
        xx = aug.augment(k, images)

        def loss_fn(p):
            logits, nb = vgg.apply(p, bn_state, xx, train=True)
            return cross_entropy(logits, labels), nb

        (loss, nb), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        np_, no = sgd.update(params, grads, opt, sgd.SGDConfig())
        return (np_, nb, no)

    def fwd_bwd_body(carry, i, xx, labels):
        params, bn_state = carry

        def loss_fn(p):
            logits, nb = vgg.apply(p, bn_state, xx, train=True)
            return cross_entropy(logits, labels), nb

        (loss, nb), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        # feed a scaled grad back so the chain is sequential, magnitude ~0
        params = jax.tree.map(lambda p, g: p + 0.0 * g, params, grads)
        return (params, nb)

    def fwd_body(carry, i, xx, labels):
        params, bn_state = carry
        logits, nb = vgg.apply(params, bn_state, xx, train=True)
        return (jax.tree.map(
            lambda p: p + 0.0 * jnp.sum(logits), params), nb)

    def sgd_body(carry, i, grads):
        params, opt = carry
        np_, no = sgd.update(params, grads, opt, sgd.SGDConfig())
        return (np_, no)

    def aug_body(carry, i, images):
        k = jax.random.fold_in(key, i)
        xx = aug.augment(k, images)
        return carry + jnp.sum(xx)

    grads = jax.jit(lambda p, s, xx, y: jax.grad(
        lambda pp: cross_entropy(vgg.apply(pp, s, xx, train=True)[0], y))(p))(
        params, bn_state, x, labels)
    jax.block_until_ready(grads)

    null_ms = bench(*scan_of(null_body, jnp.float32(0.0)))
    print(f"null scan        {null_ms:7.3f} ms/iter")

    fn, carry = scan_of(full_body, (params, bn_state, opt))
    print(f"full step        {bench(fn, carry, images, labels) - null_ms:7.3f} ms/iter")
    fn, carry = scan_of(fwd_bwd_body, (params, bn_state))
    print(f"fwd+bwd          {bench(fn, carry, x, labels) - null_ms:7.3f} ms/iter")
    fn, carry = scan_of(fwd_body, (params, bn_state))
    print(f"fwd (train BN)   {bench(fn, carry, x, labels) - null_ms:7.3f} ms/iter")
    fn, carry = scan_of(sgd_body, (params, opt))
    print(f"sgd update       {bench(fn, carry, grads) - null_ms:7.3f} ms/iter")
    fn, carry = scan_of(aug_body, jnp.float32(0.0))
    print(f"augment          {bench(fn, carry, images) - null_ms:7.3f} ms/iter")


if __name__ == "__main__":
    main()
