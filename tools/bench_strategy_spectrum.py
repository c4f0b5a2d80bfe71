"""Measure the three gradient-sync tiers' wall-clock cost spectrum.

The reference exists to show gather/scatter-via-root (Part 2a) is slower
than per-param all-reduce (Part 2b) is slower than bucketed-fused DDP
(Part 3).  On one TPU chip the collectives are trivial (world=1) and on the
CPU unit-test mesh VGG's compute drowns the comm — so this tool measures the
tiers where their *communication* patterns dominate: a parameter-heavy,
compute-light MLP (the gradient pytree is ~50 MB across many leaves) on an
8-virtual-device CPU mesh with a tiny per-device batch.  There the per-step
cost is essentially the collective pattern itself:

  * gather:    2 sequential collectives per leaf, world x gather traffic
  * allreduce: 1 all-reduce per leaf, barrier-chained
  * ddp:       1 fused variadic all-reduce per ~25 MB bucket

Run:  python tools/bench_strategy_spectrum.py [--steps 10]
Results are recorded in BASELINE.md ("Strategy cost spectrum").
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEVICES = 8
# Deep and narrow: ~17M params (~66 MB f32) spread over 122 leaves — the
# shape of the reference's point.  VGG-11+BN has 34 grad tensors; what DDP's
# bucketing buys is FEWER COLLECTIVE LAUNCHES over many tensors, so the
# spectrum needs a many-leaf pytree to be visible in wall-clock.
LAYERS = [3072] + [512] * 60 + [10]


def mlp_init(key):
    import jax
    import jax.numpy as jnp
    params = {"w": [], "b": []}
    for din, dout in zip(LAYERS[:-1], LAYERS[1:]):
        key, sub = jax.random.split(key)
        params["w"].append(
            jax.random.normal(sub, (din, dout), jnp.float32) / jnp.sqrt(din))
        params["b"].append(jnp.zeros((dout,), jnp.float32))
    return params, {}


def mlp_apply(params, state, x, *, train):
    import jax.numpy as jnp
    del train
    x = x.reshape(x.shape[0], -1)
    for i, (w, b) in enumerate(zip(params["w"], params["b"])):
        x = x @ w + b
        if i < len(params["w"]) - 1:
            x = jnp.maximum(x, 0)
    return x, state


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch-per-device", type=int, default=1)
    args = p.parse_args(argv)

    import __graft_entry__ as ge
    ge._ensure_devices(N_DEVICES, "cpu")

    import numpy as np
    import jax

    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.parallel import get_strategy, mesh as meshlib
    from cs744_ddp_tpu.train import step as steplib

    mesh = meshlib.make_mesh(N_DEVICES)
    state = steplib.init_train_state(mlp_init, jax.random.PRNGKey(0))
    state = meshlib.put_global_tree(state, meshlib.replicated(mesh))

    batch = args.batch_per_device * N_DEVICES
    rng = np.random.default_rng(0)
    images = jax.device_put(
        rng.integers(0, 256, (batch, 32, 32, 3)).astype(np.uint8),
        meshlib.batch_sharding(mesh))
    labels = jax.device_put(
        rng.integers(0, 10, (batch,)).astype(np.int32),
        meshlib.batch_sharding(mesh))
    key = jax.random.PRNGKey(1)

    result = {}
    for name in ("gather", "allreduce", "ddp"):
        step = steplib.make_train_step(
            mlp_apply, get_strategy(name), mesh, sgd.SGDConfig(),
            augment=False)
        s, loss = step(state, key, images, labels)   # compile + warmup
        float(loss)
        t0 = time.time()
        for _ in range(args.steps):
            s, loss = step(s, key, images, labels)
        float(loss)                                  # value-fetch fence
        per_step_ms = (time.time() - t0) / args.steps * 1e3
        result[name] = round(per_step_ms, 2)
        print(f"{name:10s} {per_step_ms:9.2f} ms/step", file=sys.stderr)

    nleaves = len(jax.tree.leaves(state.params))
    print(json.dumps({"config": f"mlp-60x512-{nleaves}leaves/"
                                f"world{N_DEVICES}/batch{batch}/cpu-mesh",
                      "ms_per_step": result}))


if __name__ == "__main__":
    main()
