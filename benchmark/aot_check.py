"""Rehearsal 3: compile each cell's programs at the real sizes for a v5e
that is described, not attached, and print `memory_analysis()`.

    JAX_PLATFORMS=cpu python3 benchmark/aot_check.py [cell ...]

Compiles the program's scanned train window (with the ring, as the trainer
builds it), its eval window, and the plain reference's per-shard gradient.
Nothing runs: this says what fits and what the compiler refuses, never a
time.  The bytes go into PERF.md section 4.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import manifest as mf
    from benchmark.reference import common as ref
    from cs744_ddp_tpu import models
    from cs744_ddp_tpu.obs import ringbuf
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.parallel import get_strategy
    from cs744_ddp_tpu.train import step as steplib

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    manifest = mf.load()
    gib = 2.0 ** 30
    for w in manifest["workloads"]:
        if argv and w["name"] not in argv:
            continue
        config = mf.load_config(manifest, w["config"])
        traffic = mf.load_traffic(w["traffic"])
        chips = w["chips"]
        mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
        rep = NamedSharding(mesh, P())
        ep = NamedSharding(mesh, P(None, "data"))
        b = config["per_chip_batch"] * chips
        n = config["train_images_per_chip"] * chips
        nb = n // b
        init_fn, apply_fn = models.get_model(config["model"])
        strat = get_strategy(traffic["strategy"])
        opt = config["optimizer"]
        cfg = sgd.SGDConfig(lr=config["lr"], momentum=opt["momentum"],
                            weight_decay=opt["weight_decay"])
        state = jax.eval_shape(lambda k: steplib.init_train_state(
            init_fn, k, strat, chips), jax.random.PRNGKey(0))
        sds = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
        state = jax.tree.map(lambda a: sds(a, rep), state)
        ring = (jax.ShapeDtypeStruct((ringbuf.DEFAULT_CAPACITY,
                                      ringbuf.N_METRICS), jnp.float32,
                                     sharding=rep),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        imgs = jax.ShapeDtypeStruct((nb, b, 32, 32, 3), jnp.uint8, sharding=ep)
        labs = jax.ShapeDtypeStruct((nb, b), jnp.int32, sharding=ep)
        start = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        window = steplib.make_train_window(apply_fn, strat, mesh, cfg,
                                           augment=True, metrics_ring=True)
        for wlen in sorted({min(20, nb), nb % 20 or min(20, nb), 1}):
            c = window.lower(state, ring, key, imgs, labs, start,
                             jax.ShapeDtypeStruct((wlen,), jnp.int8,
                                                  sharding=rep)).compile()
            m = c.memory_analysis()
            print(f"{w['name']}: train window W={wlen}: temp "
                  f"{m.temp_size_in_bytes / gib:.2f} GiB, args "
                  f"{m.argument_size_in_bytes / gib:.2f} GiB, out "
                  f"{m.output_size_in_bytes / gib:.2f} GiB (per device)",
                  flush=True)
        # the plain reference's per-shard gradient, on one device
        mod = mf.load_module_from_path(
            mf.reference_path(manifest, w["config"]), "aot_reference")
        rinit, rapply = mod.make(config)
        one = jax.sharding.SingleDeviceSharding(topo.devices[0])
        params, bn = jax.eval_shape(rinit, jax.random.PRNGKey(0))
        params, bn = jax.tree.map(lambda a: sds(a, one), (params, bn))
        pb = config["per_chip_batch"]

        def shard_grad(params, bn, key, images, labels):
            def loss_fn(p):
                logits, new_bn = rapply(p, bn, ref.augment(key, images), True)
                return ref.cross_entropy_sum(logits, labels) / pb, new_bn
            return jax.value_and_grad(loss_fn, has_aux=True)(params)
        c = jax.jit(shard_grad).lower(
            params, bn, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
            jax.ShapeDtypeStruct((pb, 32, 32, 3), jnp.uint8, sharding=one),
            jax.ShapeDtypeStruct((pb,), jnp.int32, sharding=one)).compile()
        m = c.memory_analysis()
        print(f"{w['name']}: reference shard gradient at {pb}: temp "
              f"{m.temp_size_in_bytes / gib:.2f} GiB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
