"""Rehearsal 3 for a `train-tokens-latent` cell (`aot_check_causal.py` knows
the hybrid decoder): compile the cell's programs at the real sizes for a
v5e that is described, not attached, and print `memory_analysis()`.

    JAX_PLATFORMS=cpu python3 benchmark/aot_check_latent.py [cell] \
        [--reference]

Compiles the program's scanned train window at the epoch's length and at
length 1 (the first three steps), its eval window, and with `--reference`
the plain reference's per-sequence gradient.  The model (the module
`models.DECODERS` names for the configuration's `model`) is built with its
Pallas kernels on.  Nothing runs: this says what fits and what the compiler
refuses, never a time.
"""

import importlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import manifest as mf
    from benchmark.drivers import train_tokens_latent as ttl
    from cs744_ddp_tpu import models
    from cs744_ddp_tpu.obs import ringbuf
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.parallel import get_strategy
    from cs744_ddp_tpu.train import step as steplib

    jax.config.update("jax_enable_compilation_cache", False)
    with_reference = "--reference" in argv
    cells = [a for a in argv if not a.startswith("--")]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    manifest = mf.load()
    gib = 2.0 ** 30
    for w in manifest["workloads"]:
        traffic = mf.load_traffic(w["traffic"])
        if traffic["kind"] != "train-tokens-latent" or \
                (cells and w["name"] not in cells):
            continue
        config = mf.load_config(manifest, w["config"])
        chips = w["chips"]
        mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
        rep = NamedSharding(mesh, P())
        ep = NamedSharding(mesh, P(None, "data"))
        b = config["per_chip_batch"] * chips
        nb = config["steps_per_epoch"]
        module = importlib.import_module(
            "cs744_ddp_tpu.models." + models.DECODERS[config["model"]][0])
        shape = module.Shape()._replace(**ttl.share(config, traffic))
        init_fn, apply_fn = module.make(shape, kernels=True)
        strat = get_strategy(traffic["strategy"])
        opt = config["optimizer"]
        cfg = sgd.SGDConfig(lr=config["lr"], momentum=opt["momentum"],
                            weight_decay=opt["weight_decay"])
        state = jax.eval_shape(lambda k: steplib.init_train_state(
            init_fn, k, strat, chips), jax.random.PRNGKey(0))
        sds = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
        state = jax.tree.map(lambda a: sds(a, rep), state)
        n_params = sum(int(np.prod(a.shape))
                       for a in jax.tree.leaves(state.params))
        print(f"{w['name']}: {n_params / 1e6:.1f} M parameters, "
              f"{12 * n_params / 1e9:.2f} GB of weight + gradient + momentum",
              flush=True)
        width = ringbuf.N_METRICS + len(apply_fn.objective.extras)
        ring = (jax.ShapeDtypeStruct((ringbuf.DEFAULT_CAPACITY, width),
                                     jnp.float32, sharding=rep),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        toks = jax.ShapeDtypeStruct((nb, b, config["seq_len"]), jnp.int32,
                                    sharding=ep)
        labs = jax.ShapeDtypeStruct((nb, b), jnp.int32, sharding=ep)
        start = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        window = steplib.make_train_window(apply_fn, strat, mesh, cfg,
                                           augment=True, metrics_ring=True)
        for wlen in (nb, 1):
            t0 = time.time()
            c = window.lower(state, ring, key, toks, labs, start,
                             jax.ShapeDtypeStruct((wlen,), jnp.int8,
                                                  sharding=rep)).compile()
            m = c.memory_analysis()
            print(f"{w['name']}: train window W={wlen}: temp "
                  f"{m.temp_size_in_bytes / gib:.2f} GiB, args "
                  f"{m.argument_size_in_bytes / gib:.2f} GiB, out "
                  f"{m.output_size_in_bytes / gib:.2f} GiB, alias "
                  f"{m.alias_size_in_bytes / gib:.2f} GiB (per device); "
                  f"compiled in {time.time() - t0:.0f} s", flush=True)
        held = jax.ShapeDtypeStruct(
            (1, config["heldout_sequences"] * chips, config["seq_len"]),
            jnp.int32, sharding=ep)
        held_l = jax.ShapeDtypeStruct(
            (1, config["heldout_sequences"] * chips), jnp.int32, sharding=ep)
        c = steplib.make_eval_window(apply_fn, mesh).lower(
            state, held, held_l).compile()
        m = c.memory_analysis()
        print(f"{w['name']}: eval window: temp "
              f"{m.temp_size_in_bytes / gib:.2f} GiB", flush=True)
        if not with_reference:
            continue
        follow = mf.load_module_from_path(
            mf.reference_path(manifest, w["config"]), "aot_reference").follow
        ref = sys.modules[follow.__module__]
        one = jax.sharding.SingleDeviceSharding(topo.devices[0])
        z = ref.sizes(config)
        params = jax.eval_shape(lambda k: ref.init(config, k),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(lambda a: sds(a, one), params)
        tokens = jax.ShapeDtypeStruct((z["L"],), jnp.int32, sharding=one)

        def seq_grad(params, tokens):
            return jax.value_and_grad(lambda p: ref.sequence_loss(
                p, tokens, z)[0])(params)
        t0 = time.time()
        c = jax.jit(seq_grad).lower(params, tokens).compile()
        m = c.memory_analysis()
        print(f"{w['name']}: reference sequence gradient: temp "
              f"{m.temp_size_in_bytes / gib:.2f} GiB, out "
              f"{m.output_size_in_bytes / gib:.2f} GiB; compiled in "
              f"{time.time() - t0:.0f} s", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
