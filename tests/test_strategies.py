"""Gradient-sync strategy tests on the 8-virtual-device CPU mesh.

Covers: mathematical equivalence of the three strategies (same averaged
gradient — the property the reference's Parts 2a/2b/3 rely on but never
test), bucketing round-trips, and the collective patterns in the lowered HLO.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from cs744_ddp_tpu.parallel import bucketing, strategies
from cs744_ddp_tpu.parallel.mesh import DATA_AXIS


def tree_of_grads(key, scale=1.0):
    ks = jax.random.split(key, 4)
    return {
        "conv": [{"w": jax.random.normal(ks[0], (3, 3, 8, 16)) * scale,
                  "b": jax.random.normal(ks[1], (16,)) * scale}],
        "fc": {"w": jax.random.normal(ks[2], (32, 10)) * scale,
               "b": jax.random.normal(ks[3], (10,)) * scale},
    }


def run_strategy(mesh, strategy, grads_per_device):
    """Apply a strategy to per-device gradient pytrees; return the synced
    (replicated) result.  grads leaves have a leading device axis."""
    f = shard_map(lambda g: strategy(
        jax.tree.map(lambda a: a[0], g), DATA_AXIS),
        mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P())
    return jax.jit(f)(grads_per_device)


@pytest.fixture
def per_device_grads(mesh8):
    n = mesh8.devices.size
    keys = jax.random.split(jax.random.PRNGKey(7), n)
    trees = [tree_of_grads(k) for k in keys]
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def test_all_strategies_compute_the_mean(mesh8, per_device_grads):
    expected = jax.tree.map(lambda a: jnp.mean(a, 0), per_device_grads)
    for name in ("gather", "allreduce", "ddp"):
        out = run_strategy(mesh8, strategies.get_strategy(name),
                           per_device_grads)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6,
                err_msg=f"strategy {name}"),
            out, expected)


def test_local_strategy_is_identity():
    grads = tree_of_grads(jax.random.PRNGKey(0))
    out = strategies.local(grads, DATA_AXIS)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b)), out, grads)


def test_bucketing_plan_partitions_all_leaves():
    grads = tree_of_grads(jax.random.PRNGKey(3))
    n_leaves = len(jax.tree.leaves(grads))
    for bucket_bytes in (64, 4096, bucketing.DEFAULT_BUCKET_BYTES):
        plan = bucketing.make_plan(grads, bucket_bytes)
        covered = sorted(i for b in plan.buckets for i in b)
        assert covered == list(range(n_leaves))  # exact partition


def test_bucketing_respects_size_bound_and_reverse_order():
    grads = {"a": jnp.zeros((1000,)), "b": jnp.zeros((1000,)),
             "c": jnp.zeros((1000,))}
    plan = bucketing.make_plan(grads, bucket_bytes=4500)  # fits 1 leaf + change
    # 4000-byte leaves, 4500-byte cap -> one leaf per bucket.
    assert plan.num_buckets == 3
    # Reverse registration order: leaf index 2 ("c") first, like DDP.
    assert plan.buckets[0] == (2,)


def test_strategy_collective_patterns_in_stablehlo(mesh8):
    """The tiers must stay observably distinct pre-optimization: the
    per-param tier is a barrier-CHAINED sequence of per-leaf all-reduces
    (Part 2b's blocking loop — leaves-1 barriers), while the ddp tier
    groups leaves into buckets with barriers only BETWEEN buckets
    (Part 3's in-order comm stream).  The compiled-level distinctness (one
    collective per leaf vs per bucket on the v5e-8 lowering) is asserted
    in tests/test_tpu_aot.py — the CPU backend here strips barriers and
    fuses both tiers (test_ddp_wallclock_not_slower_than_allreduce pins
    that convergence)."""
    grads = tree_of_grads(jax.random.PRNGKey(1))
    stacked = jax.tree.map(lambda a: a[None].repeat(8, 0), grads)

    def counts(strategy):
        f = shard_map(lambda g: strategy(
            jax.tree.map(lambda a: a[0], g), DATA_AXIS),
            mesh=mesh8, in_specs=(P(DATA_AXIS),), out_specs=P())
        hlo = jax.jit(f).lower(stacked).as_text()  # StableHLO MLIR
        return (len(re.findall(r"stablehlo\.all_reduce", hlo)),
                len(re.findall(r"stablehlo\.optimization_barrier", hlo)))

    n_ar, n_bar = counts(strategies.get_strategy("allreduce"))
    assert (n_ar, n_bar) == (4, 3)   # per leaf, sequentially chained

    n_ar, n_bar = counts(strategies.get_strategy("ddp"))
    assert (n_ar, n_bar) == (4, 0)   # all four leaves fit one 25MB bucket

    # Tiny buckets: one leaf per bucket -> chained like DDP's comm stream.
    n_ar, n_bar = counts(strategies.get_strategy("ddp", bucket_bytes=64))
    assert (n_ar, n_bar) == (4, 3)

    # gather_scatter: all-gather + all-reduce per leaf, chained.
    f = shard_map(lambda g: strategies.gather_scatter(
        jax.tree.map(lambda a: a[0], g), DATA_AXIS),
        mesh=mesh8, in_specs=(P(DATA_AXIS),), out_specs=P())
    hlo = jax.jit(f).lower(stacked).as_text()
    assert len(re.findall(r"stablehlo\.all_gather", hlo)) == 4
    assert len(re.findall(r"stablehlo\.all_reduce", hlo)) == 4
    assert len(re.findall(r"stablehlo\.optimization_barrier", hlo)) == 3


def test_compiled_step_reaches_ddp_grade_fusion(mesh8):
    """On the CPU BACKEND (which strips optimization barriers), the whole
    compiled train step must carry at most bucket-count all-reduces for
    BOTH the ddp and the per-param strategy: XLA's all-reduce combiner
    delivers DDP-grade fusion — the capability torch gets from DDP's C++
    reducer.  On TPU the barrier chains keep the tiers distinct instead
    (tests/test_tpu_aot.py); pre-optimization structure is pinned in
    test_strategy_collective_patterns_in_stablehlo."""
    if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
        pytest.skip("this jax's CPU backend keeps optimization barriers, so "
                    "the all-reduce combiner never sees a fusable chain; the "
                    "fusion capability is pinned on newer toolchains only")
    from tinynet import tiny_cnn

    import jax.numpy as jnp
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.train import step as steplib

    init_fn, apply_fn = tiny_cnn()
    state = steplib.init_train_state(init_fn, jax.random.PRNGKey(0))
    imgs = jnp.zeros((64, 32, 32, 3), jnp.uint8)
    labs = jnp.zeros((64,), jnp.int32)
    for name in ("allreduce", "ddp"):
        step = steplib.make_train_step(
            apply_fn, strategies.get_strategy(name), mesh8, sgd.SGDConfig(),
            augment=False)
        txt = step.lower(state, jax.random.PRNGKey(0), imgs, labs) \
                  .compile().as_text()
        n = len(re.findall(r" all-reduce\(", txt))
        assert 1 <= n <= 2, (name, n)  # 4 grad leaves -> <= 2 collectives


@pytest.mark.slow  # ~70s: ResNet-18 compile + timed steps on the CPU mesh
def test_ddp_wallclock_not_slower_than_allreduce(mesh8):
    """Part 3's capability claim, measured: the bucketed-fused tier must not
    lose to per-param all-reduce on a model with many parameter leaves
    (ResNet-18, ~60 leaves).  On this XLA version both compile to the same
    fused collective schedule, so this pins ddp step time <= allreduce
    step time as a wall-clock invariant (margin covers CI timer noise).

    The POSITIVE separation of all three tiers (gather > allreduce > ddp
    in ms/step) is measured where the collective patterns dominate —
    tools/bench_strategy_spectrum.py, a 122-leaf comm-bound model on this
    same 8-virtual-device mesh — and recorded in BASELINE.md ("Strategy
    cost spectrum"); this test only guards the non-regression direction."""
    import time

    import jax.numpy as jnp
    from cs744_ddp_tpu.models import resnet
    from cs744_ddp_tpu.ops import sgd
    from cs744_ddp_tpu.train import step as steplib

    init_fn, apply_fn = resnet.ResNet18()
    state = steplib.init_train_state(init_fn, jax.random.PRNGKey(0))
    imgs = jnp.zeros((32, 32, 32, 3), jnp.uint8)
    labs = jnp.zeros((32,), jnp.int32)

    # Compile and warm BOTH programs first, then INTERLEAVE the timed steps:
    # back-to-back A/B pairs cancel the load drift of a shared CI host that
    # sequential per-strategy timing is exposed to.
    steps, states = {}, {}
    for name in ("allreduce", "ddp"):
        step = steplib.make_train_step(
            apply_fn, strategies.get_strategy(name), mesh8, sgd.SGDConfig(),
            augment=False)
        s = state
        for i in range(2):
            s, loss = step(s, jax.random.PRNGKey(i), imgs, labs)
            float(loss)  # value fetch = completion fence
        steps[name], states[name] = step, s

    times = {"allreduce": [], "ddp": []}
    for i in range(9):
        for name in ("allreduce", "ddp"):
            t0 = time.time()
            states[name], loss = steps[name](
                states[name], jax.random.PRNGKey(i), imgs, labs)
            float(loss)  # value fetch = completion fence
            times[name].append(time.time() - t0)

    # Median over 9 interleaved pairs: robust to per-step scheduler spikes
    # (a single outlier cannot move the median) as well as slow drift.
    med = {k: sorted(v)[len(v) // 2] for k, v in times.items()}
    assert med["ddp"] <= med["allreduce"] * 1.5, med


def test_strategy_registry():
    assert set(strategies.STRATEGIES) == {
        "single", "gather", "allreduce", "ddp", "overlap",
        "compress-bf16", "compress-int8", "powersgd"}
    with pytest.raises(ValueError):
        strategies.get_strategy("zero_redundancy")
    assert strategies.get_strategy("powersgd").rank == \
        strategies.DEFAULT_COMPRESS_RANK
    assert strategies.get_strategy("powersgd", compress_rank=2).rank == 2
    with pytest.raises(ValueError):
        strategies.PowerSGD(rank=0)
    with pytest.raises(ValueError):
        strategies.CompressedPsum("fp4")


# -- round-7 tiers: overlapped ddp + compressed collectives -------------------

def run_stateful(mesh, strategy, grads_per_device, comm):
    """Apply a stateful strategy with its per-worker comm state threaded;
    returns (synced grads [replicated], new comm [stacked per worker])."""
    f = shard_map(
        lambda g, c: strategy(jax.tree.map(lambda a: a[0], g), DATA_AXIS,
                              comm=c),
        mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(DATA_AXIS)))
    return jax.jit(f)(grads_per_device, comm)


def test_overlap_computes_the_mean(mesh8, per_device_grads):
    expected = jax.tree.map(lambda a: jnp.mean(a, 0), per_device_grads)
    out = run_strategy(mesh8, strategies.get_strategy("overlap"),
                       per_device_grads)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        out, expected)


def test_overlapped_ddp_drops_the_barrier_chain(mesh8):
    """The overlap tier is the ddp bucket plan WITHOUT the inter-bucket
    optimization_barrier chain: at one leaf per bucket, ddp lowers
    leaves-1 barriers while overlap lowers ZERO — each bucket's psum is
    gated only by its own gradients (the StableHLO-level pin; the chain
    DEPTH contract lives in analysis/audit.py's overlap rule)."""
    grads = tree_of_grads(jax.random.PRNGKey(1))
    stacked = jax.tree.map(lambda a: a[None].repeat(8, 0), grads)

    def counts(strategy):
        f = shard_map(lambda g: strategy(
            jax.tree.map(lambda a: a[0], g), DATA_AXIS),
            mesh=mesh8, in_specs=(P(DATA_AXIS),), out_specs=P())
        hlo = jax.jit(f).lower(stacked).as_text()  # StableHLO MLIR
        return (len(re.findall(r"stablehlo\.all_reduce", hlo)),
                len(re.findall(r"stablehlo\.optimization_barrier", hlo)))

    assert counts(strategies.get_strategy("ddp", bucket_bytes=64)) == (4, 3)
    assert counts(strategies.get_strategy("overlap",
                                          bucket_bytes=64)) == (4, 0)
    # One 25MB bucket: same fused collective count as ddp, still no chain.
    assert counts(strategies.get_strategy("overlap")) == (4, 0)


def test_compressed_bf16_error_feedback(mesh8, per_device_grads):
    """The bf16 tier's wire mean must track the true mean within bf16
    rounding, the residual must be EXACTLY the untransmitted part
    (v - bf16(v)), and carrying it forward must not let quantization
    error accumulate across steps (the EF-SGD property)."""
    strat = strategies.get_strategy("compress-bf16")
    assert strat.stateful and strat.name == "compress-bf16"
    local_like = jax.tree.map(lambda a: a[0], per_device_grads)
    comm = strat.init_comm(local_like, 8)
    expected = jax.tree.map(lambda a: jnp.mean(a, 0), per_device_grads)

    out, new_comm = run_stateful(mesh8, strat, per_device_grads, comm)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=0, atol=2e-2),
        out, expected)
    # Residual == what this worker failed to transmit, bitwise.
    jax.tree.map(
        lambda g, r: np.testing.assert_array_equal(
            np.asarray(r),
            np.asarray(g.astype(jnp.float32)
                       - g.astype(jnp.bfloat16).astype(jnp.float32))),
        per_device_grads, new_comm["residual"])

    # Constant grads, residuals carried: the time-average of the synced
    # outputs converges on the true mean instead of repeating one step's
    # rounding error.
    outs, comm_t = [out], new_comm
    for _ in range(3):
        o, comm_t = run_stateful(mesh8, strat, per_device_grads, comm_t)
        outs.append(o)
    leaves_e = jax.tree.leaves(expected)
    for i, le in enumerate(leaves_e):
        avg = np.mean([np.asarray(jax.tree.leaves(o)[i]) for o in outs],
                      axis=0)
        one = np.max(np.abs(np.asarray(jax.tree.leaves(outs[0])[i]) - le))
        assert np.max(np.abs(avg - np.asarray(le))) <= one + 1e-6


def test_compressed_int8_shared_scale_never_overflows(mesh8):
    """Every worker at +amax is the wire's worst case: a naive per-worker
    127 scale (or an unclipped round at scale amax*world/127) sums past
    int8's 127 and wraps the mean NEGATIVE.  The shared pmax'd scale with
    the clip at L = 127 // world keeps the sum bounded — identical grads
    come back exactly, sign preserved."""
    g = {"w": jnp.full((4, 4), 3.0, jnp.float32),
         "b": jnp.full((2,), -3.0, jnp.float32)}
    stacked = jax.tree.map(lambda a: a[None].repeat(8, 0), g)
    strat = strategies.get_strategy("compress-int8")
    comm = strat.init_comm(g, 8)
    out, new_comm = run_stateful(mesh8, strat, stacked, comm)
    # amax=3, L=15, scale=1/5: v/scale = +-15 on the nose -> exact.
    np.testing.assert_allclose(np.asarray(out["w"]), 3.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]), -3.0, rtol=1e-6)
    jax.tree.map(lambda r: np.testing.assert_allclose(
        np.asarray(r), 0.0, atol=1e-6), new_comm["residual"])

    # Mixed magnitudes still stay within quantization distance of the
    # true mean (one scale step = amax / (127 // world)).
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    rand = jax.tree.map(
        lambda a: jnp.stack([jax.random.normal(k, a.shape) for k in keys]),
        g)
    expected = jax.tree.map(lambda a: jnp.mean(a, 0), rand)
    out2, _ = run_stateful(mesh8, strat, rand,
                           strat.init_comm(g, 8))
    amax = max(float(jnp.max(jnp.abs(l))) for l in jax.tree.leaves(rand))
    step = amax / (127 // 8)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=0, atol=step),
        out2, expected)


def test_powersgd_rank1_reconstruction_and_determinism(mesh8):
    """A rank-1 matrix is inside the rank-4 subspace, so one power-iteration
    step reconstructs it to float precision (residual ~ 0); vector leaves
    ride the bf16 fallback; and the whole tier is deterministic — a fresh
    run from the same comm state is bitwise identical."""
    u = jax.random.normal(jax.random.PRNGKey(17), (24,))
    vv = jax.random.normal(jax.random.PRNGKey(18), (6,))
    g = {"w": jnp.outer(u, vv), "b": jnp.arange(6, dtype=jnp.float32)}
    stacked = jax.tree.map(lambda a: a[None].repeat(8, 0), g)
    strat = strategies.get_strategy("powersgd")
    assert strat._low_rank(g["w"].shape) and not strat._low_rank(
        g["b"].shape)
    comm = strat.init_comm(g, 8)
    assert set(comm) == {"residual", "q"}

    out1, comm1 = run_stateful(mesh8, strat, stacked, comm)
    np.testing.assert_allclose(np.asarray(out1["w"]), np.asarray(g["w"]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(comm1["residual"]["w"]), 0.0,
                               atol=1e-4)
    # bf16 fallback leaf: mean within bf16 rounding.
    np.testing.assert_allclose(np.asarray(out1["b"]), np.asarray(g["b"]),
                               rtol=2e-2, atol=1e-3)

    out2, comm2 = run_stateful(mesh8, strat, stacked, comm)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), out1, out2)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), comm1, comm2)


def test_reshard_comm_conserves_residual_mass():
    """Elastic world resize: the total undelivered error-feedback mass is
    invariant (2 -> 1 -> 3), and PowerSGD Q factors stay replicated."""
    comm = {
        "residual": {"w": jnp.asarray([[1.0, -2.0], [3.0, 0.5]])},
        "q": {"000": jnp.repeat(jnp.asarray([[1.0, 2.0]])[None], 2, 0)},
    }
    down = strategies.reshard_comm(comm, 1)
    np.testing.assert_allclose(np.asarray(down["residual"]["w"]),
                               [[4.0, -1.5]])
    up = strategies.reshard_comm(down, 3)
    assert up["residual"]["w"].shape == (3, 2)
    np.testing.assert_allclose(
        np.asarray(up["residual"]["w"]).sum(0), [4.0, -1.5], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(up["q"]["000"]),
                               np.repeat([[[1.0, 2.0]]], 3, 0))
