"""Multi-chip TPU compilation, without TPU hardware: AOT compile-only.

``jax.experimental.topologies`` provides a deviceless v5e-8 topology, so CI
can compile the REAL 8-chip TPU programs (the thing the virtual CPU mesh
cannot check: TPU lowering, ICI collective selection, the compiled
collective schedule) and assert structure on the final HLO.

Notes on what TPU HLO shows (vs the GPU backend): XLA:GPU splits async
collectives into ``all-reduce-start/done`` pairs in the final module; the
TPU backend schedules collectives internally and typically keeps a fused
sync ``all-reduce`` op at this model scale, while splitting collectives it
chooses to overlap (the gather strategy's ``all-gather`` does appear as an
async start/done pair).  Overlap on TPU is the latency-hiding scheduler's
job.

These tests pin the COMPILED cost spectrum — the reference's pedagogical
point, which survives TPU compilation because the strategies' barrier
chains prevent the all-reduce combiner from equalizing the tiers
(strategies.py): per-param stays one collective per leaf, ddp collapses to
one fused variadic collective per ~25 MB bucket.
"""

import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cs744_ddp_tpu.models import vgg
from cs744_ddp_tpu.ops import sgd

# AOT-lowering full VGG-11 programs for a v5e-8 mesh costs minutes per test
# on a single CPU compile thread (the session fixture alone ~8 min) — far
# past the tier-1 sweep's budget; run the module with `-m slow`.
pytestmark = pytest.mark.slow
from cs744_ddp_tpu.parallel import get_strategy
from cs744_ddp_tpu.parallel.mesh import DATA_AXIS
from cs744_ddp_tpu.train import step as steplib

from tinynet import tiny_cnn


@pytest.fixture(scope="module")
def v5e8_mesh():
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc("v5e:2x4", platform="tpu")
    except Exception as e:  # no TPU compile-only client in this env
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    return Mesh(np.array(topo.devices), (DATA_AXIS,))


def _lower_step(mesh, model, strategy, batch):
    init_fn, apply_fn = model
    state = steplib.init_train_state(init_fn, jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(DATA_AXIS))
    state_sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), state)
    args = (state_sds,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct((batch, 32, 32, 3), jnp.uint8,
                                 sharding=sharded),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sharded))
    step = steplib.make_train_step(apply_fn, get_strategy(strategy), mesh,
                                   sgd.SGDConfig(), augment=True)
    return step.lower(*args)


def _compile_step(mesh, model, strategy, batch):
    return _lower_step(mesh, model, strategy, batch).compile().as_text()


def test_vgg11_ddp_compiles_for_v5e8_and_fuses(v5e8_mesh):
    """The flagship config (VGG-11, ddp) must compile for 8 real-topology
    v5e chips, and the compiled program must carry about bucket-count
    (37 MB grads / 25 MB = 2) all-reduces — DDP-grade fusion on TPU (+1
    margin for the step's own scalar-metric psum)."""
    txt = _compile_step(v5e8_mesh, vgg.VGG11(), "ddp", 256)
    n = len(re.findall(r" all-reduce\(", txt))
    assert 1 <= n <= 3, n


def test_vgg11_allreduce_keeps_per_leaf_collectives_on_tpu(v5e8_mesh):
    """Part 2b's deliberately-unfused cost model must SURVIVE TPU
    compilation: the barrier-chained per-param tier keeps (at least) one
    all-reduce per parameter leaf (34 for VGG-11+BN) — without the chain
    XLA's combiner would rewrite it into the ddp tier and erase the cost
    spectrum the reference exists to measure."""
    txt = _compile_step(v5e8_mesh, vgg.VGG11(), "allreduce", 256)
    n = len(re.findall(r" all-reduce\(", txt))
    assert n >= 34, n

    # And the spectrum is ordered: ddp strictly fewer collectives.
    txt_ddp = _compile_step(v5e8_mesh, vgg.VGG11(), "ddp", 256)
    assert len(re.findall(r" all-reduce\(", txt_ddp)) < n


def test_gather_strategy_keeps_two_phase_shape_on_tpu(v5e8_mesh):
    """Part 2a's deliberately-naive root-mediated pattern must SURVIVE TPU
    compilation as two dependent collective phases (gather, then
    mean-broadcast) — and the all-gather phase is scheduled async
    (start/done split), evidence XLA overlaps collectives it can."""
    txt = _compile_step(v5e8_mesh, tiny_cnn(), "gather", 64)
    assert len(re.findall(r"all-gather", txt)) >= 1
    assert len(re.findall(r"all-gather-start", txt)) >= 1  # async split
    assert len(re.findall(r" all-reduce\(", txt)) >= 1     # broadcast phase


def test_collective_chain_depth_pins_latency_shape(v5e8_mesh):
    """The tiers' LATENCY shape, statically (VERDICT r4 item 6): the number
    of collectives forced to run sequentially by data dependencies in the
    pre-optimization HLO, where the strategies' optimization_barrier chains
    are still visible.  Wall-clock can order gather vs allreduce on the CPU
    backend (tests/test_spectrum_wallclock.py) but not allreduce vs ddp
    (barriers are stripped there); this pins all three:

      gather    — 2 dependent collectives per leaf, leaf-chained: 2x34 = 68
                  (``/root/reference/src/Part 2a/main.py:117-127``)
      allreduce — 1 per leaf, leaf-chained: 34 (``Part 2b/main.py:116-119``)
      ddp       — 1 per ~25 MB bucket, buckets independent: 2
                  (``Part 3/main.py:61``)

    A regression that serializes the ddp buckets, de-fuses them (count
    tests above), or lets the combiner collapse a chained tier fails here
    even though the CPU backend cannot measure it."""
    from cs744_ddp_tpu.analysis import collective_chain_depth

    depth = {
        name: collective_chain_depth(
            _lower_step(v5e8_mesh, vgg.VGG11(), name, 256)
            .compiler_ir(dialect="hlo").as_hlo_text())
        for name in ("gather", "allreduce", "ddp")}
    # 34 = VGG-11's trainable leaves (the tier chains one psum per leaf);
    # a tight BAND rather than equality because toolchain bumps have moved
    # the count by the odd loss/metric psum the parser attributes to the
    # chain (VERDICT r5 item 5) — the regression this pins is the chain
    # COLLAPSING (fusion to a handful) or exploding, not +-2 bookkeeping.
    assert 34 <= depth["allreduce"] <= 36, depth
    assert depth["gather"] >= 2 * 34, depth
    # 2 buckets (37 MB / 25 MB) + margin of 1 for the loss/metric psum;
    # strictly below the per-leaf tier either way.
    assert depth["ddp"] <= 3, depth
    assert depth["ddp"] < depth["allreduce"] < depth["gather"], depth


@pytest.mark.slow  # compiles four big models for v5e-8 on one CPU thread
def test_large_zoo_models_compile_for_v5e8(v5e8_mesh):
    """vgg13 (10 BNs), vgg16 (13 BNs), vgg19 (16 BNs), resnet18 (20 BNs)
    and resnet34 (36 BNs) must compile for the 8-chip TPU topology.  Regression lock
    for the round-3 post-main-fusion SIGILL (every model beyond vgg11
    crashed the v5e compiler until the BN backward's fusion fence) — and
    since round 4 the lock covers BOTH fence regimes: every VGG compiles
    UNFENCED (the crash no longer reproduces and unfenced is faster
    there) while the ResNets compile FENCED (faster for them); a compiler
    regression on either path crashes this test loudly.
    models/layers.py::_bn_train_bwd has the full history."""
    from cs744_ddp_tpu.models import resnet

    txt = _compile_step(v5e8_mesh, vgg.VGG13(), "ddp", 64)
    assert " all-reduce(" in txt
    txt = _compile_step(v5e8_mesh, vgg.VGG16(), "ddp", 64)
    assert " all-reduce(" in txt
    txt = _compile_step(v5e8_mesh, vgg.VGG19(), "ddp", 64)
    assert " all-reduce(" in txt
    txt = _compile_step(v5e8_mesh, resnet.ResNet18(), "ddp", 64)
    assert " all-reduce(" in txt
    txt = _compile_step(v5e8_mesh, resnet.ResNet34(), "ddp", 64)
    assert " all-reduce(" in txt


def test_delta_rule_kernels_compile_for_a_v5e_under_their_scope(v5e8_mesh):
    """ops/gdn.py's two Pallas kernels at the hybrid decoder's widths (8192
    positions x 32 heads on 16 key heads x 128): Mosaic takes the forward kernel and, in the
    gradient, the state-keeping forward and the backward one; each
    custom-call is named `gdn_chunks_*` and keeps `gdn_recurrence` in its
    op_name, which is how the benchmark's reader finds its time."""
    from jax.sharding import SingleDeviceSharding
    from cs744_ddp_tpu.ops import gdn
    one = SingleDeviceSharding(v5e8_mesh.devices.flat[0])
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
    x = (sds(8192, 16, 128),) * 2 + (sds(8192, 32, 128),) \
        + (sds(8192, 32),) * 2
    rule = lambda *a: gdn.delta_rule(*a, kernels=True)
    grads = jax.grad(lambda *a: jnp.sum(rule(*a)), argnums=(0, 1, 2, 3, 4))
    for f, kernels in ((rule, ["gdn_chunks_fwd"]),
                       (grads, ["gdn_chunks_fwd", "gdn_chunks_bwd"])):
        calls = [line for line in
                 jax.jit(f).lower(*x).compile().as_text().splitlines()
                 if "custom-call(" in line and "tpu_custom_call" in line]
        assert len(calls) == len(kernels)
        for line, name in zip(calls, kernels):
            assert re.search(r"%?" + name + r"[.\d]* = ", line), line
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "gdn_recurrence" in op_name and name in op_name


def test_hyper_connection_kernels_compile_for_a_v5e_under_their_scope(
        v5e8_mesh):
    """ops/hyper.py's four Pallas kernels at the latent decoder's widths
    (4 streams x 4096 x 3584) around a stand-in sublayer: Mosaic takes the
    forward pair and, in the gradient, the backward pair beside it; every
    custom-call is named `mhc_*` and keeps `mhc_mix` in its op_name, the
    backward pair's inside the `custom_vjp`'s backward rule, which is how
    the benchmark's reader finds their time; the sublayer's own product
    does not."""
    from jax.sharding import SingleDeviceSharding
    from cs744_ddp_tpu.ops import hyper
    one = SingleDeviceSharding(v5e8_mesh.devices.flat[0])
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
    n, width = 4, 3584
    x, w = sds(n, 4096, width), sds(width, width)
    p = {"phi": sds(n * width, n * n + 2 * n), "alpha": sds(3),
         "bias": sds(n * n + 2 * n)}

    def connection(x, p, w):
        def sublayer(h):
            with jax.named_scope("stand_in"):
                return jnp.dot(h, w), ()
        return hyper.connect(sublayer, x, p, iters=20, eps=1e-6,
                             clamp=(-30.0, 30.0), kernels=True)[0]
    grads = jax.grad(lambda *a: jnp.sum(connection(*a) ** 2),
                     argnums=(0, 1, 2))
    for f, kernels in (
            (connection, ["mhc_read_fwd", "mhc_write_fwd"]),
            (grads, ["mhc_read_fwd", "mhc_write_fwd", "mhc_write_bwd",
                     "mhc_read_bwd"])):
        text = jax.jit(f).lower(x, p, w).compile().as_text()
        calls = [line for line in text.splitlines()
                 if "custom-call(" in line and "tpu_custom_call" in line]
        assert len(calls) == len(kernels)
        for line, name in zip(calls, kernels):
            assert re.search(r"%?" + name + r"[.\d]* = ", line), line
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "mhc_mix" in op_name and name in op_name
            assert ("transpose(jvp(mhc_mix))" in op_name) == \
                name.endswith("_bwd")
        inside = [m for m in re.findall(r'op_name="([^"]*)"', text)
                  if "stand_in" in m]
        assert inside and not any("mhc_mix" in m for m in inside)
