"""POSITIVE strategy-spectrum separation in CI (VERDICT r3 item 3a).

The reference's entire pedagogical point is the ordering
gather (Part 2a) > allreduce (Part 2b) > ddp (Part 3) in per-step cost
(``/root/reference/src/Part 2a/main.py:117-127`` vs ``Part 2b/main.py:
116-119`` vs ``Part 3/main.py:61``).  tests/test_strategies.py pins the
structural distinction (HLO patterns) and a one-directional bound (ddp must
not lose); this test asserts the POSITIVE wall-clock separation, so a
regression that equalized the tiers — e.g. a barrier-chain change letting
XLA's all-reduce combiner merge the per-param tier — fails CI.

Measured where the collective patterns dominate: a shrunken variant of the
comm-bound MLP from tools/bench_strategy_spectrum.py (many small leaves, 1
example per device) on the 8-virtual-device CPU mesh; the full-size tool
run is what BASELINE.md records (gather 3,110 > allreduce 2,068 > ddp
1,430 ms/step, a 1.5x gap for the asserted pair).

Noise discipline — this host is ONE core timesliced across 8 virtual
devices, so external load inflates steps by 2x+ in bursts: samples are
single steps, rounds are INTERLEAVED across tiers, and the compared
statistic is the MIN over rounds (contention is strictly one-sided; an
early median-based version of this test flaked twice under full-suite
load, once even inverting the ordering when a burst landed on gather's
quiet slot).

Only gather > allreduce is asserted: the allreduce-vs-ddp separation does
NOT survive the CPU backend reliably — it strips the optimization-barrier
chains, so the per-param and bucketed tiers' compiled forms converge
there (strategies.py module docstring; observed inverted under full-suite
load).  That ordering is pinned where it is real: structurally on the TPU
lowering (tests/test_tpu_aot.py — per-leaf vs per-bucket collective
counts) and by the audit contracts (tests/test_analysis.py).
"""

import os
import sys
import time

import numpy as np

import jax

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import bench_strategy_spectrum as spectool  # noqa: E402

from cs744_ddp_tpu.ops import sgd
from cs744_ddp_tpu.parallel import get_strategy, mesh as meshlib
from cs744_ddp_tpu.train import step as steplib

ROUNDS = 5


def test_spectrum_ordering_gather_above_allreduce(mesh8, monkeypatch):
    # Half-depth MLP (62 leaves): the separation is structural (2
    # sequential collectives per leaf vs 1), so fewer/smaller leaves keep
    # the ratio while making 5 interleaved rounds affordable in CI.
    monkeypatch.setattr(spectool, "LAYERS", [3072] + [512] * 30 + [10])
    state = steplib.init_train_state(spectool.mlp_init, jax.random.PRNGKey(0))
    state = meshlib.put_global_tree(state, meshlib.replicated(mesh8))

    batch = 8  # 1 example/device: per-step cost ~ the collective pattern
    rng = np.random.default_rng(0)
    images = jax.device_put(
        rng.integers(0, 256, (batch, 32, 32, 3)).astype(np.uint8),
        meshlib.batch_sharding(mesh8))
    labels = jax.device_put(
        rng.integers(0, 10, (batch,)).astype(np.int32),
        meshlib.batch_sharding(mesh8))
    key = jax.random.PRNGKey(1)

    # Only the two tiers whose ordering IS asserted get compiled and
    # stepped (ddp's separation lives on the TPU lowering, module
    # docstring — benchmarking it here was unasserted dead cost).
    steps, states = {}, {}
    for name in ("gather", "allreduce"):
        steps[name] = steplib.make_train_step(
            spectool.mlp_apply, get_strategy(name), mesh8, sgd.SGDConfig(),
            augment=False)
        s, loss = steps[name](state, key, images, labels)  # compile+warmup
        float(loss)
        states[name] = s

    samples = {name: [] for name in steps}
    for _ in range(ROUNDS):
        for name, step in steps.items():   # interleaved: contention is
            s = states[name]               # shared across tiers per round
            t0 = time.time()
            s, loss = step(s, key, images, labels)
            float(loss)                    # value fetch = completion fence
            samples[name].append(time.time() - t0)
            states[name] = s

    best = {name: min(v) for name, v in samples.items()}
    assert best["gather"] > 1.1 * best["allreduce"], (best, samples)


def test_compressed_tiers_never_lose_on_measured_comm_bytes(mesh8,
                                                            monkeypatch):
    """Round-7 byte ladder, MEASURED on the lowering (collective RESULT
    bytes from the pre-optimization HLO, analysis/stats.py — the same
    accounting --audit-zoo certifies): no compressed tier may ever carry
    more all-reduce traffic than the per-param f32 tier, and the declared
    ratios hold with margin — bf16 ~2x, int8 ~4x, powersgd far below on
    the MLP's (3072,512)/(512,512) leaves.  Wall-clock can't separate the
    tiers on the one-core CPU mesh (docstring above); bytes can."""
    from cs744_ddp_tpu.analysis import stats
    monkeypatch.setattr(spectool, "LAYERS", [3072] + [512] * 6 + [10])

    batch = 8
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (batch,)).astype(np.int32)
    key = jax.random.PRNGKey(1)

    def ar_bytes(name):
        strat = get_strategy(name)
        state = steplib.init_train_state(
            spectool.mlp_init, jax.random.PRNGKey(0), strat, 8)
        step = steplib.make_train_step(spectool.mlp_apply, strat, mesh8,
                                       sgd.SGDConfig(), augment=False)
        hlo = step.lower(state, key, images, labels).compiler_ir(
            dialect="hlo").as_hlo_text()
        return stats.collective_bytes(hlo).get("all-reduce", 0)

    f32 = ar_bytes("allreduce")
    measured = {t: ar_bytes(t)
                for t in ("compress-bf16", "compress-int8", "powersgd")}
    # The satellite's one-directional floor: never lose to per-param f32.
    for tier, got in measured.items():
        assert got < f32, (tier, got, f32)
    # And the contract ratios, with headroom for the non-gradient aux
    # collectives (loss psum; int8's packed shared-scale pmax).
    assert measured["compress-bf16"] <= 0.55 * f32, (measured, f32)
    assert measured["compress-int8"] <= 0.30 * f32, (measured, f32)
    # rank 4 on (3072,512): 4*(m+n) floats vs m*n — order-of-magnitude.
    assert measured["powersgd"] <= 0.20 * f32, (measured, f32)
