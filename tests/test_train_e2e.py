"""End-to-end training tests on the virtual 8-device CPU mesh.

The equivalence test is the one the reference's structure implies but never
writes down (SURVEY.md §4): strategies gather/allreduce/ddp must produce
fp-tolerance-equal parameters after N steps from identical init and shards.

A tiny conv net stands in for VGG-11 to keep CPU compiles fast — the
strategy/step/loop code under test is identical (full VGG runs in
tests/test_models.py and in the benchmark's cells on the TPU).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cs744_ddp_tpu.data import cifar10
from cs744_ddp_tpu.ops import sgd
from cs744_ddp_tpu.ops.loss import cross_entropy
from cs744_ddp_tpu.train.loop import Trainer, _shard_batches

from tinynet import run_steps, tiny_cnn, tiny_cnn_nobn


def make_trainer(tmp_path, mesh, strategy, **kw):
    kw.setdefault("global_batch", 64)
    kw.setdefault("augment", False)  # determinism across strategies
    kw.setdefault("log", lambda s: None)
    kw.setdefault("model", tiny_cnn())
    return Trainer(strategy=strategy, mesh=mesh, data_dir=str(tmp_path), **kw)


def params_allclose(a, b, atol):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


def test_strategy_equivalence_after_steps(tmp_path, mesh8):
    """gather ≡ allreduce ≡ ddp: same params after 5 steps."""
    results = {}
    for strategy in ("gather", "allreduce", "ddp"):
        tr = make_trainer(tmp_path, mesh8, strategy)
        key = jax.random.PRNGKey(123)
        for it, (imgs, labs) in enumerate(_shard_batches(
                tr.train_split, tr.world, tr.global_batch, 0, shuffle=True)):
            if it >= 5:
                break
            x, y = tr._put(imgs, labs)
            tr.state, loss = tr.train_step(tr.state, key, x, y)
        results[strategy] = jax.block_until_ready(tr.state.params)
    # Tolerance: the three collective patterns sum in different orders
    # (stack+mean vs ring all-reduce vs bucketed all-reduce), so results
    # differ at fp32 rounding level, amplified by BN + lr=0.1 — exactly as
    # the reference's Gloo strategies would.  Bitwise equality is neither
    # achievable nor claimed.
    params_allclose(results["gather"], results["allreduce"], atol=5e-4)
    params_allclose(results["ddp"], results["allreduce"], atol=5e-4)


def test_single_matches_eight_way_ddp(tmp_path, mesh1, mesh8):
    """A world-1 run and an 8-way DDP run on the same global batch take the
    same parameter step, modulo BatchNorm: the 8-way run normalizes each
    shard with LOCAL batch stats (per-replica BN, reference semantics), so
    only the BN-free subtree is compared after step 1."""
    tr1 = make_trainer(tmp_path, mesh1, "single")
    tr8 = make_trainer(tmp_path, mesh8, "ddp")
    # Force identical init (same seed => already identical, but be explicit).
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tr1.state.params, tr8.state.params)

    imgs, labs = next(_shard_batches(tr1.train_split, tr1.world, 64, 0,
                                     shuffle=True))
    x1, y1 = tr1._put(imgs, labs)
    tr1.state, _ = tr1.train_step(tr1.state, jax.random.PRNGKey(0), x1, y1)

    imgs8, labs8 = next(_shard_batches(tr8.train_split, tr8.world, 64, 0,
                                       shuffle=True))
    x8, y8 = tr8._put(imgs8, labs8)
    tr8.state, _ = tr8.train_step(tr8.state, jax.random.PRNGKey(0), x8, y8)

    # Different sampler world sizes shard the SAME seed-0 permutation
    # differently; global batch content is the first 64 entries either way.
    np.testing.assert_array_equal(np.sort(labs), np.sort(labs8))

    # fc gradient depends on BN output => compare conv weights only would
    # also differ through BN backward.  The directly comparable piece with
    # per-replica BN stats is the fc BIAS gradient (sum of dlogits), which
    # is batch-mean over the same examples in both runs... but dlogits pass
    # through BN too.  So: assert closeness loosely — per-replica BN at
    # shard size 8 vs 64 is a real (documented) semantic difference, and
    # this test pins it as BOUNDED, not zero.
    for xa, xb in zip(jax.tree.leaves(tr1.state.params),
                      jax.tree.leaves(tr8.state.params)):
        a, b = np.asarray(xa), np.asarray(xb)
        # Loose bound: per-replica BN stats (shard size 8 vs 64) are a real
        # semantic difference.  The TIGHT averaging oracle is the BN-free
        # test below — this bound once masked a grads×world bug, so it only
        # documents that BN noise stays bounded, nothing more.
        assert np.max(np.abs(a - b)) < 0.6, "divergence beyond BN-stat noise"


def test_single_matches_eight_way_ddp_bnfree_tight(tmp_path, mesh1, mesh8):
    """The REAL cross-world averaging oracle (VERDICT r1 item 5): with no
    BatchNorm there is no per-replica batch-stats semantic, so a 1-device
    run and an 8-way DDP run on the same global batch compute the same
    mathematics — the mean gradient over the global batch is invariant to
    how the batch is dealt across shards (the round-robin deal of batch b
    covers exactly permutation positions [b*64, (b+1)*64) in both worlds).
    Equality must hold to fp tolerance over several steps."""
    # lr=0.01: the default 0.1 makes the tiny net's trajectory unstable
    # (loss grows), and an unstable trajectory amplifies benign fp32
    # reassociation into O(1) parameter differences — the oracle needs
    # stable dynamics so only a REAL averaging bug can produce divergence.
    cfg = sgd.SGDConfig(lr=0.01)
    tr1 = make_trainer(tmp_path, mesh1, "single", model=tiny_cnn_nobn(),
                       sgd_cfg=cfg)
    tr8 = make_trainer(tmp_path, mesh8, "ddp", model=tiny_cnn_nobn(),
                       sgd_cfg=cfg)
    for tr in (tr1, tr8):
        run_steps(tr, 5)
    # fp32 reassociation (8-way psum vs one batch mean) only — no BN noise.
    params_allclose(tr1.state.params, tr8.state.params, atol=2e-5)
    params_allclose(tr1.state.opt_state.momentum,
                    tr8.state.opt_state.momentum, atol=2e-5)


def test_windowed_path_matches_per_step_path(tmp_path, mesh8):
    """A W-step compiled window must produce the same TrainState as W
    individual per-step calls (augment off so PRNG streams are moot)."""
    tr_win = make_trainer(tmp_path, mesh8, "ddp")
    tr_step = make_trainer(tmp_path, mesh8, "ddp")
    n_iters = 7
    # Shrink BOTH trainers to the same n_iters-batch epoch (the sampler
    # permutation depends on the dataset size, so the splits must match).
    for tr in (tr_win, tr_step):
        tr.train_split = cifar10.Split(
            tr.train_split.images[:64 * n_iters],
            tr.train_split.labels[:64 * n_iters])
    tr_win.train_model(0)

    key = jax.random.fold_in(jax.random.PRNGKey(tr_step.seed), 0)
    for it, (imgs, labs) in enumerate(_shard_batches(
            tr_step.train_split, tr_step.world, 64, 0, shuffle=True)):
        if it >= n_iters:
            break
        x, y = tr_step._put(imgs, labs)
        tr_step.state, _ = tr_step.train_step(
            tr_step.state, jax.random.fold_in(key, it), x, y)

    # Tolerance: scan vs unrolled dispatch compile to different programs,
    # so fp32 reassociation gives ~1e-5-level divergence over 7 steps.
    params_allclose(tr_win.state.params, tr_step.state.params, atol=1e-4)
    params_allclose(tr_win.state.opt_state.momentum,
                    tr_step.state.opt_state.momentum, atol=1e-4)
    # Running variance accumulates squared activations — more fp-sensitive.
    params_allclose(tr_win.state.bn_state, tr_step.state.bn_state, atol=1e-3)


def test_windowed_path_matches_per_step_path_with_augment(tmp_path, mesh4):
    """With the canonical PRNG fold order (batch index, then mesh position)
    the windowed and per-step paths must consume the SAME augmentation
    stream — this pins ADVICE r1's fold-order divergence as fixed."""
    tr_win = make_trainer(tmp_path, mesh4, "ddp", augment=True)
    tr_step = make_trainer(tmp_path, mesh4, "ddp", augment=True)
    n_iters = 4
    for tr in (tr_win, tr_step):
        tr.train_split = cifar10.Split(
            tr.train_split.images[:64 * n_iters],
            tr.train_split.labels[:64 * n_iters])
    tr_win.train_model(0)

    key = jax.random.fold_in(jax.random.PRNGKey(tr_step.seed), 0)
    for it, (imgs, labs) in enumerate(_shard_batches(
            tr_step.train_split, tr_step.world, 64, 0, shuffle=True)):
        if it >= n_iters:
            break
        x, y = tr_step._put(imgs, labs)
        tr_step.state, _ = tr_step.train_step(
            tr_step.state, jax.random.fold_in(key, it), x, y)

    # Same stream => same data => scan-vs-unrolled fp divergence only.
    params_allclose(tr_win.state.params, tr_step.state.params, atol=1e-4)


def test_ragged_tail_batch_is_trained(tmp_path, mesh8):
    """drop_last=False parity (VERDICT r2 item 4): the short final batch is
    trained — through its own compiled step at its true shape — and the
    windowed and per-step paths agree on it.

    208 examples / world 8 / global batch 64: per-rank 26 = 3*8 + 2, so the
    epoch is 3 full batches plus a ragged global tail of 16."""
    tr_win = make_trainer(tmp_path, mesh8, "ddp")
    tr_step = make_trainer(tmp_path, mesh8, "ddp", profile_phases=True)
    for tr in (tr_win, tr_step):
        tr.train_split = cifar10.Split(tr.train_split.images[:208],
                                       tr.train_split.labels[:208])
    t_win = tr_win.train_model(0)
    t_step = tr_step.train_model(0)
    # Printed count == trained count: ceil(26 / 8) = 4 iterations.
    assert t_win.iter_number - 1 == 4
    assert t_step.iter_number - 1 == 4
    # Both paths take the same parameter trajectory through the tail.
    params_allclose(tr_win.state.params, tr_step.state.params, atol=1e-4)
    # The tail actually MOVED the params: replay only the 3 full windows.
    tr_full = make_trainer(tmp_path, mesh8, "ddp")
    tr_full.train_split = cifar10.Split(tr_full.train_split.images[:208],
                                        tr_full.train_split.labels[:208])
    tr_full.limit_train_batches = 3
    tr_full.train_model(0)
    diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(tr_win.state.params),
                             jax.tree.leaves(tr_full.state.params))]
    assert max(diffs) > 1e-6, "tail step was a no-op"


def test_staging_cache_invalidates_on_split_replacement(tmp_path, mesh4):
    """Replacing test_split after an eval must restage (not reuse stale
    device arrays)."""
    tr = make_trainer(tmp_path, mesh4, "allreduce")
    tr.test_split = cifar10.Split(tr.test_split.images[:128],
                                  tr.test_split.labels[:128])
    _, correct_full, _ = tr.test_model()
    tr.test_split = cifar10.Split(tr.test_split.images[:64],
                                  tr.test_split.labels[:64])
    _, correct_small, _ = tr.test_model()
    assert correct_small <= 64  # would exceed 64 if stale staging were used


def test_loss_decreases_single_device(tmp_path, mesh1):
    """The reference's convergence oracle: running loss drops (SURVEY.md §4).
    Synthetic data is class-templated, so a few steps cut loss sharply."""
    tr = make_trainer(tmp_path, mesh1, "single", global_batch=64,
                      sgd_cfg=sgd.SGDConfig(lr=0.05))
    key = jax.random.PRNGKey(0)
    losses = []
    for it, (imgs, labs) in enumerate(_shard_batches(
            tr.train_split, 1, 64, 0, shuffle=True)):
        if it >= 30:
            break
        x, y = tr._put(imgs, labs)
        tr.state, loss = tr.train_step(tr.state, jax.random.fold_in(key, it),
                                       x, y)
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.7, losses


def test_eval_counts_exact_over_full_test_set(tmp_path, mesh4):
    tr = make_trainer(tmp_path, mesh4, "allreduce", global_batch=64)
    # Shrink the test set for speed, with a ragged tail (not % 64).
    tr.test_split = cifar10.Split(tr.test_split.images[:200],
                                  tr.test_split.labels[:200])
    avg_loss, correct, acc = tr.test_model()
    assert 0 <= correct <= 200
    assert acc == pytest.approx(100.0 * correct / 200)
    assert avg_loss > 0

    # Cross-check against a direct (unsharded, unpadded) computation.
    from cs744_ddp_tpu.data import augment as aug
    from cs744_ddp_tpu.ops.loss import accuracy_counts
    x = aug.normalize(jnp.asarray(tr.test_split.images))
    logits, _ = tr.apply_fn(tr.state.params, tr.state.bn_state, x, train=False)
    expected_correct = int(accuracy_counts(logits,
                                           jnp.asarray(tr.test_split.labels)))
    assert correct == expected_correct
    expected_loss = float(cross_entropy(
        logits, jnp.asarray(tr.test_split.labels)))
    assert avg_loss == pytest.approx(expected_loss, abs=1e-5)


def test_trainer_run_prints_reference_schedule(tmp_path, mesh1):
    lines = []
    tr = make_trainer(tmp_path, mesh1, "single", global_batch=64,
                      log=lines.append)
    tr.test_split = cifar10.Split(tr.test_split.images[:64],
                                  tr.test_split.labels[:64])
    # ~25 iterations: one full window + part of the next.
    tr.train_split = cifar10.Split(tr.train_split.images[:64 * 25],
                                   tr.train_split.labels[:64 * 25])
    tr.run(epochs=1)
    text = "\n".join(lines)
    # Reference prints len(train_loader) = per-rank batch count
    # (Part 2a/main.py:46): ceil(50000 / 64) = 782 at construction time.
    assert "Size of training set is 782" in text
    assert "Training loss after 20 iterations is" in text
    assert "Training time after 1 epoch is" in text
    assert "Test set: Average loss:" in text
    # First window excluded from timing report (reference main.py:51).
    assert "Average Pass time in iter 20 is" not in text


def test_bf16_precision_trains_and_evaluates(tmp_path, mesh4):
    """Mixed-precision mode: master params stay f32, training converges on
    the synthetic split, and the eval path runs under bf16 activations."""
    tr = Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh4,
                 global_batch=64, data_dir=str(tmp_path), augment=False,
                 precision="bf16", log=lambda s: None)
    assert all(l.dtype == jnp.float32
               for l in jax.tree.leaves(tr.state.params))
    key = jax.random.PRNGKey(0)
    losses = []
    for it, (imgs, labs) in enumerate(_shard_batches(
            tr.train_split, 4, 64, 0, shuffle=True)):
        if it >= 30:
            break
        x, y = tr._put(imgs, labs)
        tr.state, loss = tr.train_step(tr.state, jax.random.fold_in(key, it),
                                       x, y)
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.7, losses
    tr.test_split = cifar10.Split(tr.test_split.images[:128],
                                  tr.test_split.labels[:128])
    avg_loss, correct, acc = tr.test_model()
    assert np.isfinite(avg_loss) and 0 <= correct <= 128

    import pytest
    with pytest.raises(ValueError):
        Trainer(model=tiny_cnn(), strategy="ddp", mesh=mesh4,
                global_batch=64, data_dir=str(tmp_path),
                precision="fp16", log=lambda s: None)
