"""The strong-scaling elastic train window: bitwise world-invariant math.

The standard step programs (``train/step.py``) are deliberately
world-DEPENDENT in three places: the loss/grad reduction is a
``lax.pmean`` of per-shard means (float reduction order changes with the
shard count), BatchNorm normalizes with the local shard's statistics, and
the augmentation PRNG folds ``lax.axis_index``.  All three are faithful to
the reference — and all three make a world-resize change the trajectory.

This module builds the program whose update is a pure function of the
GLOBAL batch, independent of how many ranks compute it:

* every global batch of B examples is decomposed into S fixed-size
  **microshards** (S a power of two, microshard batch B/S) laid out in
  canonical order;
* rank r of world M (M | S) loops over its k = S/M contiguous microshards
  with a ``lax.fori_loop`` whose trip count is a RUNTIME scalar — the loop
  body is one compiled computation per microshard shape at EVERY world
  size (a static k=1 loop would be inlined and re-fused), so the
  per-microshard loss/grads/BN-stats are the same values whether a rank
  runs 1, 2, or 4 iterations;
* the PRNG key for microshard m = r*k + j folds the batch index first and
  the GLOBAL microshard index second — never the mesh position — so the
  augmentation stream is a function of canonical data position only;
* BatchNorm normalizes with MICROSHARD-local statistics (batch B/S),
  identical at every world size;
* per-microshard results are ``lax.all_gather``-ed over the data axis
  (deterministic rank order → global microshard order) and combined with
  a fixed pairwise binary tree (``x[0::2] + x[1::2]`` until one row
  remains, then / S) — one float summation order, regardless of M;
* the combined (replicated) gradient drives one SGD update per batch.

The gradient all-gather costs S× the allreduce bandwidth of the standard
programs — that is the price of a pinned trajectory, and it is why this is
a separate opt-in window rather than a change to the default step.  The
residual empirical assumption (XLA lowers the loop body identically across
runtime trip counts) is exactly what the world 1→2→4 CI pin checks.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..data import augment as aug
from ..ops import sgd
from ..ops.loss import cross_entropy
from ..parallel.mesh import DATA_AXIS
from ..train.step import TrainState, maybe_cast, shard_map


def tree_combine_mean(x: jax.Array) -> jax.Array:
    """Mean over the leading axis with a FIXED pairwise summation tree.

    ``x`` has leading dim S (power of two).  Plain ``jnp.mean`` would let
    XLA pick a reduction order that may differ between program variants;
    the explicit tree pins one order: (((x0+x1)+(x2+x3))...)/S.
    """
    s = x.shape[0]
    if s & (s - 1):
        raise ValueError(f"tree combine needs a power-of-two count, got {s}")
    while x.shape[0] > 1:
        x = x[0::2] + x[1::2]
    return x[0] / s


def make_elastic_train_window(apply_fn: Callable, mesh: Mesh,
                              cfg: sgd.SGDConfig = sgd.SGDConfig(), *,
                              microshards: int,
                              augment: bool = True,
                              compute_dtype=None) -> Callable:
    """Build the strong-scaling windowed train program.

    window(state, key, epoch_images[NB,B,...], epoch_labels[NB,B],
           start, length_arr) -> (state, losses[W])

    Same contract as ``make_train_window`` (epoch arrays device-resident,
    W = length_arr.shape[0] static, state donated), but the batch axis B
    is decomposed into ``microshards`` and the gradient reduction is the
    fixed gather+tree combine described in the module docstring.  The
    gradient-sync *strategy* is intentionally absent: the combine IS the
    reduction, and it must not vary with the strategy or the world.
    ``augment`` is True/False only — the host-augment path shards work by
    mesh position and cannot be world-invariant.
    """
    if augment == "host":
        raise ValueError("elastic strong scaling requires on-device "
                         "augmentation (host streams are rank-shaped)")
    world = int(mesh.devices.size)
    s = int(microshards)
    if s < 1 or (s & (s - 1)):
        raise ValueError(f"microshards must be a power of two, got {s}")
    if s % world:
        raise ValueError(f"microshards {s} not divisible by world {world} "
                         "— this world size cannot run the pinned program")
    k = s // world  # microshards per rank

    def window_body(params, bn_state, opt_state, key, epoch_images,
                    epoch_labels, start, length_arr, k_dyn):
        w = length_arr.shape[0]
        imgs = lax.dynamic_slice_in_dim(epoch_images, start, w, axis=0)
        labs = lax.dynamic_slice_in_dim(epoch_labels, start, w, axis=0)
        idxs = start + jnp.arange(w, dtype=jnp.int32)
        rank = lax.axis_index(DATA_AXIS)

        def one(carry, xs):
            params, bn_state, opt_state, key = carry
            images, labels, idx = xs  # local slice: [B/M, ...]
            # Canonical elastic fold order: batch index first, GLOBAL
            # microshard index second (inside the loop below).  The mesh
            # position never enters the stream — rank r merely evaluates
            # the microshards it happens to hold.
            bkey = jax.random.fold_in(key, idx)
            mb = images.shape[0] // k
            imgs_k = images.reshape((k, mb) + images.shape[1:])
            labs_k = labels.reshape((k, mb))
            losses0 = jnp.zeros((k,), jnp.float32)
            grads0 = jax.tree.map(
                lambda a: jnp.zeros((k,) + a.shape, a.dtype), params)
            bns0 = jax.tree.map(
                lambda a: jnp.zeros((k,) + a.shape, a.dtype), bn_state)

            def micro(j, acc):
                losses_k, grads_k, bns_k = acc
                mimgs = lax.dynamic_index_in_dim(imgs_k, j, keepdims=False)
                mlabs = lax.dynamic_index_in_dim(labs_k, j, keepdims=False)
                mk = jax.random.fold_in(bkey, rank * k + j)
                # Fence the per-microshard math off from its k-shaped
                # surroundings (the [k,...] stacking buffers): inside the
                # barriers the computation depends only on microshard-shaped
                # values, so it lowers identically at every world size.
                mimgs, mlabs, mk = lax.optimization_barrier(
                    (mimgs, mlabs, mk))
                x = aug.augment(mk, mimgs) if augment else aug.normalize(
                    mimgs)
                x = maybe_cast(x, compute_dtype)

                def loss_fn(p):
                    logits, new_bn = apply_fn(p, bn_state, x, train=True)
                    return cross_entropy(logits, mlabs), new_bn

                (loss, new_bn), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                loss, grads, new_bn = lax.optimization_barrier(
                    (loss, grads, new_bn))
                upd = lambda buf, v: lax.dynamic_update_index_in_dim(
                    buf, v, j, 0)
                return (upd(losses_k, loss), jax.tree.map(upd, grads_k, grads),
                        jax.tree.map(upd, bns_k, new_bn))

            # The trip count is k at every call — but it is passed as a
            # RUNTIME scalar (``k_dyn``), not baked into the loop, so XLA
            # cannot simplify the k=1 (world == S) case into straight-line
            # code.  An inlined body is re-fused with its surroundings and
            # lowers differently than the same body inside a while loop —
            # observed as 1-ulp drift in the BN running-var aux — so every
            # world size must run the SAME loop-shaped program.
            losses_k, grads_k, bns_k = lax.fori_loop(
                0, k_dyn, micro, (losses0, grads0, bns0))
            # [k, ...] per rank -> [S, ...] in global microshard order
            # (tiled all_gather concatenates in rank order, and rank r's
            # microshards are exactly m = r*k .. r*k+k-1, in order).
            gather = partial(lax.all_gather, axis_name=DATA_AXIS, axis=0,
                             tiled=True)
            losses_s, grads_s, bns_s = jax.tree.map(
                gather, (losses_k, grads_k, bns_k))
            loss = tree_combine_mean(losses_s)
            grads = jax.tree.map(tree_combine_mean, grads_s)
            new_bn = jax.tree.map(tree_combine_mean, bns_s)
            new_params, new_opt = sgd.update(params, grads, opt_state, cfg)
            return (new_params, new_bn, new_opt, key), loss

        (p, bn, opt, _), losses = lax.scan(
            one, (params, bn_state, opt_state, key), (imgs, labs, idxs))
        return p, bn, opt, losses

    # check_vma=False, unlike every program in train/step.py: this
    # window's reduction is a tiled all_gather + fixed-order tree, whose
    # result is replicated by construction but device-varying by TYPE (the
    # public all_gather has no invariant form), so the P() out_specs cannot
    # pass the varying-axes check.  Without the check, in-body jax.grad of
    # the replicated params is shard-local (no auto-psum), which is what
    # the explicit combine needs; the bitwise world 1/2/4 test is the proof.
    mapped = shard_map(
        window_body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(None, DATA_AXIS), P(None, DATA_AXIS),
                  P(), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def window_impl(state: TrainState, key, epoch_images, epoch_labels,
                    start, length_arr, k_dyn):
        p, bn, opt, losses = mapped(
            state.params, state.bn_state, state.opt_state, key,
            epoch_images, epoch_labels, start, length_arr, k_dyn)
        return TrainState(p, bn, opt), losses

    # k is fed as a runtime argument (see window_body) — same public
    # contract as make_train_window, including .lower for AOT warmup.
    k_arr = jnp.int32(k)

    def window(state: TrainState, key, epoch_images, epoch_labels, start,
               length_arr):
        return window_impl(state, key, epoch_images, epoch_labels, start,
                           length_arr, k_arr)

    window.lower = lambda *args: window_impl.lower(*args, k_arr)
    return window
