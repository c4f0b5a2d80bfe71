"""Compiled SPMD train / eval steps.

The reference's per-batch loop body (zero_grad -> forward -> CE loss ->
backward -> [grad sync] -> SGD step; ``/root/reference/src/Part 2a/main.py:
86-96``) becomes ONE jitted ``shard_map`` program over the data-parallel mesh:
the batch arrives sharded on the "data" axis, the gradient-sync strategy is a
collective pattern between ``jax.grad`` and the optimizer update, and
parameters/optimizer state stay replicated.  Augmentation (pad-crop/flip) and
normalization run on device inside the same program, so the host only moves
uint8 bytes.

BatchNorm: training normalizes with the *local shard's* batch statistics —
exactly the reference's per-replica BN semantics (SURVEY.md §7).  Running
stats are pmean'd across shards before being stored so the replicated state
invariant holds; this only affects evaluation and is documented in
BASELINE.md.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..data import augment as aug
from ..ft import guard as ftguard
from ..ops import sgd
from ..ops.loss import cross_entropy
from .. import parallel
from ..parallel.mesh import DATA_AXIS

# Every shard_map here runs with jax's varying-manual-axes (VMA) type check
# ON (``check_vma`` left at its default): a ``P()`` out_spec is then a
# static proof that the value is replicated — the invariant data parallelism
# rests on (params, BN stats and momentum identical on every position) —
# instead of an unchecked promise.  The price: loop carries must enter with
# the varying-ness they leave with, and in-body ``jax.grad`` w.r.t.
# replicated params must see a varying view or it pre-reduces the grads
# (``pvary``, below).  The one program that opts out, and why, is
# elastic/step_elastic.py.


def pvary(x: jax.Array) -> jax.Array:
    """Mark a replicated value device-varying over the data axis."""
    return lax.pcast(x, DATA_AXIS, to="varying")


def maybe_cast(x: jax.Array, compute_dtype) -> jax.Array:
    """Cast activations to the compute dtype (None = keep f32)."""
    return x.astype(compute_dtype) if compute_dtype else x


def _prepare(augment, key, images):
    """The train input transform, by mode:

    ``True``   — device-side pad-crop/flip/normalize (the default: uint8 in,
                 the whole transform fused into the step's XLA program);
    ``False``  — device-side normalize only (uint8 in, augmentation off);
    ``"host"`` — images arrive PREPROCESSED (f32, already augmented and
                 normalized by the C++ host pipeline, data/native.py — the
                 reference's DataLoader-worker model); pass through.
    """
    if augment == "host":
        return images
    return aug.augment(key, images) if augment else aug.normalize(images)


class ImageObjective:
    """What a model trains on, as the compiled steps below call it; this
    one is every image model's: crop/flip + normalize, cross-entropy, argmax
    counts.  A model that trains on something else brings its own
    (`apply_fn.objective`, models/sdar.py: token ids in, the masked
    block-diffusion loss), with the same methods and attributes.  What a
    configuration IS decides; no option switches it."""

    extras = ()             # per-step scalars besides the loss: (name, how
                            # shards and then steps combine: "sum" / "max")
    per_example = {}        # constants an example, tallied beside them
    gauges = ()             # (name, value, attrs) known when the model is
                            # built; a recorder gets each once
    eval_key = 0            # the evaluation draws nothing
    eval_dtypes = (jnp.float32, jnp.int32)      # loss sum, correct
    example_shape, example_dtype = (32, 32, 3), jnp.uint8
    stream = False          # one staged set, reused every epoch
    # Does the shard_map of this model's programs run the varying-axes
    # check?  (The note at the top.)
    checks_vma = True

    def prepare(self, key, images, augment, compute_dtype):
        return maybe_cast(_prepare(augment, key, images), compute_dtype)

    def loss(self, apply_fn, params, bn_state, x, labels, compute_dtype):
        """(loss, (new_bn, extras))."""
        logits, new_bn = apply_fn(params, bn_state, x, train=True)
        return cross_entropy(logits, labels), (new_bn, ())

    def eval_counts(self, apply_fn, params, bn_state, key, images, labels,
                    compute_dtype):
        x = maybe_cast(aug.normalize(images), compute_dtype)
        logits, _ = apply_fn(params, bn_state, x, train=False)
        return masked_eval_counts(logits, labels)


IMAGES = ImageObjective()


def objective_of(apply_fn):
    return getattr(apply_fn, "objective", IMAGES)


def fold_and_prepare(augment, compute_dtype, key, images, *, idx=None,
                     fold_axis=True, objective=IMAGES):
    """The ONE definition of the train input path's PRNG fold order and
    transform: fold the batch index first (when the caller passes one —
    the per-step path folds it on the host instead), the mesh position
    second, then the objective's own transform (`IMAGES`: prepare + cast;
    a decoder's: its noising, drawn from the same key).  Shared by the
    fused step, the train window and the forward-only window so the
    streams cannot drift apart (the phase split's validity depends on the
    forward window consuming bit-identical inputs to the train window)."""
    if idx is not None:
        key = jax.random.fold_in(key, idx)
    if fold_axis:
        key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
    return objective.prepare(key, images, augment, compute_dtype)


def _vary(objective):
    """`pvary` where the varying-axes check runs.  A decoder's programs opt
    out of it (`checks_vma = False`): its Pallas kernels (ops/attention.py,
    ops/moe.py) are library `pallas_call`s whose outputs carry no `vma`,
    which the check refuses, and marking those outputs varying by hand
    would be worse than no check: `pvary` transposes to a psum, so the
    backward pass would all-reduce every kernel's cotangent.  Without the
    check `pvary` is not needed: in-body jax.grad of the replicated params
    is shard-local (no auto-psum), and the strategy stays the only gradient
    reduction (tests/test_sdar.py trains on two devices against the plain
    reference)."""
    return pvary if objective.checks_vma else (lambda x: x)


def reduce_extras(objective, extras):
    """Combine the shards' extras over the data axis, each as it says."""
    ops = {"sum": lax.psum, "max": lax.pmax}
    return tuple(ops[how](e, DATA_AXIS)
                 for (_, how), e in zip(objective.extras, extras))


class TrainState(NamedTuple):
    params: Any
    bn_state: Any
    opt_state: sgd.SGDState


def init_train_state(init_fn, key: jax.Array, strategy=None,
                     world: int = 1) -> TrainState:
    """Seed-identical init on every process — the reference relies on
    identical seeds instead of a parameter broadcast (SURVEY.md C12); in SPMD
    the replicated init is constructed once and placed on all devices, making
    that invariant structural rather than probabilistic.

    A STATEFUL ``strategy`` (the compressed gradient-sync tiers,
    parallel/strategies.py) contributes its communication state — error
    feedback residuals, PowerSGD Q factors — to ``SGDState.comm``, stacked
    per worker for a ``world``-position mesh; stateless strategies leave
    ``comm`` None and the pytree identical to the pre-compression layout."""
    params, bn_state = init_fn(key)
    opt = sgd.init(params)
    if strategy is not None and getattr(strategy, "stateful", False):
        opt = opt._replace(comm=strategy.init_comm(params, world))
    return TrainState(params=params, bn_state=bn_state, opt_state=opt)


def apply_strategy(strategy, grads, axis_name, comm):
    """Run the gradient-sync strategy, threading communication state.

    Stateful strategies are ``(grads, axis, comm) -> (grads, comm')``;
    stateless ones are ``(grads, axis) -> grads`` and pass ``comm``
    through untouched.  The ONE dispatch point, so every execution path
    (fused step, train window, host window) threads identically."""
    if getattr(strategy, "stateful", False):
        return strategy(grads, axis_name, comm)
    return strategy(grads, axis_name), comm


def _opt_specs(strategy):
    """shard_map partition specs for the optimizer state: everything
    replicated except a stateful strategy's comm state, which is per-worker
    — stacked on a leading mesh axis and sharded over DATA_AXIS so each
    position carries only its own residual/factor slice (the global array
    a checkpoint sees is the (world, ...) stack)."""
    if not getattr(strategy, "stateful", False):
        return P()
    return sgd.SGDState(momentum=P(), comm=P(DATA_AXIS))


def _guarded_update(params, bn_state, opt_state, grads, cfg, loss, new_bn,
                    staged_opt=None):
    """The non-finite-guarded tail of a train step: one finiteness scalar
    decides, branch-free, between the SGD update and keeping the ENTIRE
    prior state (params, BN stats, momentum) — see ft/guard.py.

    ``staged_opt`` (compressed strategies) is the optimizer state with the
    strategy's freshly-written comm state: the update branch applies it,
    while the keep branch restores ``opt_state`` — the PRE-sync comm —
    so a non-finite step leaves no poisoned residuals behind."""
    ok = ftguard.finite_ok(loss, grads)
    upd_params, upd_opt = sgd.update(
        params, grads, opt_state if staged_opt is None else staged_opt, cfg)
    return (ftguard.select_update(ok, upd_params, params),
            ftguard.select_update(ok, new_bn, bn_state),
            ftguard.select_update(ok, upd_opt, opt_state), ok)


def make_train_step(apply_fn: Callable, strategy: parallel.strategies.Strategy,
                    mesh: Mesh, cfg: sgd.SGDConfig = sgd.SGDConfig(),
                    *, augment: bool = True, compute_dtype=None,
                    nonfinite_guard: bool = False,
                    inject_nonfinite: bool = False) -> Callable:
    """Build the jitted train step.

    step(state, key, images[B,32,32,3], labels[B]) -> (state, loss)
    with B = global batch, sharded over the mesh's "data" axis; images are
    uint8 (``augment`` True/False: transform on device) or preprocessed
    float32 (``augment="host"`` — see ``_prepare``).

    ``nonfinite_guard`` compiles in the finiteness check + branch-free
    conditional update (ft/guard.py) and the step returns an extra
    replicated ``ok`` scalar: (state, loss, ok).  ``inject_nonfinite``
    (chaos only) unconditionally poisons the gradients with NaN — the
    Trainer swaps this variant in for exactly one batch.  Both default
    off, leaving the program identical to the unguarded build.

    The ``local`` strategy (reference Part 1: single process, no process
    group — ``/root/reference/src/Part 1/main.py``) compiles WITHOUT
    shard_map or any axis: a plain jitted step, the degenerate world-size-1
    case, exactly as Part 1 carries no torch.distributed code.
    """
    objective = objective_of(apply_fn)
    if strategy is parallel.strategies.local:
        if mesh.devices.size != 1:
            raise ValueError("'single' strategy requires a 1-device mesh "
                             "(reference Part 1 is world_size==1)")

        @jax.jit
        def single_step(state: TrainState, key, images, labels):
            x = fold_and_prepare(augment, compute_dtype, key, images,
                                 fold_axis=False, objective=objective)

            def loss_fn(p):
                return objective.loss(apply_fn, p, state.bn_state, x, labels,
                                      compute_dtype)

            (loss, (new_bn, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            if inject_nonfinite:
                grads = ftguard.inject_nan(grads)
            if nonfinite_guard:
                p, bn, opt, ok = _guarded_update(
                    state.params, state.bn_state, state.opt_state, grads,
                    cfg, loss, new_bn)
                return TrainState(p, bn, opt), loss, ok
            new_params, new_opt = sgd.update(state.params, grads,
                                             state.opt_state, cfg)
            return TrainState(new_params, new_bn, new_opt), loss

        return single_step

    def shard_body(params, bn_state, opt_state, key, images, labels):
        # Distinct augmentation stream per shard, deterministic in (key, pos);
        # the batch index is folded on the host by the per-step caller.
        x = fold_and_prepare(augment, compute_dtype, key, images,
                             objective=objective)

        def loss_fn(p):
            return objective.loss(apply_fn, p, bn_state, x, labels,
                                  compute_dtype)

        # Differentiate w.r.t. a device-VARYING view of the replicated
        # params: shard_map autodiff auto-psums the cotangent of an
        # invariant input (the transpose of broadcast is reduce), which
        # would pre-reduce the grads and leave the strategy's own collective
        # double-counting by a factor of world.  pcast-to-varying keeps the
        # grads genuinely shard-local so the strategy below is the ONLY
        # gradient reduction — its collective pattern, exactly once.
        params_var = jax.tree.map(_vary(objective), params)
        (loss, (new_bn, _)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params_var)
        if inject_nonfinite:
            # Poison BEFORE the gradient sync — a real overflow is born on
            # a shard and spreads through the collective, and so must the
            # injected one.
            grads = ftguard.inject_nan(grads)
        grads, new_comm = apply_strategy(strategy, grads, DATA_AXIS,
                                         opt_state.comm)
        staged_opt = opt_state._replace(comm=new_comm)
        new_bn = jax.tree.map(lambda a: lax.pmean(a, DATA_AXIS), new_bn)
        loss = lax.pmean(loss, DATA_AXIS)
        if nonfinite_guard:
            return _guarded_update(params, bn_state, opt_state, grads, cfg,
                                   loss, new_bn,
                                   staged_opt=staged_opt) + (loss,)
        new_params, new_opt = sgd.update(params, grads, staged_opt, cfg)
        return new_params, new_bn, new_opt, loss

    opt_spec = _opt_specs(strategy)
    out_specs = ((P(), P(), opt_spec, P(), P()) if nonfinite_guard
                 else (P(), P(), opt_spec, P()))
    mapped = shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(), opt_spec, P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=out_specs, check_vma=objective.checks_vma,
    )

    if nonfinite_guard:
        @jax.jit
        def guarded_step(state: TrainState, key, images, labels):
            p, bn, opt, ok, loss = mapped(
                state.params, state.bn_state, state.opt_state, key, images,
                labels)
            return TrainState(p, bn, opt), loss, ok

        return guarded_step

    @jax.jit
    def step(state: TrainState, key, images, labels):
        new_params, new_bn, new_opt, loss = mapped(
            state.params, state.bn_state, state.opt_state, key, images, labels)
        return TrainState(new_params, new_bn, new_opt), loss

    return step


def _ring_row(buf, cnt, loss, grads, ok, idx, extras=()):
    """Append one (loss, grad sqnorm, ok, step marker) row to the metric
    ring inside the scanned body (obs/ringbuf.py).  The sqnorm is computed
    on the POST-sync grads, so the write is replicated and the ring can
    carry a replicated out-spec; the loss value is the same tensor the
    non-ring path stacks into ys — observation only, bitwise-inert.
    `extras`: an objective's per-step scalars, in the wider row its
    trainer allocates."""
    from ..obs import ringbuf
    gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(grads))
    return ringbuf.ring_write((buf, cnt), (loss, gsq, ok, idx) + extras)


def make_train_window(apply_fn: Callable,
                      strategy: parallel.strategies.Strategy, mesh: Mesh,
                      cfg: sgd.SGDConfig = sgd.SGDConfig(),
                      *, augment: bool = True,
                      compute_dtype=None,
                      nonfinite_guard: bool = False,
                      nonfinite_chaos_steps=(),
                      metrics_ring: bool = False) -> Callable:
    """Windowed train step: W iterations per dispatch via ``lax.scan``.

    window(state, key, epoch_images[NB,B,32,32,3], epoch_labels[NB,B],
           start, length_arr) -> (state, losses[W])

    where W = length_arr.shape[0] (static per compile), ``start`` is the
    first batch index (dynamic), and the epoch arrays stay RESIDENT on
    device across calls.  Rationale: per-call dispatch and host->device
    transfer carry fixed costs that dwarf VGG's ~6 ms of compute per batch,
    so the framework amortizes one dispatch over a full 20-iteration
    reporting window — the granularity the reference itself reports at
    (``/root/reference/src/Part 1/main.py:47-57``).  State buffers are
    donated (the optimizer update is in-place in XLA terms).

    ``nonfinite_guard`` adds the per-iteration finiteness check + select
    (ft/guard.py); the window then returns (state, losses[W], oks[W]).
    ``nonfinite_chaos_steps`` (static ints, chaos only) poisons gradients
    with NaN at those ABSOLUTE batch indices — the scan folds the absolute
    index, so one compiled program injects at exactly the planned batches
    regardless of window boundaries.  Both default off/empty: the program
    is identical to the unguarded build.

    ``metrics_ring`` swaps the per-step ys for a device-resident metric
    ring (obs/ringbuf.py) carried through the scan and DONATED alongside
    the state:

    window(state, ring, key, epoch_images, epoch_labels, start,
           length_arr) -> (state, ring)

    The scanned body writes one (loss, grad sqnorm, ok, step) row per
    iteration via dynamic-update-slice; the host drains the ring once per
    window instead of fetching stacked ys — same loss values, one fetch.
    """
    chaos_steps = tuple(int(s) for s in nonfinite_chaos_steps)
    objective = objective_of(apply_fn)

    def scan_one(apply_fn, strategy_fn, axis_ok):
        def one(carry, xs):
            if metrics_ring:
                params, bn_state, opt_state, key, buf, cnt = carry
            else:
                params, bn_state, opt_state, key = carry
            images, labels, idx = xs
            # Canonical fold order across ALL execution paths (see
            # fold_and_prepare): batch index first, mesh position second —
            # the per-step path folds the iteration on the host (loop.py)
            # and the position in make_train_step, so the windowed and
            # per-step paths consume identical augmentation streams.
            x = fold_and_prepare(augment, compute_dtype, key, images,
                                 idx=idx, fold_axis=axis_ok,
                                 objective=objective)

            def loss_fn(p):
                return objective.loss(apply_fn, p, bn_state, x, labels,
                                      compute_dtype)

            # See make_train_step: differentiate w.r.t. a varying view so
            # the strategy is the only gradient reduction (no autodiff
            # psum of invariant-param cotangents double-counting it).
            diff_params = params if not axis_ok else jax.tree.map(
                _vary(objective), params)
            (loss, (new_bn, extras)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(diff_params)
            if chaos_steps:
                mask = (idx == chaos_steps[0])
                for s in chaos_steps[1:]:
                    mask = mask | (idx == s)
                grads = ftguard.inject_nan(grads, mask=mask)
            grads, new_comm = strategy_fn(grads, opt_state.comm)
            staged_opt = opt_state._replace(comm=new_comm)
            if axis_ok:
                new_bn = jax.tree.map(
                    lambda a: lax.pmean(a, DATA_AXIS), new_bn)
                loss = lax.pmean(loss, DATA_AXIS)
                if extras:
                    extras = reduce_extras(objective, extras)
            if nonfinite_guard:
                p, bn, opt, ok = _guarded_update(
                    params, bn_state, opt_state, grads, cfg, loss, new_bn,
                    staged_opt=staged_opt)
                if metrics_ring:
                    buf, cnt = _ring_row(buf, cnt, loss, grads, ok, idx,
                                         extras)
                    return (p, bn, opt, key, buf, cnt), None
                return (p, bn, opt, key), (loss, ok)
            new_params, new_opt = sgd.update(params, grads, staged_opt, cfg)
            if metrics_ring:
                buf, cnt = _ring_row(buf, cnt, loss, grads,
                                     jnp.float32(1.0), idx, extras)
                return (new_params, new_bn, new_opt, key, buf, cnt), None
            return (new_params, new_bn, new_opt, key), loss
        return one

    single = strategy is parallel.strategies.local

    def _scan(params, bn_state, opt_state, key, buf, cnt, epoch_images,
              epoch_labels, start, length_arr):
        w = length_arr.shape[0]
        imgs = lax.dynamic_slice_in_dim(epoch_images, start, w, axis=0)
        labs = lax.dynamic_slice_in_dim(epoch_labels, start, w, axis=0)
        idxs = start + jnp.arange(w, dtype=jnp.int32)
        one = scan_one(apply_fn,
                       (lambda g, c: (g, c)) if single
                       else (lambda g, c: apply_strategy(
                           strategy, g, DATA_AXIS, c)),
                       axis_ok=not single)
        carry = ((params, bn_state, opt_state, key, buf, cnt)
                 if metrics_ring else (params, bn_state, opt_state, key))
        return lax.scan(one, carry, (imgs, labs, idxs))

    if metrics_ring:
        def window_body(params, bn_state, opt_state, key, buf, cnt,
                        epoch_images, epoch_labels, start, length_arr):
            (p, bn, opt, _, buf, cnt), _ = _scan(
                params, bn_state, opt_state, key, buf, cnt, epoch_images,
                epoch_labels, start, length_arr)
            return p, bn, opt, buf, cnt
    else:
        def window_body(params, bn_state, opt_state, key, epoch_images,
                        epoch_labels, start, length_arr):
            (p, bn, opt, _), ys = _scan(
                params, bn_state, opt_state, key, None, None, epoch_images,
                epoch_labels, start, length_arr)
            if nonfinite_guard:
                losses, oks = ys
                return p, bn, opt, losses, oks
            return p, bn, opt, ys

    if single:
        if mesh.devices.size != 1:
            raise ValueError("'single' strategy requires a 1-device mesh")

        if metrics_ring:
            @partial(jax.jit, donate_argnums=(0, 1))
            def window(state: TrainState, ring, key, epoch_images,
                       epoch_labels, start, length_arr):
                out = window_body(
                    state.params, state.bn_state, state.opt_state, key,
                    ring[0], ring[1], epoch_images, epoch_labels, start,
                    length_arr)
                return TrainState(*out[:3]), (out[3], out[4])

            return window

        @partial(jax.jit, donate_argnums=(0,))
        def window(state: TrainState, key, epoch_images, epoch_labels,
                   start, length_arr):
            out = window_body(
                state.params, state.bn_state, state.opt_state, key,
                epoch_images, epoch_labels, start, length_arr)
            return (TrainState(*out[:3]),) + tuple(out[3:])

        return window

    opt_spec = _opt_specs(strategy)
    if metrics_ring:
        # The ring rows are written from replicated values (pmean'd loss,
        # post-sync grads), so the ring stays replicated like the state.
        mapped = shard_map(
            window_body, mesh=mesh,
            in_specs=(P(), P(), opt_spec, P(), P(), P(),
                      P(None, DATA_AXIS), P(None, DATA_AXIS), P(), P()),
            out_specs=(P(), P(), opt_spec, P(), P()),
            check_vma=objective.checks_vma,
        )

        @partial(jax.jit, donate_argnums=(0, 1))
        def window(state: TrainState, ring, key, epoch_images, epoch_labels,
                   start, length_arr):
            out = mapped(state.params, state.bn_state, state.opt_state, key,
                         ring[0], ring[1], epoch_images, epoch_labels,
                         start, length_arr)
            return TrainState(*out[:3]), (out[3], out[4])

        return window

    out_specs = ((P(), P(), opt_spec, P(), P()) if nonfinite_guard
                 else (P(), P(), opt_spec, P()))
    mapped = shard_map(
        window_body, mesh=mesh,
        in_specs=(P(), P(), opt_spec, P(), P(None, DATA_AXIS),
                  P(None, DATA_AXIS), P(), P()),
        out_specs=out_specs, check_vma=objective.checks_vma,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def window(state: TrainState, key, epoch_images, epoch_labels, start,
               length_arr):
        out = mapped(state.params, state.bn_state, state.opt_state, key,
                     epoch_images, epoch_labels, start, length_arr)
        return (TrainState(*out[:3]),) + tuple(out[3:])

    return window


def masked_eval_counts(logits: jax.Array, labels: jax.Array):
    """(loss_sum, correct) over valid examples; label -1 marks padding.

    Shared by the per-batch eval step and the scanned eval window so the
    masking/accounting semantics cannot drift apart."""
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)  # full-precision loss in bf16 mode
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    loss_sum = jnp.sum(jnp.where(valid, logz - picked, 0.0))
    correct = jnp.sum(valid & (jnp.argmax(logits, axis=-1) == safe))
    return loss_sum, correct


def make_eval_window(apply_fn: Callable, mesh: Mesh, *,
                     compute_dtype=None) -> Callable:
    """Whole-test-set evaluation in ONE dispatch: scan over [T,B,...] staged
    batches, psum counts across the mesh.  Returns the objective's counts
    over all valid (label >= 0) examples: (loss_sum, correct) for images; a
    decoder, evaluated on draws from its fixed key, adds what `correct` is
    out of (masked tokens)."""
    objective = objective_of(apply_fn)

    def scan_eval(params, bn_state, images, labels):
        def one(carry, xs):
            imgs, labs, t = xs
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(objective.eval_key), t),
                lax.axis_index(DATA_AXIS))
            counts = objective.eval_counts(apply_fn, params, bn_state, key,
                                           imgs, labs, compute_dtype)
            return tuple(a + b for a, b in zip(carry, counts)), None
        # Initial carry must already be marked device-varying (each shard
        # accumulates its own partial sums) for shard_map's VMA typing.
        vary = _vary(objective)
        init = tuple(vary(jnp.zeros((), dt)) for dt in objective.eval_dtypes)
        steps = jnp.arange(images.shape[0], dtype=jnp.int32)
        counts, _ = lax.scan(one, init, (images, labels, steps))
        return counts

    def shard_body(params, bn_state, images, labels):
        return tuple(lax.psum(c, DATA_AXIS)
                     for c in scan_eval(params, bn_state, images, labels))

    mapped = shard_map(shard_body, mesh=mesh,
                       in_specs=(P(), P(), P(None, DATA_AXIS),
                                 P(None, DATA_AXIS)),
                       out_specs=(P(),) * len(objective.eval_dtypes),
                       check_vma=objective.checks_vma)

    @jax.jit
    def evaluate(state: TrainState, images, labels):
        return mapped(state.params, state.bn_state, images, labels)

    return evaluate


def make_eval_step(apply_fn: Callable, mesh: Mesh, *,
                   compute_dtype=None) -> Callable:
    """Jitted eval step over a sharded batch.

    Returns (loss_sum, correct) summed over the GLOBAL batch via psum —
    reporting the same quantities as the reference's ``test_model``
    (``/root/reference/src/Part 1/main.py:61-76``) but computed once across
    the mesh instead of redundantly per rank.
    """

    def shard_body(params, bn_state, images, labels):
        x = maybe_cast(aug.normalize(images), compute_dtype)
        logits, _ = apply_fn(params, bn_state, x, train=False)
        # Reference accumulates per-batch mean CE; we return the per-example
        # sum so partial final batches stay exact, and divide on the host.
        # Padded examples are marked label = -1 and masked out (the final
        # test batch of 10000 % 256 = 16 examples stays exact this way).
        loss_sum, correct = masked_eval_counts(logits, labels)
        return (lax.psum(loss_sum, DATA_AXIS),
                lax.psum(correct, DATA_AXIS))

    mapped = shard_map(shard_body, mesh=mesh,
                       in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS)),
                       out_specs=(P(), P()))

    @jax.jit
    def step(state: TrainState, images, labels):
        return mapped(state.params, state.bn_state, images, labels)

    return step
