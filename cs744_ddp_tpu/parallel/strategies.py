"""The three gradient-synchronization strategies, as collective patterns.

Each strategy is a pure function ``(grads_pytree, axis_name) -> grads_pytree``
running *inside* a ``shard_map``-compiled SPMD program; the strategy
difference is the collective pattern XLA emits, mirroring the reference's
spectrum (SURVEY.md §2.3):

  * ``gather_scatter``  — reference Part 2a (``main.py:117-127``):
    per parameter, rank 0 gathers every worker's grad, means them, scatters
    the average back — one blocking gather + one blocking scatter per leaf,
    sequentially.  Here: a ROOT-EQUIVALENT COMM PATTERN WITH REPLICATED
    COMPUTE — per leaf, ``all_gather`` (a superset of gather-to-root on
    ICI), then the gathered stack is zeroed on every mesh position except 0
    before the mean, and the root's mean is broadcast via ``psum``.  In
    SPMD every position executes the (cheap) masked mean; what matches the
    reference's rank-0 bottleneck is the *communication* shape — two
    sequential collectives per leaf — which is the term that dominates its
    cost model.  Leaves are chained through ``optimization_barrier`` so the
    per-leaf collective pairs stay *sequential* in the compiled TPU
    program, preserving the deliberately-naive blocking-loop cost model
    for honest benchmarking.

  * ``per_param_psum``  — reference Part 2b (``main.py:116-119``):
    one blocking all-reduce per parameter leaf, sequentially, no fusion.
    Here: one ``lax.psum`` per leaf (34 collectives for VGG-11+BN), chained
    through ``optimization_barrier`` — without the chain XLA's all-reduce
    combiner would quietly rewrite this tier into the fused one, erasing
    the Part-2b/Part-3 cost distinction the reference exists to measure.

  * ``bucketed_psum``   — reference Part 3 (``DDP(model)``, ``main.py:61``):
    DDP's bucketed fused reducer.  torch materialises ~25 MB flat buffers
    because NCCL wants one contiguous launch; XLA's native fused form is
    the *variadic* all-reduce (exactly what its all-reduce combiner
    produces), so the TPU-native bucket is one multi-operand ``lax.psum``
    over the bucket's leaves — one fused collective per bucket with ZERO
    copy overhead (no flatten/concat/slice round-trip through HBM).
    Buckets are formed in reverse registration order (grads become ready
    last-layer-first) and chained bucket-to-bucket, mirroring DDP's single
    in-order comm stream; comm/compute overlap within the step belongs to
    XLA's latency-hiding scheduler.

  * ``local``           — reference Part 1: single process, no sync.

Round 9 extends the ladder past the reference (ROADMAP item 3) with an
overlap tier and three compressed tiers:

  * ``overlapped_ddp``  — the ddp bucket plan WITHOUT the inter-bucket
    barrier chain: each bucket's fused all-reduce is gated only by its own
    gradients (bucketing.make_schedule), so comm overlaps the remaining
    backward (torch DDP's backward-hook launches).
  * ``CompressedPsum``  — bf16/int8 quantized all-reduce with per-worker
    error-feedback residuals carried in the optimizer state (>=2x / >=4x
    fewer collective bytes; audit-certified).
  * ``PowerSGD``        — rank-r low-rank factor all-reduce with warm-started
    Q and error feedback (>=8x on VGG-11's conv/fc leaves at rank 4);
    non-matrix leaves ride the bf16 path.

The compressed tiers are STATEFUL: callables with ``stateful = True``
whose ``init_comm(params_like, world)`` state (residuals, Q factors)
lives in ``SGDState.comm``, stacked per worker on a leading mesh axis and
sharded over the data axis through every compiled program — see
train/step.py (threading) and train/checkpoint.py (bitwise resume).

XLA note: the barrier chains are what keep the tiers *observably distinct
in the compiled TPU program* (SURVEY.md §7 "hard parts"): on the v5e-8
lowering, ``allreduce`` compiles to one all-reduce per leaf while ``ddp``
compiles to bucket-count fused all-reduces (asserted in
tests/test_tpu_aot.py).  The CPU backend used by the unit tests strips
optimization barriers and combines everything — there the tiers are
asserted distinct at the StableHLO level instead
(tests/test_strategies.py), and their wall-clock converges, which is also
asserted: the fused tier must never LOSE to the per-param tier.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .bucketing import (BucketPlan, DEFAULT_BUCKET_BYTES, make_plan,
                        make_schedule)

Strategy = Callable[[Any, str], Any]

# Low-rank compression rank (PowerSGD --compress-rank default): rank 4 is
# the paper's sweet spot for conv nets (Vogels et al. 2019, table 2) and
# what the >=8x byte contract in analysis/audit.py is certified at.
DEFAULT_COMPRESS_RANK = 4


def _after(x, dep):
    """Order ``x``'s consumers after ``dep`` (sequential-collective chains).

    ``optimization_barrier`` makes ``x`` data-depend on ``dep``, so the
    collective fed by ``x`` cannot start — nor be combiner-merged — before
    the collective that produced ``dep`` completes, reproducing the
    reference's blocking per-parameter loops in compiled form."""
    if dep is None:
        return x
    x, _ = lax.optimization_barrier((x, dep))
    return x


def local(grads: Any, axis_name: str) -> Any:
    """No synchronization (single-worker Part-1 semantics)."""
    del axis_name
    return grads


def per_param_psum(grads: Any, axis_name: str) -> Any:
    """One all-reduce per leaf, sequentially; sum / world (Part 2b parity).

    ``world`` is the STATIC axis size: a ``psum(1)`` spelling would add a
    collective and distort the strategy spectrum."""
    world = lax.axis_size(axis_name)
    leaves, treedef = jax.tree.flatten(grads)
    out: List[Any] = []
    prev = None
    for g in leaves:
        s = lax.psum(_after(g, prev), axis_name)
        out.append(s / world)
        prev = s
    return jax.tree.unflatten(treedef, out)


def gather_scatter(grads: Any, axis_name: str) -> Any:
    """Part 2a parity: root-equivalent comm pattern, replicated compute.

    Two sequential collectives per leaf (all_gather, then psum of the
    root-masked mean) reproduce the reference's gather->mean->scatter
    communication cost; the masked mean itself runs on every position
    (SPMD), not only on the root — see the module docstring."""
    idx = lax.axis_index(axis_name)
    leaves, treedef = jax.tree.flatten(grads)
    out: List[Any] = []
    prev = None
    for g in leaves:
        gathered = lax.all_gather(_after(g, prev), axis_name)  # collective 1
        # Mask BEFORE the mean: non-root positions reduce zeros, so the
        # mean that survives the psum is computed at mesh position 0 only —
        # root-located compute, like rank 0's torch.mean in the reference.
        rooted = jnp.where(idx == 0, gathered, jnp.zeros_like(gathered))
        mean = jnp.mean(rooted, axis=0)
        s = lax.psum(mean, axis_name)                          # collective 2
        out.append(s)
        prev = s
    return jax.tree.unflatten(treedef, out)


def bucketed_psum(grads: Any, axis_name: str, *,
                  plan: Optional[BucketPlan] = None,
                  bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> Any:
    """Bucketed fused all-reduce — the DDP-equivalent performance tier.

    One variadic ``psum`` per bucket: XLA lowers the multi-operand reduce
    to a single fused all-reduce (its combiner's own canonical form), so
    each bucket costs exactly one collective and no data movement beyond
    the wire transfer itself."""
    if plan is None:
        plan = make_plan(grads, bucket_bytes)
    world = lax.axis_size(axis_name)
    leaves = jax.tree.leaves(grads)
    out: List[Any] = [None] * len(leaves)
    prev = ()
    for bucket in plan.buckets:
        gs = tuple(leaves[i] for i in bucket)
        if prev:
            # Chain on the WHOLE previous bucket: every one of this
            # bucket's reduces must follow every one of the previous
            # bucket's, or the combiner could legally merge collectives
            # across the bucket boundary.
            gs = lax.optimization_barrier(gs + prev)[:len(gs)]
        reduced = lax.psum(gs, axis_name)
        for i, r in zip(bucket, reduced):
            out[i] = r / world
        prev = tuple(reduced)
    return jax.tree.unflatten(plan.treedef, out)


def overlapped_ddp(grads: Any, axis_name: str, *,
                   plan: Optional[BucketPlan] = None,
                   bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> Any:
    """Bucketed fused all-reduce with NO cross-bucket ordering — the
    overlap tier (torch DDP's backward-hook launches, ROADMAP item 3a).

    Same bucket plan and one variadic ``psum`` per bucket as
    ``bucketed_psum``, but the inter-bucket ``optimization_barrier`` chain
    is gone: each bucket's collective depends only on its own gradients
    (its gate leaf, bucketing.make_schedule), so XLA's latency-hiding
    scheduler is free to issue bucket k's all-reduce while the backward
    for earlier layers is still computing — comm overlaps compute instead
    of forming a single post-backward chain.  Certified statically by
    analysis/audit.py's overlap rule: same fused-collective count as the
    ddp tier, collective chain depth 1 (no collective consumes another's
    result), and at least one collective whose operand cone excludes part
    of the backward (it can start before backward finishes)."""
    if plan is None:
        plan = make_plan(grads, bucket_bytes)
    sched = make_schedule(plan)
    world = lax.axis_size(axis_name)
    leaves = jax.tree.leaves(grads)
    out: List[Any] = [None] * len(leaves)
    for b in sched.order:
        gs = tuple(leaves[i] for i in plan.buckets[b])
        reduced = lax.psum(gs, axis_name)
        for i, r in zip(plan.buckets[b], reduced):
            out[i] = r / world
    return jax.tree.unflatten(plan.treedef, out)


def _stack_zeros_like(params_like: Any, world: int) -> Any:
    """Per-worker f32 state stacked on a leading mesh axis: the global
    array is (world, *leaf.shape), carried in the optimizer state and
    sharded P(DATA_AXIS) through the compiled programs (train/step.py
    _opt_specs) so each mesh position reads and writes only its own
    slice — error-feedback residuals are genuinely per-worker."""
    return jax.tree.map(
        lambda p: jnp.zeros((world,) + tuple(p.shape), jnp.float32),
        params_like)


def _local(comm_leaf):
    """A worker's own slice of stacked per-worker comm state (the leading
    mesh axis arrives sharded, so the local block is (1, ...))."""
    return comm_leaf[0]


class CompressedPsum:
    """bf16 / int8 quantized all-reduce with error feedback — ROADMAP 3b.

    Per leaf: ``v = g + residual``; quantize ``v``; all-reduce the
    QUANTIZED values (that is the whole point: the wire carries 2 bytes
    (bf16) or 1 byte (int8) per element instead of 4); dequantize the sum;
    the new residual is ``v - dequant(quant(v))`` — the part this worker
    failed to transmit, re-injected next step so quantization error
    accumulates into the trajectory instead of being lost (Deep Gradient
    Compression / EF-SGD; PAPERS.md).  Residuals are per-worker state in
    the optimizer pytree (``init_comm``), so checkpoints carry them and
    preemption resume stays bitwise (tests/test_ft.py).

    int8 needs a shared scale: per-leaf |v|-maxima are packed into ONE
    vector and pmax'd (a single extra scalar-vector collective), then each
    worker quantizes to ``clip(round(v / scale), -L, L)`` with ``L =
    127 // world`` and ``scale = amax / L`` — per-worker wire values stay
    within +-L, so the summed int8 wire value is bounded by world * L <=
    127 and cannot overflow (a bare ``round`` at scale amax*world/127
    would: world workers at +amax round to world * round(127/world) =
    128 > 127 at world 8, wrapping the sum negative).  Clipped mass lands
    in the residual like any other quantization error.  Worlds beyond 127
    would need a wider wire type; every mesh here is far below that.

    Called with ``comm=None`` (the elastic tail path, where the window's
    fixed-tree combine owns the reduction and no residual state is
    threaded), compression still applies but error feedback is off —
    documented degradation, not an error.
    """

    stateful = True

    def __init__(self, qdtype: str = "bf16"):
        if qdtype not in ("bf16", "int8"):
            raise ValueError(f"qdtype must be bf16 or int8, got {qdtype!r}")
        self.qdtype = qdtype

    @property
    def name(self) -> str:
        return f"compress-{self.qdtype}"

    def init_comm(self, params_like: Any, world: int) -> Any:
        return {"residual": _stack_zeros_like(params_like, world)}

    def __call__(self, grads: Any, axis_name: str, comm: Any = None):
        world = lax.axis_size(axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        if comm is None:
            vs = [g.astype(jnp.float32) for g in leaves]
        else:
            rs = jax.tree.leaves(comm["residual"])
            vs = [g.astype(jnp.float32) + _local(r)
                  for g, r in zip(leaves, rs)]

        prev = None
        limit = max(1, 127 // world)
        if self.qdtype == "int8":
            # One packed pmax shares every leaf's scale (see class doc).
            amax = jnp.stack([jnp.max(jnp.abs(v)) for v in vs])
            amax = lax.pmax(amax, axis_name)
            scales = jnp.where(amax > 0.0, amax / limit, 1.0)
            prev = scales

        out: List[Any] = []
        new_rs: List[Any] = []
        for i, (g, v) in enumerate(zip(leaves, vs)):
            if self.qdtype == "bf16":
                q = _after(v, prev).astype(jnp.bfloat16)
                s = lax.psum(q, axis_name)
                sent = q.astype(jnp.float32)
                avg = s.astype(jnp.float32) / world
                prev = s
            else:
                q = jnp.clip(jnp.round(_after(v, prev) / scales[i]),
                             -limit, limit).astype(jnp.int8)
                s = lax.psum(q, axis_name)
                sent = q.astype(jnp.float32) * scales[i]
                avg = s.astype(jnp.float32) * scales[i] / world
                prev = s
            out.append(avg.astype(g.dtype))
            new_rs.append((v - sent)[None])
        new_comm = None if comm is None else {
            "residual": jax.tree.unflatten(treedef, new_rs)}
        return jax.tree.unflatten(treedef, out), new_comm


def _orthonormalize(p: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Deterministic modified Gram-Schmidt over the (few) columns of a
    tall matrix; replicated inputs give bitwise-replicated outputs (no
    pivoting, no randomized algorithm).

    A column that is numerically inside the span of the earlier ones is
    DROPPED to zero, not normalized: after the cancellation the remainder
    is amplified rounding noise with a large component along the earlier
    columns, and normalizing it would double-count those directions in
    the ``P @ Q'^T`` reconstruction (a rank-deficient gradient would come
    back scaled ~k x, k the column multiplicity)."""
    cols = []
    for i in range(p.shape[1]):
        c = p[:, i]
        ref = jnp.linalg.norm(c)
        for u in cols:
            c = c - jnp.dot(u, c) * u
        n = jnp.linalg.norm(c)
        keep = n > jnp.maximum(ref * 1e-5, eps)
        c = jnp.where(keep, c / jnp.where(keep, n, 1.0), 0.0)
        cols.append(c)
    return jnp.stack(cols, axis=1)


class PowerSGD:
    """Low-rank gradient compression (Vogels et al. 2019) — ROADMAP 3b.

    Per matrix leaf (reshaped to (m, n) = (prod(shape[:-1]), shape[-1])):
    all-reduce the rank-r factors ``P = mean(M @ Q)`` and ``Q' = mean(M^T
    @ P)`` instead of M itself — r(m+n) wire floats instead of m*n, >=8x
    for VGG-11's conv/fc leaves at the default rank 4.  P is
    orthonormalized (modified Gram-Schmidt, deterministic) before the
    back-projection; Q is warm-started across steps in the comm state, so
    the power iteration converges over the run.  The decompressed update
    is ``P @ Q'^T`` (replicated: both factors come out of psums); error
    feedback keeps ``M - P @ Q'^T`` per worker, like CompressedPsum.

    Leaves where low-rank doesn't pay — vectors (biases, BN scales) and
    matrices with r(m+n) >= m*n — fall back to the bf16 compressed path
    inline.  Q's cold start is a fixed-key normal draw per leaf, identical
    on every worker (and across runs: the key depends only on the leaf
    index), so the whole strategy is deterministic.
    """

    stateful = True
    name = "powersgd"

    def __init__(self, rank: int = DEFAULT_COMPRESS_RANK):
        if rank < 1:
            raise ValueError(f"compress rank must be >= 1, got {rank}")
        self.rank = int(rank)

    def _low_rank(self, shape) -> bool:
        if len(shape) < 2:
            return False
        m = 1
        for d in shape[:-1]:
            m *= int(d)
        n = int(shape[-1])
        return self.rank * (m + n) < m * n

    def _q_init(self, i: int, n: int) -> jax.Array:
        key = jax.random.fold_in(jax.random.PRNGKey(0x9D5C), i)
        return jax.random.normal(key, (n, self.rank), jnp.float32)

    def init_comm(self, params_like: Any, world: int) -> Any:
        leaves = jax.tree.leaves(params_like)
        qs = {}
        for i, p in enumerate(leaves):
            if self._low_rank(p.shape):
                q = self._q_init(i, int(p.shape[-1]))
                # Stacked like the residuals (every worker's slice holds
                # the same replicated Q) so ONE pytree spec covers the
                # whole comm state — see _stack_zeros_like.
                qs[f"{i:03d}"] = jnp.repeat(q[None], world, axis=0)
        return {"residual": _stack_zeros_like(params_like, world), "q": qs}

    def __call__(self, grads: Any, axis_name: str, comm: Any = None):
        world = lax.axis_size(axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        rs = (jax.tree.leaves(comm["residual"])
              if comm is not None else [None] * len(leaves))

        out: List[Any] = [None] * len(leaves)
        new_rs: List[Any] = [None] * len(leaves)
        new_qs = {}
        prev = None
        for i, (g, r) in enumerate(zip(leaves, rs)):
            v = g.astype(jnp.float32)
            if r is not None:
                v = v + _local(r)
            if self._low_rank(g.shape):
                m_rows = v.size // v.shape[-1]
                mat = v.reshape(m_rows, v.shape[-1])
                if comm is not None:
                    q = _local(comm["q"][f"{i:03d}"])
                else:
                    q = self._q_init(i, int(g.shape[-1]))
                p = lax.psum(_after(mat @ q, prev), axis_name) / world
                p = _orthonormalize(p)
                new_q = lax.psum(mat.T @ p, axis_name) / world
                approx = p @ new_q.T
                out[i] = approx.reshape(g.shape).astype(g.dtype)
                new_rs[i] = (mat - approx).reshape(g.shape)[None]
                # Both factors come out of psums (replicated), but the
                # stored Q is per-worker stacked state like the residuals:
                # typed device-varying so a scan carry leaves as it entered.
                new_qs[f"{i:03d}"] = lax.pcast(
                    new_q, axis_name, to="varying")[None]
                prev = new_q
            else:
                # compressed_psum bf16 fallback, inline and chained.
                q16 = _after(v, prev).astype(jnp.bfloat16)
                s = lax.psum(q16, axis_name)
                out[i] = (s.astype(jnp.float32) / world).astype(g.dtype)
                new_rs[i] = (v - q16.astype(jnp.float32))[None]
                prev = s
        new_comm = None if comm is None else {
            "residual": jax.tree.unflatten(treedef, new_rs), "q": new_qs}
        return jax.tree.unflatten(treedef, out), new_comm


def reshard_comm(comm: Any, new_world: int) -> Any:
    """Map an (old_world, ...)-stacked comm pytree onto ``new_world``
    positions — the elastic-resume world resize (train/loop.py).

    Residuals reshard SUM-conservingly: each old worker's residual is mass
    the collective has not yet delivered, so the total is split evenly,
    ``r_new[i] = sum_old(r) / new_world`` — what error feedback re-injects
    into training is invariant to the resize.  Warm-start Q factors hold
    identical replicated content per slice (PowerSGD.init_comm), so the
    mean slice is repeated.  Host-side numpy on purpose: this runs once
    per resume, before the state is committed to the new mesh."""

    def _sum_split(a):
        a = np.asarray(a, dtype=np.float32)
        total = a.sum(axis=0, keepdims=True)
        return np.repeat(total / new_world, new_world, axis=0)

    def _mean_repeat(a):
        a = np.asarray(a, dtype=np.float32)
        return np.repeat(a.mean(axis=0, keepdims=True), new_world, axis=0)

    out = dict(comm)
    out["residual"] = jax.tree.map(_sum_split, comm["residual"])
    if "q" in comm:
        out["q"] = jax.tree.map(_mean_repeat, comm["q"])
    return out


STRATEGIES = {
    "single": local,
    "gather": gather_scatter,
    "allreduce": per_param_psum,
    "ddp": bucketed_psum,
    "overlap": overlapped_ddp,
    "compress-bf16": CompressedPsum("bf16"),
    "compress-int8": CompressedPsum("int8"),
    "powersgd": PowerSGD(),
}


def get_strategy(name: str, bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 compress_rank: int = DEFAULT_COMPRESS_RANK) -> Strategy:
    """Resolve a CLI strategy name to a gradient-sync callable.

    Stateless strategies are ``(grads, axis) -> grads`` functions; the
    compressed tiers are callables with ``stateful = True`` and an
    ``init_comm(params_like, world)`` hook whose state rides in
    ``SGDState.comm`` (train/step.py apply_strategy dispatches on the
    attribute)."""
    name = name.lower()
    if name not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {name!r}; expected one of {sorted(STRATEGIES)}")
    if name == "ddp":
        return partial(bucketed_psum, bucket_bytes=bucket_bytes)
    if name == "overlap":
        return partial(overlapped_ddp, bucket_bytes=bucket_bytes)
    if name == "powersgd" and compress_rank != DEFAULT_COMPRESS_RANK:
        return PowerSGD(compress_rank)
    return STRATEGIES[name]
