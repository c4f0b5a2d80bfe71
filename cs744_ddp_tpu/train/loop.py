"""Training driver: the reference's ``run``/``train_model``/``test_model``
(``/root/reference/src/Part 2a/main.py:19-68,71-114,130-145``) rebuilt around
one compiled SPMD step.

Differences from the reference, by design (all documented in BASELINE.md):

  * one process drives all local devices; "workers" are mesh positions, and
    each mesh position sees exactly the shard the reference's
    DistributedSampler would hand that rank (data.sharding);
  * the per-batch phases (augment/forward/loss/backward/sync/step) are one
    XLA program — timing therefore reports the fused step time, fenced by
    fetching the loss values (the host needs them anyway, and a value
    fetch cannot return before the computation that produced it); an
    optional split-phase mode additionally times a forward-only program
    for the reference's fwd/bwd split;
  * the ragged final train batch (drop_last=False) runs through a second
    compiled step at its true static shape — exact short-batch BN/CE
    semantics, same iteration count as the reference;
  * evaluation runs once across the mesh (psum'd counts) instead of
    redundantly per rank, reporting identical quantities.
"""

from __future__ import annotations

import functools
import os
import queue
import signal
import threading
import time
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import models as model_zoo
from ..data import cifar10, native, sharding
from ..ft import (FTConfig, ChaosError, NULL_CHAOS, NonFiniteError,
                  PreemptedError, PreemptionGuard, RankDeathError)
from ..ft import guard as ftguard
from ..ft import supervisor as ftsup
from ..obs import NULL, NULL_SPAN, git_sha, ringbuf
from ..ops import sgd
from ..parallel import get_strategy, mesh as meshlib, strategies
from ..utils.metrics import WINDOW, WindowedTimers
from . import step as steplib

GLOBAL_BATCH = 256      # reference: batch_size=256 (Part 2a/main.py:173)
SEED = 0                # reference: torch.manual_seed(0) (main.py:80-81)


def _shard_batch_cols(n_examples: int, world: int, global_batch: int,
                      epoch: int, *, shuffle: bool, seed: int = SEED,
                      reshuffle_each_epoch: bool = False
                      ) -> Iterator[np.ndarray]:
    """Yield each global batch's device-major index columns (the sampler
    layout ``_shard_batches`` materializes).  The chunked staging producer
    consumes the RAW indices so the fused C++ gather+augment
    (native.gather_augment_u8) can write arena rows straight from the
    resident dataset, with no intermediate gathered batch."""
    per = global_batch // world
    idx = sharding.global_epoch_indices(
        n_examples, world, seed=seed, shuffle=shuffle, epoch=epoch,
        reshuffle_each_epoch=reshuffle_each_epoch)
    nfull = idx.shape[1] // per
    for b in range(nfull):
        yield idx[:, b * per:(b + 1) * per].reshape(-1)  # device-major
    if idx.shape[1] % per:
        yield idx[:, nfull * per:].reshape(-1)


def _shard_batches(split: cifar10.Split, world: int, global_batch: int,
                   epoch: int, *, shuffle: bool, seed: int = SEED,
                   reshuffle_each_epoch: bool = False
                   ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Yield [global_batch,...] host arrays laid out so that sharding dim 0
    over the mesh gives device d exactly sampler-rank d's examples.

    The final yield may be SHORT (the ragged tail): the reference's
    DataLoader uses ``drop_last=False`` (``Part 1/main.py:96-101``), so the
    short 196th/782nd batch is trained too.  The sampler's wrap-padding
    guarantees every rank holds the same per-rank count, so the tail is
    equal-sized across ranks and shards cleanly; it runs through a second
    compiled step at its own (static) shape — exact short-batch BN/CE
    semantics, no masking."""
    for cols in _shard_batch_cols(
            len(split.labels), world, global_batch, epoch, shuffle=shuffle,
            seed=seed, reshuffle_each_epoch=reshuffle_each_epoch):
        # Batch assembly via the native threaded gather (the reference's
        # DataLoader-worker equivalent); falls back to numpy fancy indexing.
        yield native.gather(split.images, cols), split.labels[cols]


def _eval_batches(split: cifar10.Split, global_batch: int
                  ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Full test set in order, final batch padded with label -1 sentinels
    (masked in the eval step) so every batch keeps the compiled shape."""
    n = len(split.labels)
    for start in range(0, n, global_batch):
        imgs = split.images[start:start + global_batch]
        labs = split.labels[start:start + global_batch]
        if len(labs) < global_batch:
            pad = global_batch - len(labs)
            imgs = np.concatenate(
                [imgs, np.zeros((pad,) + imgs.shape[1:], imgs.dtype)])
            labs = np.concatenate([labs, np.full((pad,), -1, np.int32)])
        yield imgs, labs


def emit_memory_gauges(telemetry, **attrs) -> None:
    """Host + device memory gauges at a window/epoch boundary (round 8):
    peak host RSS via ``resource.getrusage`` and live device bytes via
    ``jax.live_arrays()``.  The enabled-guard lives INSIDE so call sites
    stay one-liners; through the NULL recorder this is a single attribute
    check — no allocation, no write (pinned by the exploding-recorder
    test in tests/test_telemetry.py)."""
    if not telemetry.enabled:
        return
    import resource
    rss_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    payload = {"host_rss_peak_mib": round(rss_kib / 1024.0, 1)}
    try:
        live = jax.live_arrays()
        payload["device_live_mib"] = round(
            sum(int(getattr(a, "nbytes", 0) or 0) for a in live) / 2 ** 20, 2)
        payload["device_live_arrays"] = len(live)
    except Exception:          # pragma: no cover - backend without the API
        pass
    telemetry.gauge("memory", payload, **attrs)


def _init_span(init):
    """Run ``Trainer.__init__`` inside one ``trainer_init`` span of its own
    recorder (a no-op through ``NULL``)."""
    @functools.wraps(init)
    def traced(self, *args, telemetry=NULL, **kw):
        with telemetry.span("trainer_init"):
            init(self, *args, telemetry=telemetry, **kw)
    return traced


class Trainer:
    """Wires data + model + strategy + mesh into the reference's run()."""

    @_init_span
    def __init__(self, model: str = "vgg11", strategy: str = "allreduce",
                 *, mesh=None, num_devices: Optional[int] = None,
                 compress_rank: Optional[int] = None,
                 global_batch: int = GLOBAL_BATCH, data_dir: str = "./data",
                 seed: int = SEED, init_seed: Optional[int] = None,
                 augment: bool = True,
                 sgd_cfg: sgd.SGDConfig = sgd.SGDConfig(),
                 profile_phases: bool = False,
                 host_augment: bool = False,
                 host_chunks: int = 4,
                 precision: str = "f32",
                 reshuffle_each_epoch: bool = False,
                 limit_train_batches: Optional[int] = None,
                 limit_eval_batches: Optional[int] = None,
                 metrics_ring: Optional[int] = None,
                 log: Callable[[str], None] = print,
                 telemetry=NULL,
                 ft: Optional[FTConfig] = None,
                 elastic=None):
        self.mesh = mesh if mesh is not None else meshlib.make_mesh(num_devices)
        self.world = self.mesh.devices.size
        if global_batch % self.world:
            raise ValueError(f"global batch {global_batch} not divisible by "
                             f"world size {self.world}")
        self.global_batch = global_batch
        self.log = log
        # Structured telemetry recorder (obs/) — NULL (a stateless no-op)
        # by default, so the disabled path writes no files and allocates
        # nothing per step; the stdout print schedule above/below is the
        # reference-parity surface either way and is never redirected.
        self.telemetry = telemetry
        self.profile_phases = profile_phases
        # host_augment: the train transform runs in the C++ host pipeline
        # (data/native.py fl_augment_f32 — the reference's DataLoader-worker
        # model, Part 1/main.py:96-101) and the step receives preprocessed
        # f32 batches.  Since round 5 this dispatches scanned WINDOWS over
        # producer-staged buffers (_train_model_host_windowed — the
        # reference's own num_workers=2 + batching amortization); the
        # per-batch dispatch path remains under profile_phases.  The
        # default (False) keeps the TPU-first design: uint8 to the device,
        # transform fused into the compiled step.
        self.host_augment = host_augment
        # host_chunks: the windowed host-augment path stages each WINDOW as
        # K sub-window chunks put_global'd individually by the producer, so
        # window w+1's transfers overlap window w's device compute (round 6;
        # the round-5 path shipped ONE blocking whole-window put and left
        # the host->device link idle during compute).  K=1 degrades exactly to round 5's
        # whole-window staging; default 4 keeps chunks ~5 batches (~3.8 MiB
        # at B=256) — deep enough to overlap, coarse enough that per-put
        # fixed costs stay amortized.
        if host_chunks < 1:
            raise ValueError(f"host_chunks must be >= 1, got {host_chunks}")
        self.host_chunks = int(host_chunks)
        # Compute precision: "f32" (reference parity, the default) or "bf16"
        # (mixed precision: f32 master weights/optimizer/BN statistics/loss,
        # bf16 conv+matmul activations — the MXU's native mode).
        if precision not in ("f32", "bf16"):
            raise ValueError(f"precision must be 'f32' or 'bf16', "
                             f"got {precision!r}")
        self.precision = precision
        self.compute_dtype = compute_dtype = (
            jnp.bfloat16 if precision == "bf16" else None)
        self.augment = augment
        self.seed = seed
        # The initial weights' seed, where it is not the data's: `seed`
        # then draws the batches, the augmentation / noising and nothing
        # else (a benchmark cell pins the weights and varies the data).
        self.init_seed = seed if init_seed is None else init_seed
        # The reference never reshuffles across epochs (no sampler.set_epoch
        # call — SURVEY.md C6); opt in for proper per-epoch reshuffling.
        self.reshuffle_each_epoch = reshuffle_each_epoch
        # Optional iteration caps (None = full splits, the reference's
        # behavior): bound epoch cost for smoke runs and benchmarks.
        for name, lim in (("limit_train_batches", limit_train_batches),
                          ("limit_eval_batches", limit_eval_batches)):
            if lim is not None and lim < 1:
                raise ValueError(f"{name} must be >= 1, got {lim}")
        self.limit_train_batches = limit_train_batches
        self.limit_eval_batches = limit_eval_batches

        # Fault tolerance (ft/): all opt-in through one config.  ft=None —
        # the default — keeps every hot path byte-identical to the
        # unsupervised build: chaos is the stateless NULL_CHAOS singleton,
        # the non-finite guard is never compiled into the step programs,
        # and the staging pipeline runs exactly the PR-2 code.
        self.ft = ft
        self.chaos = ft.chaos if ft is not None else NULL_CHAOS
        self._nf_policy = ft.nonfinite if ft is not None else "off"
        if self._nf_policy not in ftguard.POLICIES:
            raise ValueError(f"nonfinite policy must be one of "
                             f"{ftguard.POLICIES}, got {self._nf_policy!r}")
        self._guard_on = self._nf_policy != "off"
        self._nf_chaos_steps = (self.chaos.steps("nonfinite_grad")
                                if self.chaos.enabled else ())
        if self._nf_chaos_steps and not self._guard_on:
            raise ValueError(
                "chaos nonfinite_grad injection requires a nonfinite policy "
                "(halt/skip/restore) — injecting NaNs with the guard off "
                "just corrupts the run")
        self._supervise = ft is not None
        self._verify_chunks = bool(ft is not None and (
            ft.verify_chunks or self.chaos.steps("corrupt_slot")))
        self.staging_degraded = bool(ft is not None and ft.degrade_staging)

        # Elastic mode (elastic/): accepts an ElasticConfig or a protocol
        # name.  "weak" pins the per-chip batch and only changes resume
        # PLANNING (the standard per-rank programs already are the weak-
        # scaling semantics); "strong" pins the global batch and swaps the
        # train window for the microshard program whose update is bitwise
        # world-invariant (elastic/step_elastic.py).
        from ..elastic.protocol import ElasticConfig, PROTOCOLS
        if isinstance(elastic, str):
            elastic = ElasticConfig(protocol=elastic)
        self.elastic = elastic
        self.rank_death = None      # (rank, epoch, step) after a death
        self.resume_plan = None     # ResumePlan from an elastic resume
        self._straggler = None      # lazily-built StragglerDetector
        if elastic is not None:
            if elastic.protocol not in PROTOCOLS:
                raise ValueError(f"elastic protocol must be one of "
                                 f"{PROTOCOLS}, got {elastic.protocol!r}")
            if elastic.protocol == "strong":
                s = elastic.microshards
                if global_batch % s:
                    raise ValueError(
                        f"elastic strong scaling: global batch "
                        f"{global_batch} not divisible by microshards {s}")
                if host_augment:
                    raise ValueError(
                        "elastic strong scaling requires device-side "
                        "augmentation (host streams are rank-shaped)")
                if profile_phases:
                    raise ValueError(
                        "elastic strong scaling is windowed-only; "
                        "profile_phases uses the per-step programs")
                if self._guard_on or self._nf_chaos_steps:
                    raise ValueError(
                        "elastic strong scaling does not support the "
                        "non-finite guard (the pinned window carries no "
                        "guarded variant)")
        # Device-resident metric ring (obs/ringbuf.py, round 8): the
        # windowed paths write per-step (loss, grad sqnorm, ok, step) rows
        # into a donated on-device ring and the host drains it ONCE per
        # window instead of fetching stacked per-step ys.  None = on by
        # default at DEFAULT_CAPACITY; 0 disables; N sets the capacity.
        # Forced off where it cannot apply: elastic strong scaling (the
        # pinned world-invariant window carries no ring variant) and
        # profile_phases (per-step dispatch is that mode's point — every
        # step already round-trips).
        if metrics_ring is None:
            ring_cap = ringbuf.DEFAULT_CAPACITY
        else:
            ring_cap = int(metrics_ring)
            if ring_cap < 0:
                raise ValueError(
                    f"metrics_ring must be >= 0, got {metrics_ring}")
            if ring_cap and ring_cap < WINDOW:
                raise ValueError(
                    f"metrics_ring capacity {ring_cap} is below the scan "
                    f"window length {WINDOW}: rows would be overwritten "
                    f"before the per-window drain")
        if profile_phases or (
                elastic is not None and elastic.protocol == "strong"):
            ring_cap = 0
        self.metrics_ring = ring_cap
        self.preempted = False
        self._preempt_guard: Optional[PreemptionGuard] = None
        self._rollback = None            # host snapshot for policy=restore
        self._chaos_step_cache: dict = {}
        self.nonfinite_skipped = 0       # run totals (epoch counts are
        self.nonfinite_restored = 0      # logged per epoch summary)
        self._epoch_nf_skipped = 0
        self._epoch_nf_restored = 0
        self.producer_failures = 0

        # Split-replacement generations: staging caches key on these, so
        # swapping a split always restages (id() reuse after GC cannot serve
        # stale device arrays).  Must exist before the property assignments.
        self._train_gen = 0
        self._test_gen = 0
        # `model` is a registry name ("vgg11", "resnet18", ...) or a custom
        # (init_fn, apply_fn) pair (used by tests to keep compiles small).
        if isinstance(model, str):
            self.model_name = model
            init_fn, self.apply_fn = model_zoo.get_model(model)
        else:
            self.model_name = "custom"
            init_fn, self.apply_fn = model
        # What the model trains on (train/step.py `ImageObjective`, or the
        # model's own: a decoder's token ids and masked loss), through the
        # same default path either way: staged epochs, scanned windows, the
        # ring, the eval window.  A stream of tokens has only that path.
        self.objective = steplib.objective_of(self.apply_fn)
        if self.objective.stream:
            unsupported = [name for name, on in (
                ("host_augment", host_augment),
                ("profile_phases", profile_phases),
                ("elastic", elastic is not None),
                ("nonfinite guard", self._guard_on)) if on]
            if unsupported:
                raise ValueError(
                    f"model {self.model_name!r} trains on the default "
                    f"windowed path only; not with {unsupported}")
            from ..data import tokens
            with telemetry.span("load_splits"):
                self.train_split, self.test_split, self.real_data = \
                    tokens.load(data_dir, self.objective.seq_len,
                                self.objective.vocab - 1, seed)
        else:
            with telemetry.span("load_splits"):
                self.train_split, self.test_split, self.real_data = \
                    cifar10.load(data_dir)
        # Reference parity: these lines print len(train_loader) — the
        # per-rank BATCH count, not the example count (Part 2a/main.py:46,55).
        def ceil_div(a, b):
            return -(-a // b)

        per_rank_samples = ceil_div(len(self.train_split.labels), self.world)
        per_rank_batch = global_batch // self.world
        # The printed count is ceil (DataLoader drop_last=False parity, 782
        # at 50000/64) and matches the trained count: the ragged final batch
        # runs through its own compiled step at its true shape (_shard_batches
        # docstring), so printed == trained.
        self.log(f"Size of training set is "
                 f"{ceil_div(per_rank_samples, per_rank_batch)}")
        # The reference's test loader uses the PER-RANK batch (256/world,
        # Part 2a/main.py:50-54) over the UNsharded 10k test set, so its
        # printed size is ceil(10000/(256/world)).
        self.log(f"Size of test set is "
                 f"{ceil_div(len(self.test_split.labels), per_rank_batch)}")

        self.strategy_name = strategy
        self.sgd_cfg = sgd_cfg
        # compress_rank only parameterizes the powersgd tier; None defers
        # to the strategy default (strategies.DEFAULT_COMPRESS_RANK).
        self.compress_rank = compress_rank
        strat = self._strategy = get_strategy(
            strategy, **({} if compress_rank is None
                         else {"compress_rank": compress_rank}))
        with telemetry.span("init_state"):
            self.state = steplib.init_train_state(
                init_fn, jax.random.PRNGKey(self.init_seed), strat,
                self.world)
        # Commit the state to the mesh up front: otherwise the first
        # windowed call sees uncommitted arrays and the second call a
        # different sharding signature -> a full recompile.  Everything is
        # replicated except a stateful strategy's comm state, which lives
        # sharded over the data axis (_commit_state).
        self.state = self._commit_state(self.state)
        self.train_step = steplib.make_train_step(
            self.apply_fn, strat, self.mesh, sgd_cfg, augment=augment,
            compute_dtype=compute_dtype, nonfinite_guard=self._guard_on)
        self.train_window = steplib.make_train_window(
            self.apply_fn, strat, self.mesh, sgd_cfg, augment=augment,
            compute_dtype=compute_dtype, nonfinite_guard=self._guard_on,
            nonfinite_chaos_steps=self._nf_chaos_steps)
        if elastic is not None and elastic.protocol == "strong":
            # The pinned-math window replaces BOTH the strategy's gradient
            # reduction and the windowed program: its gather + fixed-tree
            # combine is the one float summation order every world size
            # shares (elastic/step_elastic.py) — the strategy choice still
            # names the NON-elastic programs (tail/eval/per-step).
            from ..elastic.step_elastic import make_elastic_train_window
            self.train_window = make_elastic_train_window(
                self.apply_fn, self.mesh, sgd_cfg,
                microshards=elastic.microshards, augment=augment,
                compute_dtype=compute_dtype)
        # Ring variants of the windowed programs (built alongside, compiled
        # lazily): same math, ys swapped for the donated device ring.
        self.train_window_ring = None
        self.train_window_host_ring = None
        if self.metrics_ring:
            self.train_window_ring = steplib.make_train_window(
                self.apply_fn, strat, self.mesh, sgd_cfg, augment=augment,
                compute_dtype=compute_dtype, nonfinite_guard=self._guard_on,
                nonfinite_chaos_steps=self._nf_chaos_steps,
                metrics_ring=True)
        if host_augment:
            self.train_step_host = steplib.make_train_step(
                self.apply_fn, strat, self.mesh, sgd_cfg, augment="host",
                compute_dtype=compute_dtype, nonfinite_guard=self._guard_on)
            # The windowed host path ships COMPACT uint8 (the C++ pipeline
            # does the stochastic crop/flip; the affine normalize fuses
            # into the device step, augment=False = normalize-only): the
            # host->device link is the path's roofline (BASELINE.md), and
            # uint8 carries 4x fewer bytes than the f32 per-step format.
            self.train_window_host = steplib.make_train_window(
                self.apply_fn, strat, self.mesh, sgd_cfg, augment=False,
                compute_dtype=compute_dtype, nonfinite_guard=self._guard_on,
                nonfinite_chaos_steps=self._nf_chaos_steps)
            if self.metrics_ring:
                self.train_window_host_ring = steplib.make_train_window(
                    self.apply_fn, strat, self.mesh, sgd_cfg, augment=False,
                    compute_dtype=compute_dtype,
                    nonfinite_guard=self._guard_on,
                    nonfinite_chaos_steps=self._nf_chaos_steps,
                    metrics_ring=True)
        self.eval_window = steplib.make_eval_window(
            self.apply_fn, self.mesh, compute_dtype=compute_dtype)
        if profile_phases:
            self._fwd_only = self._make_fwd_only()

        self._batch_sharding = meshlib.batch_sharding(self.mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._epoch_sharding = NamedSharding(self.mesh, P(None, meshlib.DATA_AXIS))
        if host_augment:
            # On-device window assembly for the chunked staging path: ONE
            # jitted concatenate over the K device-resident chunks (shared
            # by images and labels; retraced per distinct arity/shape).  The
            # u8 window copy it performs is ~15.7 MiB at W=20/B=256 —
            # microseconds of HBM bandwidth.  The rejected assembly
            # variant: dispatching the scanned window per-chunk — or
            # scanning across the chunk list — pays the fixed per-dispatch
            # host cost PER CHUNK, i.e. K x the cost round 5's windowing
            # exists to amortize (not re-measured on the chip since the
            # host link changed; ROADMAP S2); and a K-argument fused
            # scan-over-chunks program recompiles per distinct chunk-count
            # signature while still serializing the window on its LAST
            # chunk's arrival.  Concatenate-then-scan keeps one dispatch
            # per window and lets earlier chunks transfer while the
            # previous window computes.
            self._assemble_chunks = jax.jit(
                lambda *chunks: jnp.concatenate(chunks, axis=0),
                out_shardings=self._epoch_sharding)
        self._staging_arena = None          # lazily-built native.StagingArena
        self._staging_put_copies = None     # backend aliasing probe result
        self._staged_train = None   # (epoch_images, epoch_labels, tail)
        self._staged_eval = None
        self._warmed_tail_shapes = set()
        self._warmed_window_shapes = set()
        self.last_epoch_timers: Optional[WindowedTimers] = None
        self.last_epoch_extras: dict = {}   # an objective's totals, by name
        self._collective_stats_emitted = False
        self._span_epoch: Optional[int] = None  # of the last train_model

        if self._nf_policy == "restore":
            # "Last checkpoint" before any save is the initial state.
            self._snapshot_rollback()

        if telemetry.enabled:
            d0 = self.mesh.devices.flat[0]
            ft_manifest = None
            if ft is not None:
                ft_manifest = {
                    "nonfinite": self._nf_policy,
                    "chaos": self.chaos.spec() if self.chaos.enabled else [],
                    "put_timeout_s": ft.put_timeout_s,
                    "put_retries": ft.put_retries,
                    "stall_timeout_s": ft.stall_timeout_s,
                    "producer_restarts": ft.producer_restarts,
                    "verify_chunks": self._verify_chunks,
                    "degrade_staging": ft.degrade_staging,
                }
            telemetry.write_manifest({
                "fault_tolerance": ft_manifest,
                "model": self.model_name,
                "strategy": self.strategy_name,
                "world_size": self.world,
                "global_batch": global_batch,
                "precision": precision,
                "augment": augment,
                "host_augment": host_augment,
                "host_chunks": host_chunks,
                "elastic": (None if elastic is None else
                            {"protocol": elastic.protocol,
                             "microshards": elastic.microshards}),
                "profile_phases": profile_phases,
                "metrics_ring": self.metrics_ring,
                "seed": seed,
                "reshuffle_each_epoch": reshuffle_each_epoch,
                "real_data": self.real_data,
                "lr": sgd_cfg.lr, "momentum": sgd_cfg.momentum,
                "weight_decay": sgd_cfg.weight_decay,
                "jax_version": jax.__version__,
                "backend": jax.default_backend(),
                "device_kind": getattr(d0, "device_kind", str(d0)),
                "num_devices": self.world,
                # The native host loader degrades SILENTLY to NumPy; the
                # manifest records whether this run really had the C++
                # pipeline, and if not, why (data/native.py load_error).
                "native_loader": {"available": native.available(),
                                  "error": native.load_error()},
                "git_sha": git_sha(),
            })
            for name, value, attrs in self.objective.gauges:
                telemetry.gauge(name, value, **attrs)

    def _commit_state(self, state) -> "steplib.TrainState":
        """Commit a (host or device) TrainState to the mesh: params/BN/
        momentum replicated, a stateful strategy's comm state sharded over
        the data axis — its leaves are (world, ...) per-worker stacks and
        each mesh position owns exactly its own slice (strategies
        ``_stack_zeros_like``; the compiled programs consume it under
        ``P(DATA_AXIS)``, steplib._opt_specs).  Committing both shardings
        up front keeps every later dispatch signature-stable."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        comm = state.opt_state.comm
        stripped = state._replace(
            opt_state=state.opt_state._replace(comm=None))
        out = meshlib.put_global_tree(stripped, meshlib.replicated(self.mesh))
        if comm is not None:
            sharded = NamedSharding(self.mesh, P(meshlib.DATA_AXIS))
            comm = jax.tree.map(
                lambda a: meshlib.put_global(
                    np.asarray(jax.device_get(a)), sharded), comm)
        return out._replace(opt_state=out.opt_state._replace(comm=comm))

    # -- telemetry helpers ---------------------------------------------------

    def _emit_device_gauges(self, epoch: int) -> None:
        """Per-device ``memory_stats()`` gauges (backends without the API —
        CPU — contribute nothing)."""
        for d in self.mesh.devices.flat:
            ms = getattr(d, "memory_stats", None)
            if ms is None:
                continue
            try:
                stats = ms()
            except Exception:
                continue
            if not stats:
                continue
            keep = {k: stats[k] for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                     "largest_alloc_size") if k in stats} or dict(stats)
            self.telemetry.gauge("device_memory", keep, device=int(d.id),
                                 epoch=epoch)

    def _emit_collective_telemetry(self) -> None:
        """Counters/gauges for the compiled train step's collective pattern
        (analysis/stats over the pre-optimization HLO): op counts, result
        bytes and chain depth — the static cost shape of the gradient-sync
        tier, attached to the run artifact.  Best-effort: backends that
        cannot produce the HLO print contribute an error gauge instead."""
        if self._collective_stats_emitted:
            return
        self._collective_stats_emitted = True
        from ..analysis import stats as hlo_stats
        try:
            x = jax.ShapeDtypeStruct(
                (self.global_batch,) + self.objective.example_shape,
                jnp.float32 if self.host_augment
                else self.objective.example_dtype,
                sharding=self._batch_sharding)
            y = jax.ShapeDtypeStruct((self.global_batch,), jnp.int32,
                                     sharding=self._batch_sharding)
            step_fn = self.train_step_host if self.host_augment \
                else self.train_step
            txt = step_fn.lower(
                self.state, jax.random.PRNGKey(0), x, y) \
                .compiler_ir(dialect="hlo").as_hlo_text()
        except Exception as e:
            self.telemetry.gauge("collective_stats_error", repr(e))
            return
        stats = hlo_stats.collective_stats(txt)
        for op, entry in stats["ops"].items():
            self.telemetry.counter(f"collective_{op}_count", entry["count"])
            self.telemetry.counter(f"collective_{op}_result_mib",
                                   entry["result_mib"])
        self.telemetry.gauge(
            "collective_totals", {
                "total_count": stats["total_count"],
                "total_result_mib": stats["total_result_mib"],
                "chain_depth": hlo_stats.collective_chain_depth(txt)})
        # Compression headline: the uncompressed wire cost is every f32
        # gradient byte exactly once (per_param_psum's result bytes); the
        # delta against this strategy's measured collective bytes is what
        # a compressed tier buys.  gather's doubled comm clamps to 0 saved.
        grad_mib = float(sum(
            int(np.prod(a.shape, dtype=np.int64)) * 4
            for a in jax.tree.leaves(self.state.params))) / 2 ** 20
        self.telemetry.gauge(
            "comm_bytes_saved", {
                "strategy": self.strategy_name,
                "baseline_grad_mib": round(grad_mib, 3),
                "strategy_result_mib": stats["total_result_mib"],
                "saved_mib": round(
                    max(0.0, grad_mib - stats["total_result_mib"]), 3)})

    # -- metric ring (obs/ringbuf.py, round 8) ------------------------------

    def _make_ring_device(self):
        """Fresh epoch ring, committed REPLICATED to the mesh up front —
        like ``_commit_state``, so the first ring dispatch already sees the
        sharding every later (donated) dispatch returns: signature-stable
        from call one."""
        rep = meshlib.replicated(self.mesh)
        return (meshlib.put_global(
                    np.zeros((self.metrics_ring, self._ring_width()),
                             np.float32), rep),
                meshlib.put_global(np.zeros((), np.int32), rep))

    def _ring_width(self) -> int:
        """The four columns every model writes, plus its objective's own."""
        return ringbuf.N_METRICS + len(self.objective.extras)

    def _ring_sds(self):
        """ShapeDtypeStructs of the ring pair, for AOT warmup lowers."""
        rep = meshlib.replicated(self.mesh)
        return (jax.ShapeDtypeStruct(
                    (self.metrics_ring, self._ring_width()), jnp.float32,
                    sharding=rep),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))

    def _count_round_trip(self, site: str) -> None:
        """Tally one device->host value fetch.  The windowed+ring epoch is
        pinned at <= windows + 2 of these (per-window drains, the ragged
        tail, the eval fetch); the per-step path honestly records one per
        iteration — the contrast the ring exists to remove."""
        if self.telemetry.enabled:
            self.telemetry.counter("host_round_trips", 1, site=site,
                                   epoch=self._span_epoch)

    def _consume_ring(self, buf_host, writes_total: int, w: int,
                      per_iter: float, timers: WindowedTimers,
                      epoch: int) -> np.ndarray:
        """Feed one drained window into the reference-parity timers (and,
        when telemetry is on, the JSONL step stream with reconstructed
        absolute step indices + grad sqnorms).  Returns the ok column for
        the non-finite policy layer.  ``buf_host`` is the already-fetched
        buffer — the ONE round-trip happened inside the timed span."""
        rows = ringbuf.drain_rows(buf_host, writes_total, w)
        losses, gsq, oks, steps = ringbuf.split_columns(rows)
        names = [name for name, _ in self.objective.extras]
        extras = rows[:, ringbuf.N_METRICS:]
        if names:
            self._tally_extras(extras, epoch)
        if self.telemetry.enabled:
            for i, (l, g, s) in enumerate(zip(losses, gsq, steps)):
                timers.record(float(l), per_iter,
                              extra={"grad_sqnorm": float(g),
                                     "step_index": int(s),
                                     **dict(zip(names, map(float,
                                                           extras[i])))})
        else:
            for l in losses:
                timers.record(float(l), per_iter)
        return oks

    def _tally_extras(self, extras: np.ndarray, epoch: int) -> None:
        """A drained window's objective columns into the epoch's totals
        (`last_epoch_extras`: each combined over the steps as the
        objective says its shards combine, a sum or the largest) and, with
        a recorder, the sums into counters beside `dispatches` and
        `host_round_trips` (a decoder's `moe_rows_local`, `tokens_masked`),
        as are the objective's constants an example (`per_example`: the
        `moe_rows_expected` that even routing would have sent here)."""
        tot = self.last_epoch_extras
        for j, (name, how) in enumerate(self.objective.extras):
            col = extras[:, j]
            if how == "max":
                tot[name] = max(tot.get(name, 0.0), float(col.max()))
            else:
                tot[name] = tot.get(name, 0.0) + float(col.sum())
                if self.telemetry.enabled:
                    self.telemetry.counter(name, float(col.sum()),
                                           epoch=epoch)
        for name, each in self.objective.per_example.items():
            n = each * self.global_batch * len(extras)
            tot[name] = tot.get(name, 0.0) + n
            if self.telemetry.enabled:
                self.telemetry.counter(name, n, epoch=epoch)

    # -- fault tolerance (ft/) ----------------------------------------------

    def _snapshot_rollback(self) -> None:
        """Host copy of the current state — the ``--nonfinite=restore``
        rollback target, refreshed after every checkpoint save.  A HOST
        copy: the windowed programs donate their state buffers, so a kept
        device reference would be invalidated by the next dispatch."""
        self._rollback = jax.tree.map(
            lambda a: np.asarray(jax.device_get(a)), self.state)

    def _restore_rollback(self) -> None:
        # _commit_state restores the dual sharding layout (replicated state,
        # data-sharded comm) from the host snapshot.
        self.state = self._commit_state(self._rollback)

    def _handle_nonfinite(self, oks, epoch: int) -> bool:
        """Host-side reaction to the fetched per-step ``ok`` flags.  The
        on-device select already kept the prior state for every bad step —
        this layer only counts and applies the policy.  Returns True when
        the state was rolled back (policy=restore)."""
        oks = np.asarray(oks)
        bad = int(oks.size - np.count_nonzero(oks))
        if bad == 0:
            return False
        if self._nf_policy == "halt":
            raise NonFiniteError(
                f"non-finite loss/grad-norm in epoch {epoch} "
                f"(policy=halt; the bad update was NOT applied)")
        if self._nf_policy == "skip":
            self._epoch_nf_skipped += bad
            self.nonfinite_skipped += bad
            self.telemetry.counter("nonfinite_skipped", bad, epoch=epoch)
            return False
        # restore: the select already skipped the bad update; additionally
        # rewind to the last checkpoint snapshot — steps since it are lost
        # (training continues with the NEXT batch, not a replay).
        self._epoch_nf_restored += bad
        self.nonfinite_restored += bad
        self.telemetry.counter("nonfinite_restored", bad, epoch=epoch)
        self._restore_rollback()
        self.log(f"Non-finite step: state rolled back to the last "
                 f"checkpoint snapshot (epoch {epoch})")
        return True

    def _fetch_step(self, out):
        """Advance ``self.state`` from a per-step program result, absorbing
        the guarded arity; returns (loss, ok_or_None) as host values (the
        loss fetch is the completion fence either way)."""
        self._count_round_trip("step_fetch")
        if self._guard_on:
            self.state, loss, ok = out
            return float(loss), bool(ok)
        self.state, loss = out
        return float(loss), None

    def _chaos_nf_step(self, host: bool):
        """The per-step chaos variant: same program as train_step(_host)
        plus an unconditional NaN injection into the gradients.  Built
        lazily (one extra compile only on chaos runs) and swapped in for
        exactly the planned batch by the per-step paths — the windowed
        paths instead bake the absolute-index mask into their one program
        (make_train_window nonfinite_chaos_steps)."""
        cache_key = "host" if host else "dev"
        fn = self._chaos_step_cache.get(cache_key)
        if fn is None:
            fn = steplib.make_train_step(
                self.apply_fn, self._strategy, self.mesh,
                self.sgd_cfg, augment="host" if host else self.augment,
                compute_dtype=self.compute_dtype, nonfinite_guard=True,
                inject_nonfinite=True)
            self._chaos_step_cache[cache_key] = fn
        return fn

    def _record_chaos(self, site: str, step: int) -> None:
        self.telemetry.counter("chaos_injected", 1, site=site, step=step)
        self.log(f"chaos: injected {site} at step {step}")

    def _check_preempt(self, epoch: int, step: int) -> None:
        """Step-boundary preemption poll: fire any planned chaos SIGTERM
        once progress reaches its step, then raise ``PreemptedError`` if a
        signal has arrived (real or injected).  ``step`` is the number of
        batches already trained this epoch — exactly the resume point."""
        if self.chaos.enabled and self.chaos.fire_reached("preempt", step):
            if self._preempt_guard is None:
                raise RuntimeError(
                    "chaos preempt requires run(checkpoint_dir=...) — "
                    "without the guard installed SIGTERM would kill the "
                    "process uncheckpointed")
            self._record_chaos("preempt", step)
            os.kill(os.getpid(), signal.SIGTERM)
        g = self._preempt_guard
        if g is not None and g.requested:
            raise PreemptedError(epoch, step)

    def _rank_boundary(self, epoch: int, step: int, per_iter: float) -> None:
        """Window-boundary rank bookkeeping (elastic/ft): per-rank
        step-time gauges, straggler detection, and the rank-level chaos
        sites.  On this single-process SPMD runtime every rank's honest
        step time IS the shared window wall time (one program, lockstep);
        the gauges exist so the attribution seam is real — the
        ``slow_rank`` site injects a stall attributed to exactly one
        rank's gauge, which the detector must flag, and on a multi-process
        deployment the same gauges would carry genuinely distinct times.
        ``rank_death`` raises ``RankDeathError`` here — a step boundary,
        so ``step`` batches are exactly what the emergency checkpoint
        records.  No-op (and allocation-free) without ft/elastic."""
        if self.elastic is None and not self._supervise:
            return
        stalls = {}
        if self.chaos.enabled and self.chaos.fire_reached("slow_rank", step):
            planned = self.chaos.fired[-1][1]
            rank = self.chaos.seed_of("slow_rank", planned)
            stall_s = (self.ft.slow_rank_stall_s if self.ft is not None
                       else FTConfig().slow_rank_stall_s)
            self._record_chaos("slow_rank", step)
            time.sleep(stall_s)   # the rank really straggles: wall time too
            stalls[rank] = stall_s
        if self._straggler is None:
            from ..elastic.straggler import StragglerDetector
            self._straggler = StragglerDetector(self.world)
        for r in range(self.world):
            t = per_iter + stalls.get(r, 0.0)
            if self.telemetry.enabled:
                self.telemetry.gauge("rank_step_time_s", t, rank=r,
                                     epoch=epoch, step=step)
            self._straggler.observe(r, t)
        for r in self._straggler.check():
            self.log(f"elastic: rank {r} straggling "
                     f"(EWMA {self._straggler.ewma(r):.3f}s vs peers)")
            if self.telemetry.enabled:
                self.telemetry.counter("straggler_flagged", 1, rank=r,
                                       epoch=epoch, step=step)
        if self.chaos.enabled and \
                self.chaos.fire_reached("rank_death", step):
            planned = self.chaos.fired[-1][1]
            rank = self.chaos.seed_of("rank_death", planned)
            self._record_chaos("rank_death", step)
            raise RankDeathError(rank, epoch, step)

    # -- dataset splits (generation-tracked for staging-cache keys) ---------

    @property
    def train_split(self) -> cifar10.Split:
        return self._train_split

    @train_split.setter
    def train_split(self, split: cifar10.Split) -> None:
        self._train_split = split
        self._train_gen += 1

    @property
    def test_split(self) -> cifar10.Split:
        return self._test_split

    @test_split.setter
    def test_split(self, split: cifar10.Split) -> None:
        self._test_split = split
        self._test_gen += 1

    # -- device placement ---------------------------------------------------

    def _put(self, images: np.ndarray, labels: np.ndarray):
        return (meshlib.put_global(images, self._batch_sharding),
                meshlib.put_global(np.asarray(labels, np.int32),
                                   self._batch_sharding))

    def _make_fwd_only(self):
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P
        from ..data import augment as aug
        from ..ops.loss import cross_entropy
        from ..parallel.mesh import DATA_AXIS
        from ..train.step import maybe_cast

        def body(params, bn_state, images, labels):
            # host_augment feeds preprocessed f32; otherwise normalize here.
            x = images if self.host_augment else aug.normalize(images)
            x = maybe_cast(x, self.compute_dtype)
            logits, _ = self.apply_fn(params, bn_state, x, train=True)
            return lax.pmean(cross_entropy(logits, labels), DATA_AXIS)

        mapped = shard_map(body, mesh=self.mesh,
                           in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS)),
                           out_specs=P())
        return jax.jit(mapped)

    # -- on-device staging --------------------------------------------------

    def _stage_train_epoch(self, epoch: int):
        """Stage the whole epoch's batches on device: full batches as
        [NB, B, ...] arrays plus the ragged tail batch (or None) separately.

        One host->device transfer per epoch instead of one per batch —
        transfers carry a large fixed cost, and the uint8 epoch is ~150 MB.
        With the reference's never-reshuffled sampler (C6) the staging is
        reused across epochs; the cache is keyed on the split GENERATION
        (bumped by the train_split setter) and (when reshuffling) the epoch,
        so replacing ``train_split`` or enabling reshuffle restages.
        """
        if self.objective.stream:           # every epoch its own sequences
            return self._stage_token_epoch(epoch)
        cache_key = (self._train_gen,
                     epoch if self.reshuffle_each_epoch else 0)
        if self._staged_train is not None and \
                self._staged_train[0] == cache_key:
            return self._staged_train[1]
        if self.elastic is not None and self.elastic.protocol == "strong":
            return self._stage_train_epoch_canonical(epoch, cache_key)
        imgs, labs = [], []
        tail = None
        for i, l in _shard_batches(
                self.train_split, self.world, self.global_batch, epoch,
                shuffle=True, seed=self.seed,
                reshuffle_each_epoch=self.reshuffle_each_epoch):
            if i.shape[0] < self.global_batch:   # ragged tail (always last)
                tail = (meshlib.put_global(i, self._batch_sharding),
                        meshlib.put_global(l.astype(np.int32),
                                           self._batch_sharding))
                break
            imgs.append(i)
            labs.append(l)
            if self.limit_train_batches is not None and \
                    len(imgs) >= self.limit_train_batches:
                break
        if imgs:
            full = (meshlib.put_global(np.stack(imgs), self._epoch_sharding),
                    meshlib.put_global(np.stack(labs).astype(np.int32),
                                       self._epoch_sharding))
        else:  # dataset smaller than one global batch: tail-only epoch
            full = (meshlib.put_global(
                        np.zeros((0, self.global_batch, 32, 32, 3), np.uint8),
                        self._epoch_sharding),
                    meshlib.put_global(
                        np.zeros((0, self.global_batch), np.int32),
                        self._epoch_sharding))
        staged = (full[0], full[1], tail)
        self._staged_train = (cache_key, staged)
        return staged

    def _token_epoch_batches(self) -> int:
        """Full batches in an epoch of the token stream: as many as
        `limit_train_batches` says (a stream has no length of its own),
        else the file's sequences, else the synthetic stand-in's 64."""
        if self.limit_train_batches is not None:
            return self.limit_train_batches
        nb = len(self.train_split) // self.global_batch
        if nb < 1:
            raise ValueError(
                f"{len(self.train_split)} sequences are less than one "
                f"global batch of {self.global_batch}")
        return nb

    def _stage_token_epoch(self, epoch: int):
        """Epoch `epoch` of the token stream on the device, [NB, B, L]
        int32: its own sequences (`TokenSplit.epoch`), so nothing recurs
        within a run and the staging is inside every epoch, as it is for a
        reshuffled image epoch.  Full batches only: no ragged tail.  The
        second array is the image path's labels' place: 0 per sequence."""
        cache_key = (self._train_gen, epoch)
        if self._staged_train is not None and \
                self._staged_train[0] == cache_key:
            return self._staged_train[1]
        nb = self._token_epoch_batches()
        toks = self.train_split.epoch(epoch, nb * self.global_batch).reshape(
            nb, self.global_batch, self.objective.seq_len)
        staged = (meshlib.put_global(toks, self._epoch_sharding),
                  meshlib.put_global(
                      np.zeros((nb, self.global_batch), np.int32),
                      self._epoch_sharding), None)
        self._staged_train = (cache_key, staged)
        return staged

    def _stage_train_epoch_canonical(self, epoch: int, cache_key):
        """Elastic strong-scaling staging: batch b is canonical positions
        [b*B, (b+1)*B) IN ORDER — contiguous microshards, so sharding dim 1
        over the mesh hands rank r of world M exactly its S/M microshards
        at every M.  The epoch is wrap-padded to FULL global batches (torch
        tiling, ``canonical_epoch_order``): the pinned window has no ragged
        variant, and padding must not depend on the world size."""
        split = self.train_split
        n = len(split.labels)
        nb = -(-n // self.global_batch)              # ceil: pad, don't drop
        if self.limit_train_batches is not None:
            nb = min(nb, self.limit_train_batches)
        order = sharding.canonical_epoch_order(
            n, seed=self.seed, shuffle=True, epoch=epoch,
            reshuffle_each_epoch=self.reshuffle_each_epoch,
            pad_to=nb * self.global_batch)
        idx = order[:nb * self.global_batch]
        imgs = native.gather(split.images, idx).reshape(
            (nb, self.global_batch, 32, 32, 3))
        labs = split.labels[idx].astype(np.int32).reshape(
            (nb, self.global_batch))
        staged = (meshlib.put_global(imgs, self._epoch_sharding),
                  meshlib.put_global(labs, self._epoch_sharding),
                  None)
        self._staged_train = (cache_key, staged)
        return staged

    def _warm_train_windows(self, staged):
        """AOT-compile the 20-iteration window shapes train_model will
        dispatch (full WINDOW and the ragged window) so mid-epoch compiles
        never pollute the timers — the windowed analogue of the reference's
        first-window warmup exclusion.  Idempotent per shape."""
        epoch_images, epoch_labels, _ = staged
        nbatches = epoch_images.shape[0]
        key = jax.random.PRNGKey(self.seed)
        ring_on = self.train_window_ring is not None
        for w in self._window_shape_set(nbatches):
            cache_key = (w, tuple(epoch_images.shape), ring_on)
            if cache_key in self._warmed_window_shapes:
                continue
            with (self.telemetry.span("compile_warmup",
                                      epoch=self._span_epoch,
                                      program="train_window", window=w)
                  if self.telemetry.enabled else NULL_SPAN):
                if ring_on:
                    self.train_window_ring.lower(
                        self.state, self._ring_sds(), key, epoch_images,
                        epoch_labels, jnp.int32(0),
                        jnp.zeros((w,), jnp.int8)).compile()
                else:
                    self.train_window.lower(
                        self.state, key, epoch_images, epoch_labels,
                        jnp.int32(0), jnp.zeros((w,), jnp.int8)).compile()
            self._warmed_window_shapes.add(cache_key)

    def _warm_tail_step(self, tail) -> None:
        """AOT-compile the tail-shape train step (idempotent per shape) so
        the ragged batch's compile never lands inside a timed iteration."""
        cache_key = (tail[0].shape[0], str(tail[0].dtype))
        if cache_key in self._warmed_tail_shapes:
            return
        with (self.telemetry.span("compile_warmup", epoch=self._span_epoch,
                                  program="train_step_tail",
                                  batch=int(tail[0].shape[0]))
              if self.telemetry.enabled else NULL_SPAN):
            self.train_step.lower(
                self.state, jax.random.PRNGKey(self.seed), *tail).compile()
        self._warmed_tail_shapes.add(cache_key)

    def _stage_eval(self):
        cache_key = self._test_gen
        if self._staged_eval is not None and \
                self._staged_eval[0] == cache_key:
            return self._staged_eval[1]
        imgs, labs = [], []
        for i, l in _eval_batches(self.test_split, self.global_batch):
            imgs.append(i)
            labs.append(l.astype(np.int32))
            if self.limit_eval_batches is not None and \
                    len(imgs) >= self.limit_eval_batches:
                break
        staged = (meshlib.put_global(np.stack(imgs), self._epoch_sharding),
                  meshlib.put_global(np.stack(labs), self._epoch_sharding))
        self._staged_eval = (cache_key, staged)
        return staged

    # -- reference-parity loops --------------------------------------------

    def train_model(self, epoch: int, start_step: int = 0) -> WindowedTimers:
        """One training epoch with the reference's print/timing schedule.

        Default mode runs one compiled dispatch per 20-iteration window
        (lax.scan inside), timed with value-fetch fences — the same
        granularity the reference reports at.  ``profile_phases=True``
        switches to the per-step path, which additionally times a
        forward-only program to report the reference's fwd/bwd split.

        ``start_step`` (mid-epoch resume, ft/) skips the first N batches:
        every PRNG fold uses the ABSOLUTE batch index and the sampler is a
        fixed permutation of (seed, epoch), so training [start_step..n)
        after restoring the step checkpoint is bitwise-identical to the
        uninterrupted run's tail (pinned by tests/test_ft.py).
        """
        self._epoch_nf_skipped = 0
        self._epoch_nf_restored = 0
        self.last_epoch_extras = {}
        timers = self._train_model_impl(epoch, start_step)
        if self._guard_on and (self._epoch_nf_skipped
                               or self._epoch_nf_restored):
            self.log(f"Non-finite guard (epoch {epoch}): "
                     f"{self._epoch_nf_skipped} update(s) skipped, "
                     f"{self._epoch_nf_restored} rollback(s)")
        return timers

    def _train_model_impl(self, epoch: int, start_step: int) -> WindowedTimers:
        self._span_epoch = epoch
        if self.profile_phases:
            return self._train_model_per_step(epoch, start_step)
        if self.host_augment:
            return self._train_model_host_windowed(epoch, start_step)
        with self._loop_span("epoch_train"):
            return self._train_model_windowed(epoch, start_step)

    def _loop_span(self, name: str):
        """A span of the dispatch loop, tagged with the epoch its unit
        trains (``test_model`` carries the epoch of the ``train_model``
        before it).  Through a disabled recorder: the shared no-op, and the
        recorder is not touched."""
        if self.telemetry.enabled:
            return self.telemetry.span(name, epoch=self._span_epoch)
        return NULL_SPAN

    def _count_dispatch(self, site: str) -> None:
        """Tally one enqueued program whose result the host will fetch:
        per epoch, dispatches == fetches (``host_round_trips``)."""
        if self.telemetry.enabled:
            self.telemetry.counter("dispatches", 1, site=site,
                                   epoch=self._span_epoch)

    def _train_model_windowed(self, epoch: int,
                              start_step: int) -> WindowedTimers:
        """The default path: device-resident epoch, one dispatch and one
        drain per 20-iteration window, the ragged tail through its own
        step.  Its spans (README "Observability") end where the host's
        state changes: a ``*_dispatch`` span when the jitted call returns,
        a ``*_drain`` / ``*_fetch`` span when the value is on the host;
        between the end of a fetch and the end of the next dispatch nothing
        is in flight and the device waits for the host."""
        tel = self.telemetry
        on = tel.enabled
        span = self._loop_span
        clock = time.perf_counter_ns    # the parity timers' own readings
        if on and not self._collective_stats_emitted:
            with span("obs_emit"):  # lowers the step: a disabled run does not
                self._emit_collective_telemetry()
        timers = WindowedTimers(self.log, telemetry=tel, epoch=epoch)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
        with span("stage_lookup"):
            staged = self._stage_train_epoch(epoch)
            self._warm_train_windows(staged)
        epoch_images, epoch_labels, tail = staged
        nbatches = epoch_images.shape[0]
        start = start_step
        use_ring = self.train_window_ring is not None
        ring = None
        if use_ring:
            with span("ring_alloc"):
                ring = self._make_ring_device()
        ring_writes = 0
        self._check_preempt(epoch, start)
        while start < nbatches:
            # Resume windows re-align to the ABSOLUTE window grid: the
            # emergency checkpoint always lands on a boundary, so the
            # resumed run re-dispatches the exact window shapes the
            # uninterrupted run would — the bitwise-resume invariant does
            # not depend on scan-length-invariance of the compiler.
            w = min(WINDOW - start % WINDOW, nbatches - start)
            t0 = clock()
            # The span is tagged with the gradient-sync strategy so the
            # telemetry timeline attributes window wall time per tier.
            with (tel.span("train_window", epoch=epoch,
                           strategy=self.strategy_name, start=int(start),
                           batches=int(w)) if on else NULL_SPAN):
                with span("window_dispatch"):
                    if use_ring:
                        self.state, ring = self.train_window_ring(
                            self.state, ring, key, epoch_images,
                            epoch_labels, jnp.int32(start),
                            jnp.zeros((w,), jnp.int8))
                        ring_writes += w
                    else:
                        out = self.train_window(
                            self.state, key, epoch_images, epoch_labels,
                            jnp.int32(start), jnp.zeros((w,), jnp.int8))
                        if self._guard_on:
                            self.state, losses, oks = out
                        else:
                            (self.state, losses), oks = out, None
                self._count_dispatch("window")
                with span("window_drain"):
                    # The window's ONE device->host round-trip (with the
                    # ring: the whole buffer), doubling as the completion
                    # fence.
                    if use_ring:
                        buf_host = np.asarray(ring[0])
                    else:
                        losses = np.asarray(losses)
            per_iter = (clock() - t0) / (1e9 * w)
            with span("window_host"):
                self._count_round_trip("window_drain" if use_ring
                                       else "window_fetch")
                if use_ring:
                    oks = self._consume_ring(buf_host, ring_writes, w,
                                             per_iter, timers, epoch)
                    if not self._guard_on:
                        oks = None
                else:
                    for loss in losses:
                        timers.record(float(loss), per_iter)
                if self._nf_chaos_steps and self.chaos.fire_range(
                        "nonfinite_grad", start, start + w):
                    self._record_chaos("nonfinite_grad", next(
                        s for s in self._nf_chaos_steps
                        if start <= s < start + w))
                start += w
                if oks is not None:
                    self._handle_nonfinite(oks, epoch)
                self._rank_boundary(epoch, start, per_iter)
                with span("obs_emit"):  # work a disabled run does not do
                    emit_memory_gauges(tel, epoch=epoch, step=int(start))
                self._check_preempt(epoch, start)
        if tail is not None and start_step <= nbatches:
            # The ragged final batch (drop_last=False parity) through its
            # own compiled step; host-side fold of the batch index keeps the
            # canonical (index, position) key order of both other paths.
            with span("tail_step"):
                with span("tail_dispatch"):
                    self._warm_tail_step(tail)  # no compile in the timer
                    tail_key = jax.random.fold_in(key, nbatches)
                    t0 = clock()
                    out = self.train_step(self.state, tail_key, *tail)
                self._count_dispatch("tail")
                with span("tail_fetch"):
                    loss, ok = self._fetch_step(out)
                # steady=False: this lone per-dispatch sample carries the
                # fixed dispatch latency the amortized window samples do not.
                timers.record(loss, (clock() - t0) / 1e9, steady=False)
                if ok is not None:
                    self._handle_nonfinite(np.asarray([ok]), epoch)
        self.last_epoch_timers = timers
        return timers

    def _train_model_per_step(self, epoch: int,
                              start_step: int = 0) -> WindowedTimers:
        """Per-batch dispatch path: the fwd/bwd phase split
        (``profile_phases``) and/or the host-side augmentation pipeline
        (``host_augment`` — per-batch host work is the point of that mode,
        exactly like the reference's DataLoader workers, so it is
        double-buffered the way theirs is: batch k+1 prepares on a
        producer thread while step k runs, ``_iter_host_batches``)."""
        if self.telemetry.enabled:
            self._emit_collective_telemetry()
        timers = WindowedTimers(self.log, telemetry=self.telemetry,
                                epoch=epoch)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
        step_fn = self.train_step_host if self.host_augment \
            else self.train_step
        self._warm_per_step_tail_shapes()
        if self.host_augment:
            batches = self._iter_host_batches(epoch, start_it=start_step)
        else:
            def device_batches():
                for it, (imgs, labs) in enumerate(_shard_batches(
                        self.train_split, self.world, self.global_batch,
                        epoch, shuffle=True, seed=self.seed,
                        reshuffle_each_epoch=self.reshuffle_each_epoch)):
                    if self.limit_train_batches is not None and \
                            it >= self.limit_train_batches:
                        break
                    if it < start_step:
                        continue
                    yield (it, *self._put(imgs, labs))
            batches = device_batches()
        self._check_preempt(epoch, start_step)
        for it, x, y in batches:
            step_key = jax.random.fold_in(key, it)
            fwd_time = None
            if self.profile_phases:
                t0 = time.time()
                # np.asarray (a real value fetch) is the fence: dispatch
                # is asynchronous, so an unfenced timer would time the
                # enqueue only.
                np.asarray(self._fwd_only(
                    self.state.params, self.state.bn_state, x, y))
                fwd_time = time.time() - t0
            fn = step_fn
            if self._nf_chaos_steps and it in self._nf_chaos_steps and \
                    self.chaos.fire("nonfinite_grad", it):
                # Swap in the NaN-injecting variant for exactly this batch.
                self._record_chaos("nonfinite_grad", it)
                fn = self._chaos_nf_step(bool(self.host_augment))
            t0 = time.time()
            loss, ok = self._fetch_step(fn(self.state, step_key, x, y))
            # The fused step contains its own forward; the separately-timed
            # forward-only program is ONLY used to report the reference's
            # fwd/bwd split (backward ≈ fused − forward) and is excluded
            # from the step time so totals aren't inflated.
            step_time = time.time() - t0
            timers.record(loss, step_time, fwd_time)
            if ok is not None:
                self._handle_nonfinite(np.asarray([ok]), epoch)
            self._check_preempt(epoch, it + 1)
        self.last_epoch_timers = timers
        return timers

    def _host_aug_params(self, n: int, epoch: int, it: int):
        """The counter-based host augmentation stream: deterministic in
        (seed, epoch, iteration) — the analogue of the device path's
        fold_in chain (a different stream, same contract), and the reason
        ALL host-augment execution paths (per-step f32, windowed uint8)
        consume bit-identical crops/flips regardless of thread or dispatch
        timing."""
        rng = np.random.default_rng([self.seed, epoch, it])
        return (rng.integers(0, 9, (n, 2), dtype=np.int32),
                rng.integers(0, 2, (n,), dtype=np.uint8))

    def _host_transform(self, imgs: np.ndarray, n: int, epoch: int,
                        it: int) -> np.ndarray:
        """C++ host-pipeline transform, f32 out (the per-step format: the
        reference DataLoader's ToTensor+Normalize product)."""
        if self.augment:
            return native.augment(imgs, *self._host_aug_params(n, epoch, it))
        return native.normalize(imgs)

    def _host_transform_u8(self, imgs: np.ndarray, n: int, epoch: int,
                           it: int) -> np.ndarray:
        """C++ host-pipeline transform, uint8 out (the windowed staging
        format: same crop/flip stream as ``_host_transform``, normalize
        deferred to the device step — 4x fewer bytes over the link)."""
        if self.augment:
            return native.augment_u8(imgs,
                                     *self._host_aug_params(n, epoch, it))
        return imgs

    def _put_host_augmented(self, imgs: np.ndarray, labs: np.ndarray,
                            epoch: int, it: int):
        """Host-transform one batch and place the resulting f32 batch.

        Runs on the prefetch producer thread; the telemetry span stack is
        thread-local, so these spans nest correctly there."""
        with self.telemetry.span("host_augment"):
            xh = self._host_transform(imgs, len(labs), epoch, it)
        with self.telemetry.span("prefetch_put"):
            return (meshlib.put_global(xh, self._batch_sharding),
                    meshlib.put_global(np.asarray(labs, np.int32),
                                       self._batch_sharding))

    # Prefetched batches queued ahead of the consumer: 2 = one in flight on
    # the producer thread plus one ready — the reference's num_workers=2
    # DataLoader keeps the same depth of completed batches ahead.
    PREFETCH_DEPTH = 2

    def _prefetch_iter(self, fill, depth: Optional[int] = None,
                       stall_timeout_s: Optional[float] = None):
        """Producer-thread prefetch scaffolding shared by both host-augment
        paths: runs ``fill(emit)`` on a daemon thread — ``emit(item)``
        enqueues and returns False once the consumer has gone away — and
        yields the emitted items in order.  ``depth`` overrides the queue
        bound (the chunked windowed path queues per-CHUNK items, so its
        bound is two windows' worth of chunks rather than two windows).
        Every producer exit path enqueues a sentinel (BaseException
        included) so the consumer can never block forever; the consumer
        polls with a timeout and drains the queue before declaring a dead
        producer sentinel-less.  ``stall_timeout_s`` (ft supervision) is
        the consumer-side hard deadline: no item within it while the
        producer looks alive raises ``StagingStalled`` — the recovery
        trigger a detection-only watchdog cannot be (it can't interrupt a
        wedged native call)."""
        q: queue.Queue = queue.Queue(maxsize=depth or self.PREFETCH_DEPTH)
        stop = threading.Event()

        def safe_put(item) -> bool:
            """Enqueue unless the consumer has gone away."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                fill(lambda item: safe_put(("item", item)))
                safe_put(("done", None))
            except BaseException as e:  # noqa: BLE001 — every exit path
                # must enqueue a sentinel or the consumer would block on an
                # empty queue forever; surfaced (and re-raised) there.
                safe_put(("err", e))

        t = threading.Thread(target=produce, daemon=True,
                             name="host-augment-prefetch")
        t.start()
        last_item_t = time.time()
        try:
            while True:
                if self.telemetry.enabled:
                    # Depth BEFORE the blocking get: 0 here means the
                    # consumer is about to stall on the producer — the
                    # pipeline-health signal this gauge exists for.
                    self.telemetry.gauge("prefetch_queue_depth", q.qsize())
                try:
                    kind, payload = q.get(timeout=1.0)
                    last_item_t = time.time()
                except queue.Empty:
                    if t.is_alive():
                        stalled = time.time() - last_item_t
                        if stall_timeout_s is not None and \
                                stalled > stall_timeout_s:
                            raise ftsup.StagingStalled(
                                f"no staged item for {stalled:.1f}s "
                                f"(deadline {stall_timeout_s}s) with the "
                                f"producer thread alive but stuck")
                        continue
                    # Producer exited; its final put may have raced our
                    # timeout, so drain non-blockingly before declaring it
                    # died without a sentinel (only then fail loudly
                    # instead of hanging).
                    try:
                        kind, payload = q.get_nowait()
                    except queue.Empty:
                        raise RuntimeError(
                            "host-augment prefetch thread exited without "
                            "delivering a batch or a completion sentinel")
                if kind == "done":
                    break
                if kind == "err":
                    raise payload
                yield payload
        finally:
            stop.set()
            t.join(timeout=10)
            if t.is_alive():
                self.log("warning: host-augment prefetch thread did not "
                         "exit within 10s")

    def _iter_host_batches(self, epoch: int, start_it: int = 0):
        """Double-buffered host-augment pipeline: yields ``(it, x, y)`` with
        batch k+1 gathered, C++-augmented and device-put on a producer
        thread while step k runs on device — the reference's
        DataLoader-worker overlap (``Part 1/main.py:96-101``), which the
        previously-serial per-step path lacked (VERDICT r3 item 6).

        The host RNG stream is counter-based in (seed, epoch, it)
        (``_host_transform``), so the prefetched stream is BIT-IDENTICAL
        to the serial one regardless of thread timing — pinned by
        tests/test_cli_and_profiling.py.  ``start_it`` (mid-epoch resume)
        skips earlier batches; the absolute ``it`` keys the stream, so the
        suffix is the uninterrupted run's suffix."""
        def fill(emit):
            for it, (imgs, labs) in enumerate(_shard_batches(
                    self.train_split, self.world, self.global_batch,
                    epoch, shuffle=True, seed=self.seed,
                    reshuffle_each_epoch=self.reshuffle_each_epoch)):
                if self.limit_train_batches is not None and \
                        it >= self.limit_train_batches:
                    break
                if it < start_it:
                    continue
                if not emit((it, *self._put_host_augmented(
                        imgs, labs, epoch, it))):
                    return

        return self._prefetch_iter(
            fill,
            stall_timeout_s=self.ft.stall_timeout_s
            if self._supervise else None)

    def _chunk_cap(self) -> int:
        """Batches per staging chunk: WINDOW split into ``host_chunks``
        equal transfers (ceil — the last chunk of a window may be ragged,
        ``_chunk_plan``)."""
        return -(-WINDOW // self.host_chunks)

    def _chunk_plan(self, w: int):
        """The chunk sizes the streaming producer emits for a ``w``-batch
        window: fixed-capacity chunks plus a ragged last.  Shared by the
        producer's flush boundaries and the assembly-program warmup (a
        skewed copy of this arithmetic would warm the wrong arity and pay
        a mid-epoch compile)."""
        cap = self._chunk_cap()
        sizes = [cap] * (w // cap)
        if w % cap:
            sizes.append(w % cap)
        return sizes

    def _probe_put_aliases_host(self, buf: np.ndarray) -> bool:
        """Does ``put_global`` of a committed numpy array on this backend
        ALIAS the host memory instead of copying it?  jax's CPU client
        zero-copies suitably-aligned numpy buffers straight into device
        arrays — under aliasing, rewriting a retired arena row would
        corrupt chunks already handed to the consumer, so the producer puts
        a private copy there instead.  The copy only costs where no real
        host->device link exists; exactly where one does (TPU/GPU), device
        memory is separate, the put must copy, and the arena stays
        zero-copy.  Probed EMPIRICALLY on an actual arena row (aliasing
        depends on backend, sharding layout and buffer alignment, not just
        the backend name)."""
        before = int(buf.flat[0])
        x = meshlib.put_global(buf, self._epoch_sharding)
        jax.block_until_ready(x)
        buf.flat[0] = np.uint8(before ^ 0xFF)
        aliased = int(np.asarray(jax.device_get(x)).flat[0]) != before
        buf.flat[0] = before
        return aliased

    def _chunk_arena(self, cap: int) -> native.StagingArena:
        """The reusable chunk-aligned staging arena (built lazily; rebuilt
        when the chunk shape changes, e.g. a test monkeypatching WINDOW).
        First build also runs the backend aliasing probe that decides
        zero-copy vs copied puts."""
        arena = self._staging_arena
        if arena is not None and arena.chunk_batches == cap:
            return arena
        # Slot budget: the prefetch queue holds up to two windows' worth of
        # transferred chunks (_iter_host_window_chunks' depth) while one
        # more fills; +2 margin so the producer only stalls on a genuinely
        # full pipe, never on arena starvation.
        chunks_per_window = len(self._chunk_plan(WINDOW))
        self._staging_arena = native.StagingArena(
            2 * chunks_per_window + 2, cap, self.global_batch)
        # Probe EVERY slot: aliasing is a per-buffer property (the CPU
        # client's 64-byte alignment criterion — StagingArena docstring),
        # and one aliased slot among non-aliased ones corrupts the stream
        # just as surely, so any aliasing at all flips the path to copies.
        self._staging_put_copies = any(
            self._probe_put_aliases_host(self._staging_arena.buffer(s))
            for s in range(self._staging_arena.nslots))
        return self._staging_arena

    def _on_put_timeout(self, elapsed_s: float) -> None:
        """Watchdog callback: a chunk device_put exceeded its deadline —
        detection-only (the put may still complete); counted so a slow link
        shows up in telemetry before it becomes a stall."""
        if self.telemetry.enabled:
            self.telemetry.counter("staging_put_timeout")
        self.log(f"ft: chunk device_put exceeded its "
                 f"{self.ft.put_timeout_s}s watchdog deadline "
                 f"({elapsed_s:.1f}s elapsed)")

    def _on_put_retry(self, attempt: int, exc: BaseException) -> None:
        if self.telemetry.enabled:
            self.telemetry.counter("staging_put_retry")
        self.log(f"ft: chunk device_put attempt {attempt + 1} failed "
                 f"({exc!r}); retrying with backoff")

    def _supervised_put(self, src, lo: int, hi: int):
        """A chunk ``put_global`` under ft supervision: chaos injection
        (``put_fail`` raises once, ``put_delay`` sleeps past the watchdog
        once — both keyed to the chunk's ABSOLUTE batch range [lo, hi)),
        a detection-only watchdog on the put itself, and bounded
        exponential-backoff retry.  Without an FTConfig this is exactly
        ``meshlib.put_global``."""
        if not self._supervise:
            return meshlib.put_global(src, self._epoch_sharding)

        def attempt():
            if self.chaos.enabled and \
                    self.chaos.fire_range("put_fail", lo, hi):
                self._record_chaos("put_fail", lo)
                raise ChaosError(
                    f"injected transient chunk device_put failure "
                    f"(batches [{lo}, {hi}))")
            delay = self.chaos.enabled and \
                self.chaos.fire_range("put_delay", lo, hi)
            with ftsup.Watchdog(self.ft.put_timeout_s,
                                on_timeout=self._on_put_timeout):
                if delay:
                    self._record_chaos("put_delay", lo)
                    time.sleep(2.0 * self.ft.put_timeout_s)
                return meshlib.put_global(src, self._epoch_sharding)

        return ftsup.call_with_retry(
            attempt, attempts=self.ft.put_retries,
            backoff_base_s=self.ft.backoff_base_s,
            on_retry=self._on_put_retry)

    def _iter_host_window_chunks(self, epoch: int, start_it: int = 0):
        """Chunked, double-buffered windowed host-augment pipeline (round
        6).  Round 5 staged each window as ONE blocking whole-window
        ``put_global``: the host->device link idled while the previous
        window computed.  Here the producer thread fills
        chunk-aligned arena rows via the FUSED C++ gather+augment
        (``native.gather_augment_u8`` — straight from the resident dataset
        into the staging row, collapsing the former gather -> augment ->
        np.stack three-copy chain to one) and ``put_global``s each chunk
        individually, so window w+1's chunk transfers overlap the
        consumer's dispatch of window w; the consumer reassembles the
        device-resident chunks (``_assemble_chunks``) and dispatches the
        scanned window exactly as round 5 did.  Buffers stay UINT8
        (crop/flip host-side, normalize fused into the device step): the
        path's roofline is the host->device link, and uint8 quarters its
        traffic.

        Yields ``("chunk", (k, x[k,B,...]u8, y[k,B]i32, last))`` — ``last``
        marks a window boundary — and ``("tail", (it, x, y))`` for the
        ragged final batch (its own per-step f32 shape, exactly as round
        5).  Batches are augmented with their ABSOLUTE iteration index
        (``_host_aug_params``), so the crop/flip stream is bit-identical to
        the per-step and whole-window paths regardless of ``host_chunks``
        or thread timing — pinned by tests/test_cli_and_profiling.py.

        ``start_it`` (mid-epoch resume / producer restart) skips earlier
        batches; chunk/window boundaries use ABSOLUTE batch arithmetic so
        a restarted stream stays on the same window grid.  Under an
        FTConfig the puts run supervised (``_supervised_put``), the arena
        fence wait gets a watchdog, and ``verify_chunks`` checksums every
        staged row at fill time and re-stages any row whose bytes changed
        by flush time (the buffer-reuse corruption the ``corrupt_slot``
        chaos site injects) — repair is a re-augment keyed by the same
        absolute index, so the repaired stream is bit-identical."""
        cap = self._chunk_cap()
        arena = self._chunk_arena(cap)   # probe runs pre-thread, main thread
        nfull, _ = self._per_rank_batch_counts()
        nlim = nfull if self.limit_train_batches is None \
            else min(nfull, self.limit_train_batches)
        fence_timeout = self.ft.put_timeout_s if self._supervise else None
        stall_timeout = self.ft.stall_timeout_s if self._supervise else None

        def fill(emit):
            split = self.train_split
            chunk_x = None       # arena row block for the chunk being filled
            slot = -1
            chunk_y: list = []
            chunk_meta: list = []   # (absolute it, cols) per filled row
            chunk_sums: list = []   # fill-time crc32 per row (verify_chunks)

            def fill_row(row, cols, it) -> None:
                if self.augment:
                    native.gather_augment_u8(
                        split.images, cols,
                        *self._host_aug_params(len(cols), epoch, it),
                        out=row)
                else:
                    native.gather(split.images, cols, out=row)

            def on_fence_timeout(elapsed_s):
                if self.telemetry.enabled:
                    self.telemetry.counter("staging_fence_timeout")
                self.log(f"ft: arena slot fence exceeded its "
                         f"{fence_timeout}s watchdog deadline")

            def inject_and_verify(k: int, lo: int) -> None:
                """Chaos byte corruption + checksum verify/repair, between
                fill and put — the window where a buffer-reuse bug would
                really strike."""
                if self.chaos.enabled:
                    for s in self.chaos.steps("corrupt_slot"):
                        if lo <= s < lo + k and \
                                self.chaos.fire("corrupt_slot", s):
                            self._record_chaos("corrupt_slot", s)
                            rng = self.chaos.rng("corrupt_slot", s)
                            flat = chunk_x[s - lo].reshape(-1)
                            pos = rng.integers(0, flat.size, size=8)
                            flat[pos] ^= np.uint8(rng.integers(1, 256))
                if not self._verify_chunks:
                    return
                for j in ftsup.verify_checksums(chunk_x[:k], chunk_sums):
                    it_j, cols_j = chunk_meta[j]
                    if self.telemetry.enabled:
                        self.telemetry.counter("staging_corruption_repaired")
                    self.log(f"ft: staged batch {it_j} failed its checksum; "
                             f"re-staging from the resident dataset")
                    fill_row(chunk_x[j], cols_j, it_j)
                    if ftsup.verify_checksums([chunk_x[j]],
                                              [chunk_sums[j]]):
                        raise ftsup.StagingStalled(
                            f"staged batch {it_j} fails its checksum even "
                            f"after re-staging — arena memory is unsafe")

            def flush(last: bool) -> bool:
                nonlocal chunk_x, slot
                k = len(chunk_y)
                if k == 0:
                    return True
                lo = chunk_meta[0][0]
                inject_and_verify(k, lo)
                with self.telemetry.span("chunk_put", batches=k, last=last):
                    src = chunk_x[:k]
                    if self._staging_put_copies:
                        src = src.copy()
                    x = self._supervised_put(src, lo, lo + k)
                    y = self._supervised_put(
                        np.asarray(chunk_y, np.int32), lo, lo + k)
                if not self._staging_put_copies:
                    arena.retire(slot, x)
                chunk_x, slot = None, -1
                chunk_y.clear()
                chunk_meta.clear()
                chunk_sums.clear()
                return emit(("chunk", (k, x, y, last)))

            for it, cols in enumerate(_shard_batch_cols(
                    len(split.labels), self.world, self.global_batch,
                    epoch, shuffle=True, seed=self.seed,
                    reshuffle_each_epoch=self.reshuffle_each_epoch)):
                if self.limit_train_batches is not None and \
                        it >= self.limit_train_batches:
                    break
                if it < start_it:
                    continue
                if self.chaos.enabled and \
                        self.chaos.fire("producer_crash", it):
                    self._record_chaos("producer_crash", it)
                    raise ChaosError(
                        f"injected staging producer crash at batch {it}")
                if len(cols) < self.global_batch:   # ragged tail (last)
                    if not flush(last=True):        # defensive: nlim
                        return                      # boundary flushed it
                    emit(("tail", (it, *self._put_host_augmented(
                        native.gather(split.images, cols),
                        split.labels[cols], epoch, it))))
                    return
                if chunk_x is None:
                    slot, chunk_x = arena.acquire(
                        fence_timeout_s=fence_timeout,
                        on_timeout=on_fence_timeout)
                with self.telemetry.span("host_augment"):
                    row = chunk_x[len(chunk_y)]
                    fill_row(row, cols, it)
                chunk_y.append(split.labels[cols])
                chunk_meta.append((it, cols))
                if self._verify_chunks:
                    chunk_sums.append(ftsup.batch_checksums([row])[0])
                boundary = (it + 1) % WINDOW == 0 or (it + 1) == nlim
                if (len(chunk_y) == cap or boundary) and \
                        not flush(last=boundary):
                    return

        # Per-CHUNK queue items: bound the pipe at two windows' worth of
        # chunks — same two-windows-ahead depth round 5's PREFETCH_DEPTH=2
        # gave whole-window items.
        return self._prefetch_iter(
            fill, depth=2 * len(self._chunk_plan(WINDOW)),
            stall_timeout_s=stall_timeout)

    def _iter_host_window_chunks_sync(self, epoch: int, start_it: int = 0):
        """Degraded-mode staging: the chunked pipeline's item protocol
        (``("chunk", ...)``/``("tail", ...)``) produced SYNCHRONOUSLY on
        the consumer thread — no producer thread, no arena, one k=1 chunk
        per batch from a private buffer.  This is the graceful-degradation
        target after staging failures exhaust their restart budget: it
        loses the transfer/compute overlap but keeps the stream
        BIT-IDENTICAL — augmentation is keyed by the absolute batch index
        and window results are chunk-composition independent (the K1-vs-K2
        pin in tests/test_cli_and_profiling.py), so the windows dispatched
        downstream are exactly the ones the healthy pipeline would have
        dispatched."""
        nfull, _ = self._per_rank_batch_counts()
        nlim = nfull if self.limit_train_batches is None \
            else min(nfull, self.limit_train_batches)
        split = self.train_split
        for it, cols in enumerate(_shard_batch_cols(
                len(split.labels), self.world, self.global_batch,
                epoch, shuffle=True, seed=self.seed,
                reshuffle_each_epoch=self.reshuffle_each_epoch)):
            if self.limit_train_batches is not None and \
                    it >= self.limit_train_batches:
                break
            if it < start_it:
                continue
            if len(cols) < self.global_batch:   # ragged tail
                yield ("tail", (it, *self._put_host_augmented(
                    native.gather(split.images, cols),
                    split.labels[cols], epoch, it)))
                return
            buf = np.empty((1, self.global_batch, 32, 32, 3), np.uint8)
            with self.telemetry.span("host_augment"):
                if self.augment:
                    native.gather_augment_u8(
                        split.images, cols,
                        *self._host_aug_params(len(cols), epoch, it),
                        out=buf[0])
                else:
                    native.gather(split.images, cols, out=buf[0])
            with self.telemetry.span("chunk_put", batches=1, degraded=True):
                x = meshlib.put_global(buf, self._epoch_sharding)
                y = meshlib.put_global(
                    np.asarray([split.labels[cols]], np.int32),
                    self._epoch_sharding)
            last = (it + 1) % WINDOW == 0 or (it + 1) == nlim
            yield ("chunk", (1, x, y, last))

    def _per_rank_batch_counts(self):
        """(nfull, tail_per): full per-rank batch count and ragged per-rank
        tail size, from the sampler's ceil wrap-padding — the ONE
        derivation shared by every warmup that must predict the epoch's
        dispatch shapes (a skewed copy yields a mid-epoch compile landing
        inside a timed window)."""
        per = self.global_batch // self.world
        per_rank = -(-len(self.train_split.labels) // self.world)
        return divmod(per_rank, per)

    @staticmethod
    def _window_shape_set(nbatches: int):
        """Distinct scan-window lengths a windowed epoch of ``nbatches``
        full batches dispatches: the full WINDOW plus the ragged last
        group.  Shared by the device and host windowed warmups."""
        shapes = {min(WINDOW, nbatches)} if nbatches else set()
        if nbatches % WINDOW:
            shapes.add(nbatches % WINDOW)
        return shapes

    def _host_window_shapes(self):
        """The window sizes _iter_host_window_chunks will close with a
        ``last`` chunk, computed host-side so compiles can be warmed up
        front."""
        nfull, _ = self._per_rank_batch_counts()
        if self.limit_train_batches is not None:
            nfull = min(nfull, self.limit_train_batches)
        return self._window_shape_set(nfull)

    def _train_model_host_windowed(self, epoch: int,
                                   start_step: int = 0) -> WindowedTimers:
        """Windowed host-augment epoch: scanned dispatches over
        chunk-staged C++-augmented buffers (``_iter_host_window_chunks``),
        the reference's print/timing schedule.  The default host-augment
        mode since round 5 — the per-step path remains under
        ``profile_phases`` (where per-batch dispatch is the point).

        Under an FTConfig this is also the supervised path: a staging
        failure (producer death, injected or real; consumer stall past the
        deadline) discards the partially-assembled window and restarts the
        producer from the last TRAINED step — once — then degrades to
        synchronous per-batch staging (``_iter_host_window_chunks_sync``).
        Both recoveries preserve the training stream bitwise: re-staged
        batches are keyed by absolute index, and ``trained`` only advances
        at dispatched-window granularity, so nothing is half-applied."""
        if self.telemetry.enabled:
            self._emit_collective_telemetry()
        timers = WindowedTimers(self.log, telemetry=self.telemetry,
                                epoch=epoch)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
        self._warm_per_step_tail_shapes()
        # Warm the window + assembly compiles so none lands inside a timed
        # window.
        host_ring = self.train_window_host_ring is not None
        for w in self._host_window_shapes():
            cache_key = ("host", w, self.global_batch, host_ring)
            if cache_key not in self._warmed_window_shapes:
                x_sds = jax.ShapeDtypeStruct(
                    (w, self.global_batch, 32, 32, 3), jnp.uint8,
                    sharding=self._epoch_sharding)
                y_sds = jax.ShapeDtypeStruct(
                    (w, self.global_batch), jnp.int32,
                    sharding=self._epoch_sharding)
                with self.telemetry.span("compile_warmup",
                                         program="train_window_host",
                                         window=w):
                    if host_ring:
                        self.train_window_host_ring.lower(
                            self.state, self._ring_sds(), key, x_sds, y_sds,
                            jnp.int32(0), jnp.zeros((w,), jnp.int8)).compile()
                    else:
                        self.train_window_host.lower(
                            self.state, key, x_sds, y_sds, jnp.int32(0),
                            jnp.zeros((w,), jnp.int8)).compile()
                self._warmed_window_shapes.add(cache_key)
            pattern = tuple(self._chunk_plan(w))
            if len(pattern) > 1:
                akey = ("assemble", pattern, self.global_batch)
                if akey not in self._warmed_window_shapes:
                    def _sds(c, trailing, dtype):
                        return jax.ShapeDtypeStruct(
                            (c, self.global_batch) + trailing, dtype,
                            sharding=self._epoch_sharding)
                    with self.telemetry.span("compile_warmup",
                                             program="assemble_chunks",
                                             chunks=len(pattern)):
                        self._assemble_chunks.lower(
                            *[_sds(c, (32, 32, 3), jnp.uint8)
                              for c in pattern]).compile()
                        self._assemble_chunks.lower(
                            *[_sds(c, (), jnp.int32)
                              for c in pattern]).compile()
                    self._warmed_window_shapes.add(akey)
        trained = start_step            # absolute batches applied to state
        ring = self._make_ring_device() if host_ring else None
        ring_writes = 0
        restarts_left = self.ft.producer_restarts if self._supervise else 0
        self._check_preempt(epoch, trained)

        def make_iter(start):
            if self.staging_degraded:
                return self._iter_host_window_chunks_sync(epoch, start)
            return self._iter_host_window_chunks(epoch, start)

        chunk_iter = make_iter(trained)
        chunks_x, chunks_y = [], []
        while True:
            try:
                # chunk_wait: how long the consumer stalls on the producer —
                # with healthy overlap this is ~0 except at the first window.
                with self.telemetry.span("chunk_wait"):
                    item = next(chunk_iter, None)
            except Exception as e:
                # Staging failed: the producer died (ChaosError or a real
                # exception re-raised by _prefetch_iter) or the consumer's
                # stall deadline fired (StagingStalled).  Nothing trained
                # from the lost chunks — drop the partial window and
                # re-stage from ``trained``; the counter-keyed stream makes
                # the retake bit-identical.
                if not self._supervise:
                    raise
                self.producer_failures += 1
                if self.telemetry.enabled:
                    self.telemetry.counter("producer_failure",
                                           error=type(e).__name__)
                try:
                    chunk_iter.close()
                except Exception:  # pragma: no cover - best-effort cleanup
                    pass
                chunks_x, chunks_y = [], []
                if restarts_left > 0:
                    restarts_left -= 1
                    if self.telemetry.enabled:
                        self.telemetry.counter("producer_restart")
                    self.log(f"ft: staging failed at step {trained} "
                             f"({type(e).__name__}: {e}); restarting the "
                             f"producer from step {trained}")
                    chunk_iter = make_iter(trained)
                    continue
                self.staging_degraded = True
                if self.telemetry.enabled:
                    self.telemetry.counter("staging_degraded")
                self.log(f"ft: staging failed again at step {trained} "
                         f"({type(e).__name__}: {e}); restart budget "
                         f"exhausted — degrading to synchronous per-batch "
                         f"staging (stream unchanged, overlap lost)")
                chunk_iter = make_iter(trained)
                continue
            if item is None:
                break
            kind, payload = item
            if kind == "tail":   # ragged tail through its own per-step shape
                it, x, y = payload
                t0 = time.time()
                out = self.train_step_host(
                    self.state, jax.random.fold_in(key, it), x, y)
                loss, ok = self._fetch_step(out)  # value fetch = fence
                # steady=False: lone per-dispatch sample carries the fixed
                # dispatch latency the amortized window samples do not.
                timers.record(loss, time.time() - t0, steady=False)
                trained = it + 1
                if ok is not None:
                    self._handle_nonfinite(np.asarray([ok]), epoch)
                self._check_preempt(epoch, trained)
                continue
            k, x, y, last = payload
            chunks_x.append(x)
            chunks_y.append(y)
            if self.telemetry.enabled:
                self.telemetry.gauge("window_chunks_pending", len(chunks_x))
            if not last:
                continue
            # Window boundary: assemble the device-resident chunks and
            # dispatch ONE scanned window, exactly as round 5 (a
            # single-chunk window skips the concatenate — the K=1
            # degenerate case IS round 5's whole-window path).
            if len(chunks_x) == 1:
                xw, yw = chunks_x[0], chunks_y[0]
            else:
                xw = self._assemble_chunks(*chunks_x)
                yw = self._assemble_chunks(*chunks_y)
            chunks_x, chunks_y = [], []
            w = int(xw.shape[0])
            t0 = time.time()
            # start=trained: dynamic_slice clamps it to 0 for these
            # exact-length window arrays (value-identical), while making
            # the scan's step indices ABSOLUTE — which is what the
            # compiled-in nonfinite-chaos masks are keyed by.
            if host_ring:
                self.state, ring = self.train_window_host_ring(
                    self.state, ring, key, xw, yw, jnp.int32(trained),
                    jnp.zeros((w,), jnp.int8))
                ring_writes += w
                buf_host = np.asarray(ring[0])  # one fetch = fence
            else:
                out = self.train_window_host(
                    self.state, key, xw, yw, jnp.int32(trained),
                    jnp.zeros((w,), jnp.int8))
                if self._guard_on:
                    self.state, losses, oks = out
                else:
                    (self.state, losses), oks = out, None
                losses = np.asarray(losses)  # value fetch = fence
            per_iter = (time.time() - t0) / w
            self._count_round_trip("window_drain" if host_ring
                                   else "window_fetch")
            if host_ring:
                oks = self._consume_ring(buf_host, ring_writes, w, per_iter,
                                         timers, epoch)
                if not self._guard_on:
                    oks = None
            else:
                for loss in losses:
                    timers.record(float(loss), per_iter)
            if self._nf_chaos_steps and self.chaos.fire_range(
                    "nonfinite_grad", trained, trained + w):
                self._record_chaos("nonfinite_grad", next(
                    s for s in self._nf_chaos_steps
                    if trained <= s < trained + w))
            trained += w
            if oks is not None:
                self._handle_nonfinite(oks, epoch)
            self._rank_boundary(epoch, trained, per_iter)
            emit_memory_gauges(self.telemetry, epoch=epoch, step=int(trained))
            self._check_preempt(epoch, trained)
        self.last_epoch_timers = timers
        return timers

    def _warm_per_step_tail_shapes(self) -> None:
        """AOT-compile the ragged-tail shapes of the per-step programs.

        The full-batch compile lands in the first (warmup) window, which the
        reference's protocol excludes — but the tail arrives at the LAST
        iteration, squarely inside steady state, where a fresh multi-second
        compile would corrupt steady_step_times and the epoch total.  Warm
        both per-step programs at the tail shape up front instead."""
        nfull, tail_per = self._per_rank_batch_counts()
        will_train_tail = tail_per and (self.limit_train_batches is None
                                        or self.limit_train_batches > nfull)
        if not will_train_tail:
            return
        tb = tail_per * self.world
        dtype = np.float32 if self.host_augment else np.uint8
        dtype_name = np.dtype(dtype).name
        x = jax.ShapeDtypeStruct((tb, 32, 32, 3), dtype,
                                 sharding=self._batch_sharding)
        y = jax.ShapeDtypeStruct((tb,), jnp.int32,
                                 sharding=self._batch_sharding)
        key = jax.random.PRNGKey(self.seed)
        step_fn = self.train_step_host if self.host_augment \
            else self.train_step
        if (tb, dtype_name) not in self._warmed_tail_shapes:
            with self.telemetry.span("compile_warmup",
                                     program="per_step_tail", batch=tb):
                step_fn.lower(self.state, key, x, y).compile()
            self._warmed_tail_shapes.add((tb, dtype_name))
        if self.profile_phases and \
                ("fwd", tb, dtype_name) not in self._warmed_tail_shapes:
            with self.telemetry.span("compile_warmup",
                                     program="fwd_only_tail", batch=tb):
                self._fwd_only.lower(
                    self.state.params, self.state.bn_state, x, y).compile()
            self._warmed_tail_shapes.add(("fwd", tb, dtype_name))

    def test_model(self) -> Tuple[float, int, float]:
        """Full-test-set evaluation in one dispatch; prints the reference's
        line (``Part 1/main.py:74-76``): per-batch-averaged CE, correct/total,
        %."""
        span = self._loop_span
        with span("eval"):
            with span("eval_stage_lookup"):
                images, labels = self._stage_eval()
            with span("eval_dispatch"):
                out = self.eval_window(self.state, images, labels)
            self._count_dispatch("eval")
            with span("eval_fetch"):
                # All values in ONE fetch, inside the span so it covers
                # real device work: the evaluation's one round trip.
                loss_sum, corr, *counted = jax.device_get(out)
            self._count_round_trip("eval")
        n = len(self.test_split.labels)
        if self.limit_eval_batches is not None:
            n = min(n, self.limit_eval_batches * self.global_batch)
        # Reference divides the accumulated per-batch mean losses by the
        # number of batches; we accumulate per-example sums, so divide by n
        # (equal when batches are full; exact even on the ragged tail).
        avg_loss = float(loss_sum) / n
        correct = int(corr)
        # A decoder's "correct" is out of the masked tokens it predicted,
        # which its eval window counts; an image model's out of the images.
        total = int(counted[0]) if counted else n
        acc = 100.0 * correct / max(total, 1)
        if self.telemetry.enabled:
            self.telemetry.gauge("eval", {"avg_loss": avg_loss,
                                          "correct": correct, "total": total,
                                          "accuracy": correct / max(total, 1)})
        self.log("Test set: Average loss: {:.4f}, Accuracy: {}/{} ({:.0f}%)\n"
                 .format(avg_loss, correct, total, acc))
        return avg_loss, correct, acc

    def _elastic_meta(self, epoch: int) -> dict:
        """Topology + data-order metadata written into every checkpoint
        sidecar (round 6): enough for ``elastic.protocol.plan_resume`` to
        map saved progress onto a DIFFERENT world size, plus per-rank
        data-order keys so a dataset/seed drift under the checkpoint fails
        loudly at resume time instead of silently desynchronizing the
        example stream.  Written for every run, elastic or not — that is
        the forward-compat half of the story (old checkpoints without it
        restore as world=1 via ``elastic.protocol.world_of``)."""
        from ..elastic.protocol import rank_data_keys
        meta = {
            "world": self.world,
            "global_batch": self.global_batch,
            "seed": self.seed,
            "reshuffle_each_epoch": self.reshuffle_each_epoch,
            "rank_keys": list(rank_data_keys(
                len(self.train_split.labels), self.world, seed=self.seed,
                epoch=epoch,
                reshuffle_each_epoch=self.reshuffle_each_epoch)),
        }
        if self.elastic is not None:
            meta["protocol"] = self.elastic.protocol
            if self.elastic.protocol == "strong":
                meta["microshards"] = self.elastic.microshards
        return meta

    def _data_order_meta(self, epoch: int, step: int) -> dict:
        """The mid-epoch sidecar's ``data_order`` payload: the historical
        resume keys plus the round-6 topology metadata."""
        return {
            "seed": self.seed, "epoch": epoch, "step": step,
            "reshuffle_each_epoch": self.reshuffle_each_epoch,
            **self._elastic_meta(epoch),
        }

    def _plan_elastic_resume(self, meta: Optional[dict],
                             start_step: int) -> int:
        """Map a mid-epoch checkpoint's progress onto THIS trainer's world
        size.  Strong scaling carries the step counter over unchanged
        (batch b covers the same canonical positions at every world); weak
        scaling re-derives it from example progress.  Validates the saved
        per-rank data-order keys against this dataset/seed first."""
        from ..elastic.protocol import (flat_meta, plan_resume,
                                        validate_rank_keys)
        flat = flat_meta(meta)
        if not flat:
            return start_step
        validate_rank_keys(flat, len(self.train_split.labels))
        plan = plan_resume(
            flat, self.world, protocol=self.elastic.protocol,
            microshards=(self.elastic.microshards
                         if self.elastic.protocol == "strong" else None),
            default_global_batch=self.global_batch)
        self.resume_plan = plan
        if plan.old_world != plan.new_world:
            self.log(
                f"elastic: resuming world {plan.old_world} -> "
                f"{plan.new_world} ({plan.protocol}); start step "
                f"{start_step} -> {plan.start_step}"
                + (f", {plan.examples_replayed} example(s) replayed"
                   if plan.examples_replayed else ""))
        return plan.start_step

    def _ckpt_state_like(self, meta: Optional[dict]):
        """(state_like, saved_world) for a checkpoint restore.  When the
        save's world differs from this trainer's (elastic resume), the comm
        stack on disk is (saved_world, ...) — build the abstract tree at
        that shape, replicated (the new mesh need not divide the old
        world); ``_absorb_restored`` reshards after the restore.  Params/
        BN/momentum are world-invariant and restore directly."""
        comm = self.state.opt_state.comm
        if comm is None:
            return self.state, self.world
        from ..elastic.protocol import flat_meta
        flat = flat_meta(meta)
        saved = int(flat.get("world") or self.world)
        if saved == self.world:
            return self.state, saved
        rep = meshlib.replicated(self.mesh)
        resized = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                (saved,) + tuple(a.shape[1:]), a.dtype, sharding=rep),
            comm)
        return self.state._replace(
            opt_state=self.state.opt_state._replace(comm=resized)), saved

    def _absorb_restored(self, state, saved_world: int):
        """Finish a restore: on a world mismatch, map the restored
        (saved_world, ...) comm stack onto this world — sum-conserving for
        error-feedback residuals (strategies.reshard_comm) — and re-commit
        the dual sharding layout (_commit_state)."""
        if state.opt_state.comm is None or saved_world == self.world:
            return state
        comm = strategies.reshard_comm(
            jax.device_get(state.opt_state.comm), self.world)
        return self._commit_state(
            state._replace(opt_state=state.opt_state._replace(comm=comm)))

    def run(self, epochs: int = 1,
            checkpoint_dir: Optional[str] = None,
            profile_dir: Optional[str] = None,
            publish_dir: Optional[str] = None,
            publish_every: int = 1) -> None:
        """The reference's run(): epochs of train + eval with epoch timing.

        With ``checkpoint_dir`` set, resumes from the latest saved epoch (if
        any) and persists the full TrainState after every completed epoch —
        beyond-parity (the reference keeps state only in memory); resume is
        bitwise-exact, see train/checkpoint.py.

        With ``profile_dir`` set, the first trained epoch is captured as a
        ``jax.profiler`` trace (XPlane; viewable in TensorBoard/Perfetto) —
        the superset of the reference's print-based timers promised in
        SURVEY.md §5.

        Preemption (ft/): while running, SIGTERM/SIGINT request a stop at
        the next step boundary — the in-flight dispatch finishes, an
        EMERGENCY mid-epoch checkpoint (state + (epoch, step)) is written
        if a checkpoint dir is configured, and run() returns with
        ``self.preempted`` set.  A later run() against the same dir resumes
        from that exact step — every PRNG fold and the sampler are keyed by
        (seed, epoch, absolute step), so the interrupted+resumed run is
        bitwise identical to an uninterrupted one (pinned by
        tests/test_ft.py).

        With ``publish_dir`` set, the serving half of the state (params +
        BatchNorm stats) is published as a versioned, crc-checksummed
        weight bundle every ``publish_every`` completed epochs — the
        train side of the publish/ hot-swap loop: a live serving process
        watching that directory installs each version between dispatches
        without restarts or recompiles (see cs744_ddp_tpu/publish/)."""
        start_epoch = 0
        start_step = 0
        mngr = None
        if checkpoint_dir is not None:
            from .checkpoint import CheckpointManager
            # param_tree digests the full state structure (shapes+dtypes),
            # so two "custom" models or any architecture drift fail the
            # guard; real_data catches the silent synthetic-fallback case
            # (same config keys, different dataset).
            # comm is EXCLUDED from the digest: its leaves are (world, ...)
            # stacks, and an elastic resume legitimately changes world —
            # the "strategy"/"compress_rank" keys pin its identity instead.
            digest_state = self.state._replace(
                opt_state=self.state.opt_state._replace(comm=None))
            param_tree = jax.tree.map(
                lambda a: f"{a.dtype}{list(a.shape)}", digest_state)
            config = {
                "model": self.model_name, "strategy": self.strategy_name,
                "compress_rank": self.compress_rank,
                "seed": self.seed, "precision": self.precision,
                "global_batch": self.global_batch, "world": self.world,
                "augment": self.augment,
                "reshuffle_each_epoch": self.reshuffle_each_epoch,
                "lr": self.sgd_cfg.lr, "momentum": self.sgd_cfg.momentum,
                "weight_decay": self.sgd_cfg.weight_decay,
                "limit_train_batches": self.limit_train_batches,
                "real_data": self.real_data,
                "state_digest": str(param_tree)}
            # Building the manager is what imports orbax (seconds: the span
            # says where a checkpointed start spent them), here and not in
            # a save: the emergency save must find it loaded.
            with self.telemetry.span("checkpoint_open"):
                mngr = CheckpointManager(checkpoint_dir, config=config,
                                         elastic=self.elastic is not None)
            # Mid-epoch (emergency) checkpoints outrank the epoch series
            # exactly when they are AHEAD of it: the emergency save for
            # epoch k is newer than the epoch k-1 save it coexists with,
            # and stale (cleared, but tolerate a crash between save and
            # clear) once epoch k itself completes.
            mid = mngr.latest_mid_epoch()
            le = mngr.latest_epoch()
            if mid is not None and (le is None or mid[0] > le):
                like, saved_world = self._ckpt_state_like(
                    mngr.mid_epoch_meta())
                restored, start_epoch, start_step = \
                    mngr.restore_mid_epoch(like)
                self.state = self._absorb_restored(restored, saved_world)
                if self.elastic is not None:
                    start_step = self._plan_elastic_resume(
                        mngr.mid_epoch_meta(), start_step)
                self.log(f"Resumed from mid-epoch checkpoint: epoch "
                         f"{start_epoch}, step {start_step}")
            elif le is not None:
                like, saved_world = self._ckpt_state_like(mngr.epoch_meta())
                restored, start_epoch = mngr.restore(like)
                self.state = self._absorb_restored(restored, saved_world)
                self.log(f"Resumed from checkpoint: epoch {start_epoch}")
            if self._nf_policy == "restore" and \
                    (mid is not None or le is not None):
                self._snapshot_rollback()   # rollback point = restored state
        publisher = None
        if publish_dir is not None:
            if publish_every < 1:
                raise ValueError(f"publish_every must be >= 1, "
                                 f"got {publish_every}")
            from ..publish import WeightPublisher
            from .checkpoint import publish_fingerprint
            digest_state = self.state._replace(
                opt_state=self.state.opt_state._replace(comm=None))
            param_tree = jax.tree.map(
                lambda a: f"{a.dtype}{list(a.shape)}", digest_state)
            publisher = WeightPublisher(
                publish_dir,
                fingerprint=publish_fingerprint({
                    "model": self.model_name,
                    "strategy": self.strategy_name,
                    "seed": self.seed, "precision": self.precision,
                    "global_batch": self.global_batch,
                    "state_digest": str(param_tree)}),
                telemetry=self.telemetry, chaos=self.chaos)
        try:
            if mngr is not None or self._supervise:
                self._preempt_guard = PreemptionGuard(log=self.log).install()
            if start_epoch >= epochs:
                self.log(f"All {epochs} epoch(s) already checkpointed; "
                         f"nothing to run"
                         + (" (profile_dir ignored)" if profile_dir else ""))
            for epoch in range(start_epoch, epochs):
                t0 = time.time()
                try:
                    if profile_dir is not None and epoch == start_epoch:
                        with jax.profiler.trace(profile_dir):
                            self.train_model(epoch, start_step=start_step)
                    else:
                        self.train_model(epoch, start_step=start_step)
                except PreemptedError as e:
                    self.preempted = True
                    if self.telemetry.enabled:
                        self.telemetry.counter("preemptions",
                                               epoch=e.epoch, step=e.step)
                    if mngr is not None:
                        with self.telemetry.span("checkpoint_save_mid_epoch",
                                                 epoch=e.epoch, step=e.step):
                            mngr.save_mid_epoch(
                                e.epoch, e.step, self.state,
                                data_order=self._data_order_meta(
                                    e.epoch, e.step))
                        self.log(f"Preempted at epoch {e.epoch} step "
                                 f"{e.step}; emergency checkpoint saved")
                    else:
                        self.log(f"Preempted at epoch {e.epoch} step "
                                 f"{e.step}; no checkpoint dir — progress "
                                 f"since the last save is lost")
                    return
                except RankDeathError as e:
                    if self.telemetry.enabled:
                        self.telemetry.counter("rank_deaths", rank=e.rank,
                                               epoch=e.epoch, step=e.step)
                    if mngr is not None:
                        with self.telemetry.span("checkpoint_save_mid_epoch",
                                                 epoch=e.epoch, step=e.step):
                            mngr.save_mid_epoch(
                                e.epoch, e.step, self.state,
                                data_order=self._data_order_meta(
                                    e.epoch, e.step))
                        self.log(f"Rank {e.rank} died at epoch {e.epoch} "
                                 f"step {e.step}; emergency checkpoint "
                                 f"saved")
                    else:
                        self.log(f"Rank {e.rank} died at epoch {e.epoch} "
                                 f"step {e.step}; no checkpoint dir — "
                                 f"progress since the last save is lost")
                    self.rank_death = (e.rank, e.epoch, e.step)
                    return
                start_step = 0
                self.log(f"Training time after {epoch + 1} epoch is "
                         f"{time.time() - t0}")
                if self.telemetry.enabled:
                    self.telemetry.gauge("epoch_time_s", time.time() - t0,
                                         epoch=epoch)
                    self._emit_device_gauges(epoch)
                    emit_memory_gauges(self.telemetry, epoch=epoch)
                self.test_model()
                if mngr is not None:
                    with self.telemetry.span("checkpoint_save", epoch=epoch):
                        mngr.save(epoch, self.state,
                                  meta=self._elastic_meta(epoch))
                    mngr.clear_mid_epoch()
                    if self._nf_policy == "restore":
                        self._snapshot_rollback()   # advance rollback point
                if publisher is not None \
                        and (epoch + 1) % publish_every == 0:
                    with self.telemetry.span("publish", epoch=epoch):
                        rec = publisher.publish(self.state)
                    self.log(f"Published weights v{rec['version']} "
                             f"({rec['bytes']} B, {rec['leaves']} leaves) "
                             f"to {publish_dir}")
                if self._preempt_guard is not None and \
                        self._preempt_guard.requested:
                    # The signal landed during eval/save: the epoch boundary
                    # just persisted IS the resume point — stop cleanly.
                    self.preempted = True
                    self.log(f"Preemption requested; stopping after epoch "
                             f"{epoch} completed")
                    return
        finally:
            if self._preempt_guard is not None:
                self._preempt_guard.uninstall()
                self._preempt_guard = None
            if mngr is not None:
                mngr.close()
