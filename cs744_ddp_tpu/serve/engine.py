"""AOT batch-bucketed single-chip inference engine.

Dynamic request sizes meet a compiler that specializes on shapes: compiling
one program per request size would pay XLA compile latency on the serving
path (seconds, vs a sub-millisecond forward).  The standard resolution is a
fixed LADDER of batch buckets (e.g. {1, 8, 32, 128, 256}), every executable
AOT-compiled at startup; a request batch of n images is padded to the
smallest covering bucket and the pad rows are masked out of every reduced
quantity with the SAME label = -1 convention the training eval path uses
(``train/step.py::masked_eval_counts``), so serving and eval accounting
cannot drift apart.  Per-row outputs (logits) are sliced back to n; with
``train=False`` BatchNorm (running stats) every row is computed
independently of its batchmates, so the sliced logits are BITWISE-identical
(f32) to an unpadded direct forward — pinned in tests/test_serve.py.

The forward program mirrors the windowed host path's transfer-compact
design: uint8 in, the normalize fused into the XLA program
(``data/augment.normalize``), optional bf16 compute with f32 logits out.

Warm start: executables are looked up in a ``serve.cache.ExecutableCache``
before compiling (and saved after), on top of the repo-wide persistent XLA
compilation cache — cold vs warm startup seconds are in every
``startup()`` report (``--serve-frontend`` prints it per replica), not an
anecdote.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from ..models.serving import INGEST_VERSION, make_u8_forward
from ..obs import NULL
from ..utils import compcache
from .cache import ExecutableCache, cache_key
from .ingest import StagedIngest

BUCKETS = (1, 8, 32, 128, 256)

_DTYPES = {"f32": None}  # "bf16" resolved lazily (jnp import)


class DispatchHandle:
    """One in-flight asynchronous dispatch (``infer_counts_async``):
    the device-side result references plus the metadata ``complete``
    needs to fence, slice, and attribute it.  Opaque to callers."""

    __slots__ = ("logits", "loss_sum", "correct", "n", "bucket", "traces",
                 "t_issue")

    def __init__(self, logits, loss_sum, correct, n, bucket, traces,
                 t_issue):
        self.logits = logits
        self.loss_sum = loss_sum
        self.correct = correct
        self.n = n
        self.bucket = bucket
        self.traces = traces
        self.t_issue = t_issue


class InferenceEngine:
    """The executable ladder + padded/masked dispatch for one model.

    ``state`` is a ``TrainState`` (or any object with ``params`` /
    ``bn_state``) — typically restored from a training checkpoint; when
    omitted the model is seed-initialized (the demo mode, where
    latency is the subject and weights are irrelevant).
    """

    def __init__(self, model: str = "vgg11", *,
                 buckets: Sequence[int] = BUCKETS,
                 precisions: Sequence[str] = ("f32",),
                 state=None, seed: int = 0, telemetry=NULL,
                 cache_dir: Optional[str] = None,
                 use_staging: bool = True,
                 enable_compilation_cache: bool = True,
                 device=None):
        import jax
        import jax.numpy as jnp

        from ..models import get_model
        from ..train.step import init_train_state

        if not buckets:
            raise ValueError("need at least one bucket")
        if sorted(set(buckets)) != list(buckets):
            raise ValueError(f"buckets must be strictly increasing, got "
                             f"{tuple(buckets)}")
        for p in precisions:
            if p not in ("f32", "bf16"):
                raise ValueError(f"unknown precision {p!r}")
        if enable_compilation_cache:
            # The repo-wide persistent XLA cache: dedupes ladder compiles
            # across server restarts for engines built without a
            # ``cache_dir`` (no serialized-executable warm start).
            compcache.enable_persistent_compilation_cache()
        self.model_name = model
        self.buckets: Tuple[int, ...] = tuple(buckets)
        self.precisions: Tuple[str, ...] = tuple(precisions)
        self.telemetry = telemetry
        init_fn, apply_fn = get_model(model)
        if state is None:
            state = init_train_state(init_fn, jax.random.PRNGKey(seed))
        self.params = state.params
        self.bn_state = state.bn_state
        # Bumped by install_weights() (publish/ hot-swap); tagged into
        # every Reply so the A/B pin is checkable per request.
        self.weights_version = 0
        # Replica pinning: with an explicit device, weights live there and
        # every lowering bakes a SingleDeviceSharding for it, so N replicas
        # occupy N distinct mesh devices instead of piling onto device 0.
        self.device = device
        if device is not None:
            self.params = jax.device_put(self.params, device)
            self.bn_state = jax.device_put(self.bn_state, device)
        self._cache = ExecutableCache(cache_dir)
        self._exec: Dict[Tuple[int, str], Any] = {}
        self._ingest = (StagedIngest(max(self.buckets), device=device)
                        if use_staging else None)
        self._jax = jax

        self._forward = {"f32": make_u8_forward(apply_fn),
                         "bf16": make_u8_forward(apply_fn, jnp.bfloat16)}

        # Everything an executable's identity depends on beyond the bucket
        # and dtype: the abstract model signature (param/bn shapes+dtypes,
        # not values), the fused-ingest scheme, and the toolchain/device
        # KIND.  Not the device itself: the cache loads an entry onto
        # whichever device asks (serve/cache.py), so replicas share entries.
        d0 = device if device is not None else jax.devices()[0]
        self._exec_device = d0
        leaves, treedef = jax.tree_util.tree_flatten(
            (self.params, self.bn_state))
        self._key_fields = {
            "model": model,
            "ingest": INGEST_VERSION,
            "abstract": (str(treedef),
                         tuple((l.shape, str(l.dtype)) for l in leaves)),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": d0.device_kind,
        }

    # -- weight hot-swap ----------------------------------------------------

    def install_weights(self, params, bn_state, version: int, *,
                        assume_staged: bool = False) -> None:
        """Flip the engine's weight references to a new version.

        Weights are runtime ARGUMENTS of the AOT executables (certified
        unbaked by the audit's baked-constants rule), so this is a pure
        reference swap: no executable is touched, nothing recompiles.
        The new tree must match the abstract signature the ladder was
        compiled against — shape/dtype/structure drift would silently
        desync the executables from their arguments, so it is rejected
        here rather than at the next dispatch.

        NOT internally synchronized: the caller must guarantee no
        dispatch is concurrently reading ``self.params`` (the scheduler
        runs installs at its loop boundary via ``request_install``, when
        the worker — the only dispatcher — is provably between batches).

        ``assume_staged=True`` skips the device_put (the watcher stages
        leaves onto this engine's device beforehand, off the serving
        worker's critical path).
        """
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((params, bn_state))
        want_treedef, want_leaves = self._key_fields["abstract"]
        got = (str(treedef), tuple((l.shape, str(l.dtype)) for l in leaves))
        if got != (want_treedef, want_leaves):
            raise ValueError(
                f"install_weights: tree does not match the abstract "
                f"signature the executable ladder was compiled against "
                f"(model {self.model_name!r})")
        if not assume_staged and self.device is not None:
            params = jax.device_put(params, self.device)
            bn_state = jax.device_put(bn_state, self.device)
        self.params = params
        self.bn_state = bn_state
        self.weights_version = int(version)
        if self.telemetry.enabled:
            self.telemetry.counter("weights_installed", version=version)

    # -- ladder -------------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest bucket covering ``n`` requests."""
        if n < 1:
            raise ValueError(f"need at least one image, got {n}")
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"request batch {n} exceeds the largest bucket "
                         f"{self.buckets[-1]}; split it upstream "
                         f"(the micro-batcher never builds one this big)")

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def _abstract_args(self, bucket: int):
        import jax
        import jax.numpy as jnp
        if self.device is not None:
            from jax.sharding import SingleDeviceSharding
            sh = SingleDeviceSharding(self.device)
            to_s = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                  sharding=sh)
            return (jax.tree_util.tree_map(to_s, self.params),
                    jax.tree_util.tree_map(to_s, self.bn_state),
                    jax.ShapeDtypeStruct((bucket, 32, 32, 3), jnp.uint8,
                                         sharding=sh),
                    jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=sh))
        to_s = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)
        return (jax.tree_util.tree_map(to_s, self.params),
                jax.tree_util.tree_map(to_s, self.bn_state),
                jax.ShapeDtypeStruct((bucket, 32, 32, 3), jnp.uint8),
                jax.ShapeDtypeStruct((bucket,), jnp.int32))

    def startup(self) -> dict:
        """Build the whole ladder (cache-load or AOT-compile every
        (bucket, precision) executable); returns the timing report
        (seconds and source, ``cache`` or compiled, per rung; ``warm`` when
        every rung came from the cache)."""
        import jax

        t0 = time.time()
        per: Dict[str, dict] = {}
        for prec in self.precisions:
            for b in self.buckets:
                t1 = time.time()
                source = self._build(b, prec)
                name = f"{b}/{prec}" if len(self.precisions) > 1 else str(b)
                per[name] = {"seconds": round(time.time() - t1, 4),
                             "source": source}
        report = {
            "startup_s": round(time.time() - t0, 4),
            "per_bucket": per,
            "warm": all(v["source"] == "cache" for v in per.values()),
            "executable_cache": self._cache.stats(),
            "backend": jax.default_backend(),
            "device_id": int(self._exec_device.id),
        }
        if self.telemetry.enabled:
            self.telemetry.gauge("serve_startup_s", report["startup_s"],
                                 warm=report["warm"])
        return report

    def lowered(self, bucket: int, precision: str = "f32"):
        """Pre-compile lowering of one ladder rung — what the program
        auditor (``analysis/audit.audit_serving``) inspects."""
        jit = self._jax.jit(self._forward[precision])
        return jit.lower(*self._abstract_args(bucket))

    def lowered_hlo(self, bucket: int, precision: str = "f32") -> str:
        """Pre-optimization HLO text of one ladder rung."""
        return self.lowered(bucket, precision) \
            .compiler_ir(dialect="hlo").as_hlo_text()

    def _build(self, bucket: int, precision: str) -> str:
        """Cache-load or AOT-compile (and save) one ladder rung; returns
        which it was (``"cache"`` / ``"compile"``)."""
        key = cache_key(bucket=bucket, precision=precision,
                        **self._key_fields)
        compiled = self._cache.load(key, self._exec_device)
        source = "cache"
        if compiled is None:
            source = "compile"
            if self.telemetry.enabled:
                with self.telemetry.span("serve_compile", bucket=bucket,
                                         precision=precision):
                    compiled = self.lowered(bucket, precision).compile()
            else:
                compiled = self.lowered(bucket, precision).compile()
            self._cache.save(key, compiled)
        self._exec[(bucket, precision)] = compiled
        return source

    def _executable(self, bucket: int, precision: str):
        if (bucket, precision) not in self._exec:
            self._build(bucket, precision)   # direct use without startup()
        return self._exec[(bucket, precision)]

    # -- dispatch -----------------------------------------------------------

    def _pad_stage(self, images: np.ndarray, bucket: int):
        """Pad the request batch to ``bucket`` rows and move it to device
        (double-buffered arena staging when available; plain padded copy
        otherwise)."""
        if self._ingest is not None:
            return self._ingest.stage(images, bucket)
        padded = np.zeros((bucket, 32, 32, 3), np.uint8)
        padded[:len(images)] = images
        return padded

    def infer_counts(self, images: np.ndarray, labels=None, *,
                     precision: str = "f32",
                     trace_ids: Sequence[int] = ()):
        """Forward a request batch of n <= max_batch images.

        Returns ``(logits[n, 10] f32, loss_sum, correct)``; pad rows carry
        label -1 and contribute NOTHING to loss_sum/correct (the
        ``masked_eval_counts`` convention).  Unlabeled requests (labels
        None) get all -1 labels, so both counts are exactly 0.

        ``trace_ids`` (micro-batcher, telemetry runs) are the riding
        requests' trace ids; the dispatch/fetch spans carry them so every
        device dispatch is attributable to the exact requests it served.
        """
        images = np.ascontiguousarray(images, np.uint8)
        n = images.shape[0]
        bucket = self.bucket_for(n)
        ex = self._executable(bucket, precision)
        padded_labels = np.full((bucket,), -1, np.int32)
        if labels is not None:
            padded_labels[:n] = np.asarray(labels, np.int32)
        tel = self.telemetry
        if tel.enabled:
            tel.counter(f"serve_bucket_{bucket}")
            traces = list(trace_ids)
            with tel.span("serve_stage", bucket=bucket, n=n,
                          traces=traces):
                staged = self._pad_stage(images, bucket)
            with tel.span("serve_dispatch", bucket=bucket, n=n,
                          traces=traces):
                logits, loss_sum, correct = ex(self.params, self.bn_state,
                                               staged, padded_labels)
            with tel.span("serve_fetch", bucket=bucket, traces=traces):
                out = np.asarray(logits)[:n]
                counts = (float(loss_sum), int(correct))
        else:
            staged = self._pad_stage(images, bucket)
            logits, loss_sum, correct = ex(self.params, self.bn_state,
                                           staged, padded_labels)
            out = np.asarray(logits)[:n]
            counts = (float(loss_sum), int(correct))
        return out, counts[0], counts[1]

    # -- pipelined dispatch (issue / complete split) ------------------------

    def infer_counts_async(self, images: np.ndarray, labels=None, *,
                           precision: str = "f32",
                           trace_ids: Sequence[int] = ()) -> DispatchHandle:
        """Issue one padded bucket dispatch WITHOUT fencing it.

        jax dispatch is asynchronous: the executable call returns device
        array futures immediately, so the caller can stage and issue the
        NEXT batch (the second ``StagedIngest`` slot) while this one
        computes.  The two-slot arena bounds the depth: at most
        ``self._ingest.nslots`` dispatches may be in flight before
        ``complete`` retires one (the scheduler enforces exactly 2,
        ``scheduler.PIPELINE_SLOTS``).  Resolve with ``complete(handle)``
        — every issued handle MUST be completed, in issue order, or its
        result (and its arena slot) is leaked.
        """
        images = np.ascontiguousarray(images, np.uint8)
        n = images.shape[0]
        bucket = self.bucket_for(n)
        ex = self._executable(bucket, precision)
        padded_labels = np.full((bucket,), -1, np.int32)
        if labels is not None:
            padded_labels[:n] = np.asarray(labels, np.int32)
        tel = self.telemetry
        traces = tuple(trace_ids)
        if tel.enabled:
            tel.counter(f"serve_bucket_{bucket}")
            with tel.span("serve_stage", bucket=bucket, n=n,
                          traces=list(traces)):
                staged = self._pad_stage(images, bucket)
        else:
            staged = self._pad_stage(images, bucket)
        t_issue = time.time()
        logits, loss_sum, correct = ex(self.params, self.bn_state,
                                       staged, padded_labels)
        return DispatchHandle(logits, loss_sum, correct, n, bucket,
                              traces, t_issue)

    def complete(self, handle: DispatchHandle,
                 prev_done: Optional[float] = None):
        """Fence one in-flight dispatch and fetch its results.

        Returns ``(logits[n, 10] f32, loss_sum, correct, t_ready)`` —
        bitwise-identical rows to the serial ``infer_counts`` path (same
        executable, same staged bytes).  ``prev_done`` (the previous
        completion's ``t_ready``) clips this dispatch's telemetry span to
        the window the device actually worked on it: with two in flight,
        batch N+1's wall interval overlaps batch N's, and the honest
        per-dispatch occupancy is ``t_ready - max(t_issue, prev_done)``
        — what the waterfall's device_compute stage and the scheduler's
        EWMA read.
        """
        self._jax.block_until_ready(handle.logits)
        t_ready = time.time()
        tel = self.telemetry
        if tel.enabled:
            start = handle.t_issue if prev_done is None \
                else max(handle.t_issue, float(prev_done))
            tel.span_event("serve_dispatch", start,
                           max(t_ready - start, 0.0), bucket=handle.bucket,
                           n=handle.n, traces=list(handle.traces))
            with tel.span("serve_fetch", bucket=handle.bucket,
                          traces=list(handle.traces)):
                out = np.asarray(handle.logits)[:handle.n]
                counts = (float(handle.loss_sum), int(handle.correct))
        else:
            out = np.asarray(handle.logits)[:handle.n]
            counts = (float(handle.loss_sum), int(handle.correct))
        return out, counts[0], counts[1], t_ready

    def infer(self, images: np.ndarray, *,
              precision: str = "f32") -> np.ndarray:
        """Logits [n, 10] f32 for n <= max_batch uint8 images."""
        logits, _, _ = self.infer_counts(images, precision=precision)
        return logits
