"""Benchmark: steady-state CIFAR-10 training throughput (images/sec/chip).

Emission contract (VERDICT r5 item 1): the FINAL stdout line is a COMPACT
JSON head ({"metric", "value", "unit", "vs_baseline", "headline_stats",
MFU fields}) guaranteed to fit the driver's 2000-byte tail capture — the
full result grew past that bound in rounds 4/5 and the driver recorded
``parsed: null``.  The full payload is printed as an EARLIER stdout line
and written to a sidecar file (``BENCH_FULL.json``, committed) named by
the head's ``full_payload_file`` field; ``emit_result`` implements and
tests pin both.  The full payload carries

  * ``headline_stats`` — all N=3 independent headline runs with best /
    median / min (noise robustness on a shared host whose contention is
    one-sided; the BEST run is the least-contaminated estimate of device
    capability, the same rationale as ``timeit``'s min-latency convention —
    median and min are reported alongside so the spread is visible).
    Every per-config measurement (headline runs, matrix, peak, sweep) is
    itself best-of-2 on one staged trainer, so a single contaminated
    window cannot land in the output verbatim and all entries carry the
    same statistic,
  * ``matrix``  — per-(strategy x model) throughput over all available
    chips, the reference's strategy-cost spectrum
    (``/root/reference/src/Part 2a/main.py:83-112`` vs ``Part 2b`` vs
    ``Part 3`` — its entire pedagogical point), each entry with
    ``tflops_per_sec`` and ``mfu_vs_bf16_peak`` derived from XLA's cost
    model of the compiled step (197 TFLOP/s bf16 peak per v5e chip), and
  * ``scaling`` — a 1..N-device WEAK-scaling sweep (per-chip batch held
    constant) with efficiency vs the 1-device run (the BASELINE.json north
    star: >=90% images/sec/chip efficiency 1->8 chips) and per-point MFU,
    plus a ``strong`` sub-section measuring the reference's own protocol
    (global batch 256 divided across workers).  On a 1-chip host the sweep
    is degenerate ({"1": ...}, efficiency 1.0); the harness itself is
    exercised on the 8-virtual-device CPU mesh in tests/test_bench.py,
  * ``convergence`` — the reference's correctness oracle (test accuracy,
    ``Part 1/main.py:74-76``) as a per-epoch TRAJECTORY over 3 epochs at
    the reference config, plus a ``stable_lr`` companion entry (1 epoch
    at lr 0.01 — a faster-learning control the CI floor rides on; see
    BASELINE.md "Synthetic-task recalibration (round 7)" for the graded
    trajectory the stand-in now shows), labeled ``real_data`` false when the
    synthetic fallback is in use (this host has no egress), and
  * ``spectrum`` — static per-strategy collective counts, comm bytes and
    dependency-chain depths from the TPU v5e-8 AOT lowering (the strategy
    tiers' cost AND latency shapes, independent of wall-clock noise), and
  * ``compression`` — the round-7 gradient-compression cost sheet
    (``run_compression``): per-tier MEASURED collective result bytes
    from the pre-optimization lowering (with the ratio vs the
    uncompressed per-param tier), interleaved min-over-rounds epoch
    wall clock, and the convergence delta vs the uncompressed tier
    after an identical training schedule, and
  * ``host_pipeline`` — chunked windowed ``--host-augment`` throughput
    (the reference's DataLoader-worker model; bounded by the
    host->device link, not the chip, see BASELINE.md), alongside the measured
    pure-``device_put`` LINK FLOOR on synthetic and real-entropy bytes
    (``measure_link_floor``) so the path's target is a fraction of
    measured hardware rather than a round number, plus a ``chunk_sweep``
    over the staging chunk count K, and
  * ``robustness`` — the fault-tolerance layer's cost/benefit sheet
    (``run_robustness``): non-finite-guard throughput overhead, the
    degraded synchronous staging fallback as a fraction of the healthy
    chunked pipeline, emergency mid-epoch checkpoint save/restore wall
    clock with the steps-lost accounting, and a deterministic
    chaos-injected NaN-skip demo, and
  * ``serving`` — the inference fast path (``run_serving``,
    ``cs744_ddp_tpu/serve/``): throughput-vs-bucket curve over the AOT
    executable ladder (per-dispatch fenced latency AND the amortized
    device-program time — the two differ by the per-dispatch host cost),
    client-side latency
    p50/p95/p99 under a seeded open-loop arrival trace at 2-3 offered
    loads, and COLD vs WARM startup seconds measured in fresh
    subprocesses sharing one executable-cache dir (the warm-start
    acceptance bar: warm < 0.5 x cold), and
  * ``pipeline`` — the round-14 dispatch-pipeline cost sheet
    (``run_pipeline``): per-rung serial vs pipelined steady-state
    per-dispatch time vs the device-program floor (``gap_closed``),
    capacity goodput with the scheduler pipeline on vs off over the same
    seeded traces, and the pipelined capacity point's stage waterfall
    (staging / device-compute / fetch) with the two-slot occupancy
    distribution and the per-bucket measured-over-cost-prior ratio, and
  * ``attribution`` — the round-8 performance-attribution sheet
    (``run_attribution``): the static cost model
    (``analysis/costmodel.py``) over every zoo program's lowering
    (analytic FLOPs/HBM/wire bytes -> roofline bound, MFU ceiling,
    comm/compute ratio; overlap's exposed-comm bound vs ddp's chained
    plan) plus a measured MFU join of the headline windowed program's
    steady-state wall clock against its own audited lowering.

Protocol (BASELINE.md): the reference's own measurement design — windowed
wall-clock fenced by fetching the loss values, the first window (compile +
warmup) excluded — global batch 256, SGD(0.1, 0.9, 1e-4).  Bench windows
are EPOCH-LENGTH (one compiled dispatch per pass over the data): every
dispatch carries a fixed host cost (launch + the fencing fetch) that a
steady-state device rate must amortize away; the end-to-end epoch time a
user of the 20-iteration CLI path pays is a separate number (ROADMAP S3).
The parity path (Trainer.train_model) keeps the reference's 20-iteration
reporting.

vs_baseline: the reference publishes no numbers (BASELINE.json
"published": {}), so the comparison point is the reference's own stack
measured on this host — torch CPU VGG-11 fwd+bwd+step at batch 256
(tools/bench_torch_baseline.py: 38.9 images/sec; see BASELINE.md).
"""

import argparse
import json
import os
import statistics
import sys
from typing import Optional

# Reference stack on this host (torch CPU, batch 256): images/sec.
# Measured with tools/bench_torch_baseline.py (38.9 img/s); see BASELINE.md.
TORCH_CPU_BASELINE_IPS = 38.9

# MFU denominator: the bf16 peak of the device that ran (197 TFLOP/s per v5e
# chip; f32 configs use the same denominator since TPU f32 matmuls run bf16
# multiply passes).  Single source: analysis/costmodel.py's peak table
# keyed by device_kind (jax-free), shared with the roofline tooling.
from cs744_ddp_tpu.analysis.costmodel import (  # noqa: E402
    mfu_fields as _costmodel_mfu_fields)

MODELS = ("vgg11", "resnet18")
STRATEGIES = ("gather", "allreduce", "ddp")
# Deep-model rows measured in the matrix beyond the full strategy cross:
# the deep end of both families, ddp only (at world=1 the strategy spread
# is near-zero information — BASELINE.md "1-chip strategy matrix" — but
# depth-scaling regressions like the per-family BN fence choice show up
# exactly here; VERDICT r4 item 7).
DEEP_ROWS = (("vgg19", "ddp"), ("resnet34", "ddp"))
HEADLINE_RUNS = 3


def _make_trainer(model: str, strategy: str, num_devices, *,
                  global_batch: int, data_dir: str, log,
                  precision: str = "f32", sgd_cfg=None, **extra):
    """Central Trainer construction; ``extra`` passes through any further
    Trainer kwargs (host_augment, limit_train_batches, ...)."""
    from cs744_ddp_tpu.train.loop import Trainer
    if sgd_cfg is not None:
        extra["sgd_cfg"] = sgd_cfg
    return Trainer(model=model, strategy=strategy, num_devices=num_devices,
                   global_batch=global_batch, data_dir=data_dir,
                   precision=precision, log=log, **extra)


def _throughput(model: str, strategy: str, num_devices, *, global_batch: int,
                max_iters: int, data_dir: str, log,
                precision: str = "f32", want_flops: bool = False,
                repeats: int = 1, flops_log=None):
    """(images/sec/chip, flops_per_image | None) for one configuration.

    ``repeats`` > 1 re-measures on the SAME staged/compiled trainer and
    keeps the best — host contention is one-sided, and a single
    contaminated measurement otherwise lands in the output verbatim (a
    round-3 trial's matrix entry read 30% low this way).

    ``flops_log`` receives the MFU-unavailable reason (the trainer's own
    ``log`` is suppressed in bench runs to mute the print schedule)."""
    trainer = _make_trainer(model, strategy, num_devices,
                            global_batch=global_batch, data_dir=data_dir,
                            precision=precision, log=log)
    # Epoch-length windows: one compiled dispatch per pass over the data
    # (see steady_state_throughput's docstring re dispatch latency).
    ips_per_chip = max(
        trainer.steady_state_throughput(
            max_iters=max_iters, window_iters="epoch")[1]
        for _ in range(max(repeats, 1)))
    flops = trainer.step_flops_per_image(log=flops_log) if want_flops else None
    return ips_per_chip, flops


def _mfu_fields(ips_per_chip: float, flops_per_image) -> dict:
    """tflops_per_sec / mfu_vs_bf16_peak for one chip's throughput on the
    device this process measured on (delegates to analysis/costmodel.
    mfu_fields — the one copy of the arithmetic, rounding and the peak
    table keyed by ``device_kind``; no MFU for a device outside it)."""
    import jax
    return _costmodel_mfu_fields(ips_per_chip, flops_per_image,
                                 jax.devices()[0].device_kind)


def _matrix_pairs(ndev: int, models, strategies, deep_rows):
    """The (model, strategy) rows the matrix measures.

    At world=1 every strategy's sync collapses to a no-op, so the full
    strategy cross is near-duplicate rows for zero information
    (BASELINE.md "1-chip strategy matrix": spread within noise) — prune to
    ONE strategy per model ("ddp", the flagship, or the first offered) and
    reinvest the minutes in the bf16 deep row run_bench adds.  Deep rows
    append beyond the cross either way."""
    if ndev > 1:
        pairs = [(m, s) for m in models for s in strategies]
    else:
        keep = "ddp" if "ddp" in strategies else strategies[0]
        pairs = [(m, keep) for m in models]
    pairs += [tuple(r) for r in deep_rows if tuple(r) not in pairs]
    return pairs


def measure_link_floor(log, *, global_batch: int, ndev: int,
                       trials: int = 5) -> dict:
    """Pure host->device goodput floor for the chunked staging path: time
    nothing but ``put_global`` of WINDOW-sized uint8 buffers (the exact
    shape/sharding the producer ships) and convert to an images/sec/chip
    CEILING for the host pipeline.  Two byte distributions, so that a
    transport whose rate depends on content shows up as a difference
    between them instead of hiding in the floor:

      * ``synthetic`` — the class-templated synthetic split this
        egress-less bench host actually trains on (highly repetitive),
        and
      * ``real_entropy`` — real CIFAR-10 images from the committed
        tests/assets fixture, tiled to fill the window (``unique_mib``
        records how little unique content backs the tiling — an upper
        bound on how compressible-in-principle the buffer is).

    The host_pipeline target derived from this is "achieved >= X% of the
    matching measured floor" (BASELINE.md, VERDICT item 3 closure) —
    regression-tracked against hardware, not a round number."""
    import time as _time

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cs744_ddp_tpu.data import cifar10
    from cs744_ddp_tpu.parallel import mesh as meshlib
    from cs744_ddp_tpu.utils.metrics import WINDOW

    mesh = meshlib.make_mesh(None)
    sharding = NamedSharding(mesh, P(None, meshlib.DATA_AXIS))
    shape = (WINDOW, global_batch, 32, 32, 3)
    per_image = 32 * 32 * 3
    buf_mib = WINDOW * global_batch * per_image / 2**20

    def _fill_tiled(images: np.ndarray) -> np.ndarray:
        flat = images.reshape(-1, 32, 32, 3)
        reps = -(-WINDOW * global_batch // len(flat))
        tiled = np.tile(flat, (reps, 1, 1, 1))[:WINDOW * global_batch]
        return np.ascontiguousarray(tiled.reshape(shape))

    def _measure(buf: np.ndarray) -> dict:
        # Two alternating source buffers so no put can be served from a
        # same-object cache; they diverge by a per-trial byte flip.  Both
        # are copies: buf may alias the memoized (read-only) split.
        bufs = [buf.copy(), buf.copy()]
        best = float("inf")
        for t in range(trials + 1):   # +1 warmup (first put pays setup)
            src = bufs[t % 2]
            src[0, 0, 0, 0, 0] ^= 0xFF   # defeat content-level caching
            t0 = _time.time()
            x = meshlib.put_global(src, sharding)
            x.block_until_ready()
            # Value fetch of one element: the transfer is only done for
            # the host's purposes once a byte of it can be read back.
            np.asarray(x[0, 0, 0, 0, 0])
            dt = _time.time() - t0
            del x
            if t > 0:
                best = min(best, dt)
        images_per_s = WINDOW * global_batch / best
        return {
            "mib_per_s": round(buf_mib / best, 1),
            "ms_per_batch": round(best / WINDOW * 1e3, 2),
            "floor_images_per_sec_per_chip": round(images_per_s / ndev, 1),
        }

    log(f"[bench] link_floor: {WINDOW}x{global_batch} u8 window "
        f"({buf_mib:.1f} MiB), best of {trials}")
    synth = cifar10._synthetic_split(WINDOW * global_batch, seed=7)
    out = {
        # In-process CPU "transfers" are memcpys (or aliased no-ops) —
        # only a tpu backend's floor is a statement about the wire.
        "backend": jax.default_backend(),
        "window_batches": WINDOW,
        "buffer_mib": round(buf_mib, 2),
        "trials": trials,
        "synthetic": _measure(np.ascontiguousarray(
            synth.images.reshape(shape))),
    }
    fixture_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tests", "assets")
    if cifar10.has_real_data(fixture_dir):
        real, _, _ = cifar10.load(fixture_dir)[:3]
        entry = _measure(_fill_tiled(real.images))
        entry["unique_mib"] = round(
            real.images.size / 2**20, 2)
        out["real_entropy"] = entry
    else:   # fixture missing on this checkout: floor still has one leg
        log("[bench] link_floor: tests/assets CIFAR fixture missing; "
            "real-entropy leg omitted")
        out["real_entropy"] = None
    return out


def _collect_spectrum(log, model: str, global_batch: int,
                      strategies=STRATEGIES,
                      deep_rows=(("resnet34", "allreduce"),
                                 ("resnet34", "ddp"))):
    """Static per-strategy collective stats from the TPU v5e-8 AOT lowering
    (deviceless topology — compiles anywhere the TPU compiler is present).

    This is the strategy-cost spectrum as the COMPILER sees it: collective
    instruction counts and result-buffer bytes per tier, immune to host
    noise.  ``per_strategy`` covers the headline ``model`` across
    ``strategies``; ``deep_rows`` adds (model, strategy) rows for a deep
    model (many more parameter leaves -> the chained-collective tiers'
    latency shape scales with depth, where the bucketed ddp tier's does
    not — that contrast IS the row's information).  None (with a logged
    reason) where the TPU AOT client is unavailable."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cs744_ddp_tpu import models as model_zoo
    from cs744_ddp_tpu.ops import sgd as sgdlib
    from cs744_ddp_tpu.parallel import get_strategy
    from cs744_ddp_tpu.parallel.mesh import DATA_AXIS
    from cs744_ddp_tpu.train import step as steplib
    from cs744_ddp_tpu.analysis import (collective_chain_depth,
                                        collective_stats)

    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc("v5e:2x4", platform="tpu")
    except Exception as e:
        log(f"[bench] spectrum: TPU AOT topology unavailable ({e!r}); "
            "section omitted")
        return None
    # The lowering shards the batch 8 ways regardless of how many devices
    # the measurement host has; keep it divisible.
    global_batch = -(-global_batch // 8) * 8
    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P(DATA_AXIS))
    model_cache = {}

    def _model_args(name):
        """(apply_fn, step args, grad bytes) for one model, cached — the
        deep rows reuse the headline model's init where they share it."""
        if name not in model_cache:
            init_fn, apply_fn = model_zoo.get_model(name)
            state = steplib.init_train_state(init_fn, jax.random.PRNGKey(0))
            state_sds = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=rep), state)
            args = (state_sds,
                    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
                    jax.ShapeDtypeStruct((global_batch, 32, 32, 3),
                                         jnp.uint8, sharding=sh),
                    jax.ShapeDtypeStruct((global_batch,), jnp.int32,
                                         sharding=sh))
            grad_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                             for a in jax.tree.leaves(state.params))
            model_cache[name] = (apply_fn, args, grad_bytes)
        return model_cache[name]

    def _strategy_stats(mname, sname):
        """collective_stats + chain_depth for one (model, strategy), or
        None with the reason logged."""
        apply_fn, args, _ = _model_args(mname)
        log(f"[bench] spectrum: AOT-compiling {mname}/{sname} for v5e-8")
        try:
            step = steplib.make_train_step(
                apply_fn, get_strategy(sname), mesh, sgdlib.SGDConfig(),
                augment=True)
            low = step.lower(*args)
            # Latency shape: collectives forced sequential by data deps in
            # the pre-optimization HLO (barrier chains still visible there;
            # see hlo_stats.collective_chain_depth) — gather 2/leaf chained,
            # allreduce 1/leaf chained, ddp 1/bucket independent.
            chain_depth = collective_chain_depth(
                low.compiler_ir(dialect="hlo").as_hlo_text())
            txt = low.compile().as_text()
        except Exception as e:
            # Never let the static section kill a bench whose expensive
            # measurements already completed — omit it with the reason.
            log(f"[bench] spectrum: AOT compile failed for {mname}/{sname} "
                f"({e!r}); section omitted")
            return None
        stats = collective_stats(txt)
        if stats["total_count"] == 0:
            # Every tier here MUST lower to collectives on an 8-chip
            # mesh; zero means the HLO-text parser no longer matches this
            # XLA version's print format — omit the section rather than
            # record misleading zeros.
            log(f"[bench] spectrum: parsed 0 collectives for "
                f"{mname}/{sname} on the 8-chip lowering — HLO text "
                "format mismatch; section omitted")
            return None
        stats["chain_depth"] = chain_depth
        return stats

    _, _, grad_bytes = _model_args(model)
    out = {
        "topology": "v5e:2x4 (AOT, deviceless)",
        "model": model, "global_batch": global_batch,
        "grad_mib": round(grad_bytes / 2**20, 2),
        "note": "result_mib sums collective RESULT buffers: all-gather's "
                "is world x its input, so the gather tier's world-times "
                "traffic amplification (vs the reference's root-link "
                "gather, Part 2a/main.py:117-127) is explicit — see "
                "BASELINE.md 'Gather-tier traffic accounting'",
        "per_strategy": {},
    }
    for name in strategies:
        stats = _strategy_stats(model, name)
        if stats is None:
            return None
        out["per_strategy"][name] = stats
    if deep_rows:
        out["deep_rows"] = {}
        for mname, sname in deep_rows:
            stats = _strategy_stats(mname, sname)
            if stats is None:
                return None
            _, _, gb = _model_args(mname)
            stats["grad_mib"] = round(gb / 2**20, 2)
            out["deep_rows"][f"{mname}/{sname}"] = stats
    return out


def run_robustness(log, *, headline_model: str = "vgg11",
                   headline_strategy=None, ndev=None,
                   global_batch: int = 256, data_dir: str = "./data",
                   max_iters: int = 100) -> dict:
    """Fault-tolerance cost/benefit numbers for the ft/ layer, measured:

    * ``guard_overhead`` — steady-state throughput with the non-finite
      step guard compiled in (``nonfinite="skip"``) vs the unguarded
      program.  The guard adds an on-device finiteness check of loss +
      global grad sqnorm and a per-leaf select to every step; this is the
      price of never applying a poisoned update.
    * ``staging`` — the degraded synchronous staging fallback (what a
      doubly-failed producer leaves you with) vs the healthy chunked
      pipeline, on the ``--host-augment`` path.  The fallback ships the
      bit-identical batch stream (tests/test_ft.py pins it), so this ratio
      is the whole cost of losing the producer thread.
    * ``checkpoint`` — emergency mid-epoch save + restore wall clock (what
      a SIGTERM costs on the way down and the way back up), plus the
      steps-lost accounting: step-level checkpoints replay 0 steps,
      epoch-only checkpointing replays everything since the last epoch
      boundary (worst case one full epoch).
    * ``nonfinite_skip`` — end-to-end demo: a deterministically injected
      NaN gradient (chaos ``nonfinite_grad``) under the skip policy;
      records the skip count and that the run finishes finite.

    Standalone-callable (the committed artifact's robustness section can be
    refreshed without re-running the day-long throughput sections)."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from cs744_ddp_tpu.ft import ChaosPlan, FTConfig
    from cs744_ddp_tpu.utils.metrics import WINDOW

    log = log or (lambda s: print(s, file=sys.stderr))
    ndev = ndev or len(jax.devices())
    headline_strategy = headline_strategy or ("ddp" if ndev > 1 else "single")
    out = {
        "backend": jax.default_backend(),
        "model": f"{headline_model}/{headline_strategy}",
        "global_batch": global_batch,
    }

    # Guard overhead: same measurement design as the matrix (epoch-length
    # windows, best-of-2 on one staged trainer).  NOTE the guarded program
    # is a DIFFERENT compiled program (the check + select change XLA's
    # fusion), so the comparison is throughput-vs-throughput, not
    # bitwise-vs.
    # Bounded epoch: the guard ratio stabilizes within a couple of windows,
    # so the "epoch" each dispatch covers is capped at max_iters batches —
    # still one dispatch per pass (the dispatch-latency amortization the
    # epoch-window design exists for), without the full-epoch runtime.
    guard_lim = max(max_iters, 2 * WINDOW)

    def _ips(ft):
        tr = _make_trainer(headline_model, headline_strategy, ndev,
                           global_batch=global_batch, data_dir=data_dir,
                           log=lambda s: None,
                           limit_train_batches=guard_lim, ft=ft)
        return max(tr.steady_state_throughput(
                       max_iters=max_iters, window_iters="epoch")[1]
                   for _ in range(2))

    log("[bench] robustness: guard overhead (nonfinite=skip vs off)")
    base_ips = _ips(None)
    guard_ips = _ips(FTConfig(nonfinite="skip"))
    out["guard_overhead"] = {
        "unguarded_images_per_sec_per_chip": round(base_ips, 2),
        "guarded_images_per_sec_per_chip": round(guard_ips, 2),
        "guard_cost_pct": round((1.0 - guard_ips / base_ips) * 100.0, 2),
    }

    # Degraded vs healthy staging on the host-augment path.  Short cap:
    # the ratio stabilizes within a couple of windows and the degraded
    # path is serial by construction.
    lim = min(max_iters, 49)

    def _host_ips(ft):
        tr = _make_trainer(headline_model, headline_strategy, ndev,
                           global_batch=global_batch, data_dir=data_dir,
                           log=lambda s: None, host_augment=True,
                           limit_train_batches=lim, ft=ft)
        nfull, tail_per = tr._per_rank_batch_counts()
        images = (min(lim, nfull) * global_batch
                  + (tail_per * tr.world if lim > nfull and tail_per else 0))
        tr.train_model(0)   # compile + warm
        best = 0.0
        for _ in range(2):
            t0 = _time.time()
            tr.train_model(0)
            best = max(best, images / (_time.time() - t0))
        return best / ndev

    log("[bench] robustness: staging healthy vs degraded (host-augment)")
    healthy = _host_ips(None)
    degraded = _host_ips(FTConfig(degrade_staging=True))
    out["staging"] = {
        "limit_train_batches": lim,
        "healthy_images_per_sec_per_chip": round(healthy, 2),
        "degraded_images_per_sec_per_chip": round(degraded, 2),
        "degraded_fraction_of_healthy": round(degraded / healthy, 3),
    }

    # Emergency-checkpoint wall clock: what going down (save) and coming
    # back (restore) cost, on the real model state; plus the replay
    # accounting that motivates step-level checkpoints at all.
    log("[bench] robustness: emergency checkpoint save/restore wall clock")
    from cs744_ddp_tpu.train.checkpoint import CheckpointManager
    tr = _make_trainer(headline_model, headline_strategy, ndev,
                       global_batch=global_batch, data_dir=data_dir,
                       log=lambda s: None)
    nbatches, _ = tr._per_rank_batch_counts()
    with tempfile.TemporaryDirectory() as ckdir:
        mngr = CheckpointManager(ckdir)
        try:
            t0 = _time.time()
            mngr.save_mid_epoch(0, nbatches // 2, tr.state)
            save_s = _time.time() - t0
            t0 = _time.time()
            mngr.restore_mid_epoch(tr.state)
            restore_s = _time.time() - t0
        finally:
            mngr.close()
    out["checkpoint"] = {
        "emergency_save_s": round(save_s, 3),
        "mid_epoch_restore_s": round(restore_s, 3),
        "steps_lost_with_step_ckpt": 0,
        "steps_lost_epoch_only_worst_case": nbatches,
    }

    # End-to-end skip-policy demo: one window with a NaN gradient injected
    # at an exact step — the update is dropped, the run stays finite.
    log("[bench] robustness: non-finite skip demo (chaos nonfinite_grad:2)")
    trg = _make_trainer(headline_model, headline_strategy, ndev,
                        global_batch=global_batch, data_dir=data_dir,
                        log=lambda s: None, limit_train_batches=WINDOW,
                        ft=FTConfig(nonfinite="skip",
                                    chaos=ChaosPlan.parse(
                                        ["nonfinite_grad:2"])))
    timers = trg.train_model(0)
    out["nonfinite_skip"] = {
        "chaos": "nonfinite_grad:2",
        "updates_skipped": trg._epoch_nf_skipped,
        "final_loss_finite": bool(np.isfinite(timers.losses[-1])),
    }
    return out


def _startup_cold_warm(log, *, model: str, buckets, seed: int,
                       timeout_s: float = 900.0) -> dict:
    """COLD vs WARM engine startup, each measured in a FRESH subprocess
    (``python -m cs744_ddp_tpu.serve.demo --startup-probe``) sharing one
    executable-cache dir: run 1 populates it (cold), run 2 loads from it
    (warm).  Subprocesses because in-process \"restarts\" inherit jax's
    in-memory jit caches and would overstate the warm win.

    An accelerator belongs to ONE process: a parent that has already run
    on the chip holds it and a child that needs it fails or hangs.  So on
    a non-CPU backend no probe is started, and whenever a probe cannot
    run (the chip is held; the child interpreter has never heard of a
    test-registered model) the section says ``"measured": False`` with
    the reason — it never substitutes an in-process measurement under the
    same keys.  Note the repo-wide persistent XLA cache stays active in
    BOTH runs (exactly what a server restart on this host would see), so
    \"cold\" means \"no serialized executables\", not \"no compile
    cache\" — ``cold_includes_xla_cache`` records this."""
    import subprocess
    import tempfile

    import jax

    def _not_measured(reason: str) -> dict:
        log(f"[bench] serving: startup cold/warm not measured ({reason})")
        return {"method": "subprocess", "measured": False, "reason": reason}

    if jax.default_backend() != "cpu":
        return _not_measured(
            f"this process holds the {jax.default_backend()} device(s); "
            "a probe child could not acquire them")

    bucket_spec = ",".join(str(b) for b in buckets)
    repo = os.path.dirname(os.path.abspath(__file__))

    def _probe(cache_dir: str):
        cmd = [sys.executable, "-m", "cs744_ddp_tpu.serve.demo",
               "--startup-probe", "--model", model,
               "--buckets", bucket_spec, "--cache-dir", cache_dir,
               "--seed", str(seed)]
        proc = subprocess.run(cmd, cwd=repo, capture_output=True,
                              text=True, timeout=timeout_s)
        if proc.returncode != 0:
            return None, (proc.stderr.strip().splitlines() or ["?"])[-1]
        return json.loads(proc.stdout.strip().splitlines()[-1]), None

    with tempfile.TemporaryDirectory() as cache_dir:
        log(f"[bench] serving: cold startup probe ({model}, subprocess)")
        cold, err = _probe(cache_dir)
        if cold is None:
            return _not_measured(f"cold probe failed: {err}")
        log("[bench] serving: warm startup probe (same cache dir)")
        warm, err = _probe(cache_dir)
        if warm is None:
            return _not_measured(f"warm probe failed: {err}")
    out = {
        "method": "subprocess",
        "measured": True,
        "cold_s": cold["startup_s"],
        "warm_s": warm["startup_s"],
        "warm_was_all_cache": warm["warm"],
        "warm_lt_half_cold": warm["startup_s"] < 0.5 * cold["startup_s"],
        "cold_includes_xla_cache": True,
        "cold_per_bucket": cold["per_bucket"],
        "warm_per_bucket": warm["per_bucket"],
    }
    if not out["warm_lt_half_cold"]:
        log(f"[bench] serving: WARNING warm startup {out['warm_s']}s is "
            f"not < 0.5 x cold {out['cold_s']}s")
    return out


def run_serving(log, *, model: str = "vgg11", buckets=None,
                loads=(5.0, 20.0), n_requests: int = 100,
                max_wait_ms: float = 5.0, seed: int = 0,
                dispatch_reps: int = 20, dispatch_budget_s: float = 3.0,
                precision: str = "f32", startup_probe: bool = True) -> dict:
    """The serving fast path's numbers (``cs744_ddp_tpu/serve/``), measured:

    * ``throughput_vs_bucket`` — for every rung of the executable ladder:
      ``per_dispatch_ms`` (one FENCED ``infer_counts`` call: staging +
      dispatch + logits fetch — what a lone request experiences) and
      ``device_program_ms`` (back-to-back enqueues on the same staged
      buffer, blocked once at the end, divided by the rep count — the
      device program's amortized cost with dispatch overhead overlapped).
      The spread between the two IS the per-dispatch host cost;
      ``images_per_sec`` uses the amortized figure, the
      saturated-pipeline ceiling.
    * ``latency`` — client-side p50/p95/p99 under a seeded OPEN-LOOP
      arrival trace through the bounded-queue micro-batcher, one entry per
      offered load (requests/sec) — the knee where queueing delay takes
      over is the capacity statement.
    * ``startup`` — cold vs warm engine startup (``_startup_cold_warm``):
      the executable ladder compiled from scratch vs deserialized from the
      warm-start cache, fresh subprocess each.

    Standalone-callable, same contract as ``run_robustness``: the
    committed artifact's serving section can be refreshed without
    re-running the training-side sections."""
    import time as _time

    import jax
    import numpy as np

    from cs744_ddp_tpu.obs import Telemetry
    from cs744_ddp_tpu.serve import BUCKETS, InferenceEngine
    from cs744_ddp_tpu.serve.demo import request_pool, run_demo

    log = log or (lambda s: print(s, file=sys.stderr))
    buckets = tuple(buckets) if buckets else BUCKETS
    tel = Telemetry()   # in-memory; summary attached below
    log(f"[bench] serving: building {model} ladder over buckets "
        f"{buckets} ({precision})")
    engine = InferenceEngine(model, buckets=buckets, seed=seed,
                             precisions=(precision,), telemetry=tel)
    ladder = engine.startup()
    out = {
        "backend": jax.default_backend(),
        "model": model,
        "buckets": list(buckets),
        "precision": precision,
        "ladder_startup": ladder,
    }

    # Static audit of the executable ladder we are about to measure: each
    # bucket's program must be collective-free, precision-clean and
    # constant-lean (analysis/audit.py).  Tolerant — the audit must never
    # kill a serving bench whose measurements matter more than its paper
    # trail.
    try:
        from cs744_ddp_tpu.analysis import audit as _auditlib
        audit_res = _auditlib.AuditResult(
            reports=_auditlib.audit_serving(engine=engine,
                                            precision=precision))
        out["audit"] = audit_res.summary()
        log(f"[bench] serving: audit "
            f"{'CLEAN' if audit_res.clean else 'DIRTY'} over "
            f"{len(audit_res.reports)} bucket programs")
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] serving: ladder audit failed ({e!r}); omitted")

    # Throughput-vs-bucket curve.  The rep count adapts to the measured
    # per-dispatch time so a slow rung (vgg11/256 on a 1-core CPU host)
    # costs ~dispatch_budget_s, not dispatch_reps x seconds.
    pool = request_pool(max(buckets), seed=seed + 7)
    curve = {}
    for b in buckets:
        images = pool.images[:b]
        labels = pool.labels[:b]
        engine.infer_counts(images, labels, precision=precision)  # warm
        per_disp = float("inf")
        for _ in range(3):
            t0 = _time.time()
            engine.infer_counts(images, labels, precision=precision)
            per_disp = min(per_disp, _time.time() - t0)
        reps = max(3, min(dispatch_reps, int(dispatch_budget_s / per_disp)
                          if per_disp > 0 else dispatch_reps))
        ex = engine._executable(b, precision)
        staged = engine._pad_stage(images, b)
        padded_labels = np.asarray(labels, np.int32)
        res = ex(engine.params, engine.bn_state, staged, padded_labels)
        jax.block_until_ready(res)
        t0 = _time.time()
        for _ in range(reps):
            res = ex(engine.params, engine.bn_state, staged, padded_labels)
        jax.block_until_ready(res)
        prog = (_time.time() - t0) / reps
        curve[str(b)] = {
            "per_dispatch_ms": round(per_disp * 1e3, 3),
            "device_program_ms": round(prog * 1e3, 3),
            "images_per_sec": round(b / prog, 2),
            "reps": reps,
        }
        log(f"[bench] serving: bucket {b}: {curve[str(b)]['images_per_sec']}"
            f" img/s amortized, {curve[str(b)]['per_dispatch_ms']} ms/dispatch")
    out["throughput_vs_bucket"] = curve

    # Open-loop latency at the offered loads (seeded trace, shared pool).
    out["latency"] = {}
    for rps in loads:
        log(f"[bench] serving: open-loop trace at {rps:g} req/s "
            f"({n_requests} requests)")
        out["latency"][f"{rps:g}rps"] = run_demo(
            engine, n_requests=n_requests, offered_rps=rps, seed=seed,
            max_wait_ms=max_wait_ms, pool=pool, precision=precision)

    if startup_probe:
        out["startup"] = _startup_cold_warm(log, model=model,
                                            buckets=buckets, seed=seed)
    out["telemetry_summary"] = tel.finalize()
    return out


def _servenet_factory():
    """conv(3->8)+BN+relu+pool(4x)+fc — the serving-load workload model.

    The load rows offer thousands of requests/sec; the flagship vgg11
    ladder serves ~0.7 req/s on this host (run_serving), so the load
    sections would measure nothing but one giant queue.  Same layer kinds
    as the real models (and as the tests' tiny_cnn — redefined here
    because tests/ is not importable from the bench), registered under
    ``servenet`` via the models registry like any user model."""
    import jax
    import jax.numpy as jnp

    from cs744_ddp_tpu.models import layers

    def init_fn(key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        params = {"conv": layers.conv2d_init(k1, 3, 8, 3, dtype)}
        params["bn"], bn_state = layers.batchnorm_init(8, dtype)
        params["fc"] = layers.linear_init(k2, 8 * 8 * 8, 10, dtype)
        return params, {"bn": bn_state}

    def apply_fn(params, state, x, *, train):
        y = layers.conv2d_apply(params["conv"], x)
        y, new_bn = layers.batchnorm_apply(params["bn"], state["bn"], y,
                                           train=train)
        y = layers.relu(y)
        y = layers.maxpool2x2(layers.maxpool2x2(y))  # 32 -> 8
        y = y.reshape(y.shape[0], -1)
        return layers.linear_apply(params["fc"], y), {"bn": new_bn}

    return init_fn, apply_fn


def run_serving_load(log, *, model: str = "servenet", buckets=None,
                     replica_counts=(1, 2, 4, 8),
                     burst_requests: int = 500, burst_rps: float = 8000.0,
                     burst_slo_ms: float = 2000.0,
                     burst_sizes=(4, 8, 8, 16), queue_images: int = 256,
                     curve_loads=(250.0, 1000.0, 2000.0, 4000.0),
                     curve_requests: int = 400, curve_slo_ms: float = 500.0,
                     overload_tiers=((0, 2, 1000.0), (1, 5, 500.0),
                                     (2, 3, 800.0)),
                     overload_requests: int = 2400,
                     overload_queue_images: int = 4096,
                     matched_rps: float = 400.0,
                     seed: int = 0, precision: str = "f32") -> dict:
    """The serving tier under load (``serve/`` round 9): replicated
    device-pinned engines behind the least-loaded router, driven by
    seeded open-loop traces through the in-process client.

    * ``replica_scaling`` — goodput at a FIXED SLO as replicas grow
      1->2->4->8.  PROVENANCE: this host time-shares every replica over
      one physical core, so device throughput CANNOT scale with replica
      count — what scales is bounded-queue admission capacity (each
      replica brings its own ``max_queue_images`` admission queue).  The
      row therefore offers a burst that over-runs a single replica's
      queue, and goodput is SLO-met completions per second of the fixed
      ``span + SLO`` observation window (same denominator every row) —
      the component of replica scale-out that survives the 1-core
      constraint.  On a real mesh the same row also scales service.
    * ``goodput_vs_offered`` — the saturation curve at the full replica
      set: goodput tracks offered load until the shared core saturates,
      then attainment falls and shedding/overload absorb the excess.
    * ``overload_2x`` — 2x the measured capacity, tiered traffic:
      priority-tier admission must hold top-tier attainment while
      deterministic shedding is confined to the lower tiers; the
      no-silent-drop accounting (one terminal reply per request) rides
      in the row.
    * ``continuous_vs_drain`` — virtual-time replay of a matched trace
      through ``plan_continuous`` vs ``plan_drain`` (the round-7
      MicroBatcher's coalesce-and-drain semantics) using the MEASURED
      per-bucket service model from the live rows: continuous batching
      must hold strictly lower p99 queue-wait at matched load.

    Standalone-callable, same contract as ``run_serving``."""
    import time as _time

    import jax

    from cs744_ddp_tpu import models
    from cs744_ddp_tpu.obs import NULL, Telemetry
    from cs744_ddp_tpu.serve import (BUCKETS, EngineReplica, LoopbackClient,
                                     ReplicaRouter, demo, plan_continuous,
                                     plan_drain, virtual_requests)

    log = log or (lambda s: print(s, file=sys.stderr))
    buckets = tuple(buckets) if buckets else BUCKETS
    if model == "servenet":
        models.register_model("servenet", _servenet_factory)
    devices = jax.devices()
    nmax = max(replica_counts)
    log(f"[bench] serving_load: building {nmax} {model} replicas over "
        f"{len(devices)} device(s)")
    t0 = _time.time()
    replicas = [EngineReplica(i, model=model,
                              device=devices[i % len(devices)],
                              buckets=buckets, precision=precision,
                              seed=seed, cost_prior=True,
                              max_queue_images=queue_images)
                for i in range(nmax)]
    for r in replicas:
        r.startup()
    build_s = _time.time() - t0
    pool = demo.request_pool(seed=seed + 123)
    out = {
        "backend": jax.default_backend(),
        "model": model,
        "buckets": list(buckets),
        "num_devices": len(devices),
        "replicas_built": nmax,
        "build_s": round(build_s, 3),
        "provenance": (
            "single-physical-core host (time-shared CPU mesh): aggregate "
            "device throughput is conserved across replica counts, so the "
            "replica_scaling row measures what replicas add on this host — "
            "bounded-queue admission capacity at a fixed SLO under a burst "
            "that over-runs one replica's queue; goodput is SLO-met "
            "completions per second of the fixed span+SLO window.  The "
            f"workload model is the small registered '{model}' CNN: the "
            "flagship vgg11 ladder serves <1 req/s here (see the serving "
            "section) and cannot exercise thousands-of-req/s traces."),
    }

    def _replay(n_replicas, trace, telemetry=None, timeout_s=60.0):
        router = ReplicaRouter(replicas[:n_replicas], telemetry=telemetry)
        with router:
            client = LoopbackClient(router)
            stats = demo.replay_load(client, trace, pool=pool, seed=seed,
                                     drain_timeout_s=timeout_s)
        return stats, router.stats()

    def _row(stats, window_s=None):
        ok = sum(c["ok"] for c in stats["by_tier"].values())
        row = {
            "offered_rps": stats["offered_rps"],
            "goodput_rps": stats["goodput_rps"],
            "goodput_ips": stats["goodput_ips"],
            "attainment": stats["attainment"],
            "shed": stats["shed"],
            "overload": stats["overload"],
            "replies": stats["replies"],
            "unresolved": stats["unresolved"],
        }
        if window_s is not None:
            row["goodput_rps_window"] = round(ok / window_s, 2)
        if "queue_wait_ms" in stats:
            row["queue_wait_ms"] = stats["queue_wait_ms"]
        return row

    # Replica scaling at a fixed SLO (burst trace; see docstring).
    burst = demo.synthetic_load_trace(
        burst_requests, offered_rps=burst_rps, seed=seed,
        size_choices=burst_sizes, tiers=((0, 1, burst_slo_ms),))
    span_s = burst[-1][0]
    window_s = span_s + burst_slo_ms / 1e3
    scaling = {"offered_rps": round(burst_requests / max(span_s, 1e-9), 1),
               "slo_ms": burst_slo_ms, "window_s": round(window_s, 3),
               "per_replica_queue_images": queue_images, "rows": {}}
    for n in replica_counts:
        log(f"[bench] serving_load: scaling row, {n} replica(s), "
            f"{burst_requests} reqs @ {scaling['offered_rps']} rps, "
            f"SLO {burst_slo_ms:g} ms")
        stats, _rs = _replay(n, burst,
                             timeout_s=2.0 + 3.0 * burst_slo_ms / 1e3)
        scaling["rows"][str(n)] = _row(stats, window_s=window_s)
    g1 = scaling["rows"][str(replica_counts[0])]["goodput_rps_window"]
    g8 = scaling["rows"][str(nmax)]["goodput_rps_window"]
    scaling["goodput_scale_1_to_max"] = round(g8 / max(g1, 1e-9), 2)
    out["replica_scaling"] = scaling
    log(f"[bench] serving_load: goodput@SLO x"
        f"{scaling['goodput_scale_1_to_max']} from 1->{nmax} replicas")

    # Goodput-vs-offered-load saturation curve at the full replica set.
    curve = {"replicas": nmax, "slo_ms": curve_slo_ms, "points": {}}
    for rps in curve_loads:
        nreq = max(curve_requests, min(int(rps), 2 * curve_requests))
        trace = demo.synthetic_load_trace(
            nreq, offered_rps=rps, seed=seed + 1,
            tiers=((0, 1, curve_slo_ms),))
        log(f"[bench] serving_load: curve point {rps:g} rps ({nreq} reqs)")
        stats, _rs = _replay(nmax, trace)
        curve["points"][f"{rps:g}"] = _row(stats)
    out["goodput_vs_offered"] = curve
    cap_rps = max(p["goodput_rps"] for p in curve["points"].values())

    # 2x overload, tiered: top-tier attainment holds, shedding confined
    # to the lower tiers, every request gets a terminal reply.  The
    # tier-0 SLO sits above the p95 of one CONTENDED dispatch (8
    # replica threads share this host's core, so a ~60ms solo dispatch
    # runs ~300ms under contention) — below that floor no admission
    # policy can meet the deadline and the row measures the host, not
    # the scheduler.  The lower-tier SLOs sit BELOW the 2x backlog's
    # measured queue-wait tail, forcing real shed decisions; tier-0
    # jumps the queue at every admission, so its deadline holds while
    # the tiers beneath it absorb the overload.
    for r in replicas:
        r.scheduler.max_queue_images = overload_queue_images
    over_rps = 2.0 * cap_rps
    tel = Telemetry()   # in-memory; the slo summary rides in the row
    for r in replicas:
        r.scheduler.telemetry = tel
    log(f"[bench] serving_load: overload row at {over_rps:.0f} rps "
        f"(2x measured capacity {cap_rps:.0f} rps)")
    trace = demo.synthetic_load_trace(overload_requests,
                                      offered_rps=over_rps, seed=seed + 2,
                                      tiers=overload_tiers)
    stats, _rs = _replay(nmax, trace, telemetry=tel)
    for r in replicas:
        r.scheduler.telemetry = NULL
        r.scheduler.max_queue_images = queue_images
    shed_by_tier = {str(t): c["shed"] for t, c in stats["by_tier"].items()}
    top = min(stats["by_tier"])
    out["overload_2x"] = {
        "offered_rps": stats["offered_rps"],
        "capacity_rps": round(cap_rps, 2),
        "tiers": [list(t) for t in overload_tiers],
        "by_tier": {str(t): c for t, c in stats["by_tier"].items()},
        "top_tier_attainment": stats["by_tier"][top]["attainment"],
        "shed_by_tier": shed_by_tier,
        "total_shed": sum(shed_by_tier.values()),
        "sheds_confined_to_lower_tiers": (
            shed_by_tier.get(str(top), 0) == 0
            and sum(shed_by_tier.values()) > 0),
        "accounting": {k: stats[k] for k in
                       ("replies", "unresolved", "unique_traces", "traced")},
        "queue_wait_ms": stats.get("queue_wait_ms"),
        "telemetry_summary": tel.finalize(),
    }
    if out["overload_2x"]["top_tier_attainment"] < 0.95:
        log(f"[bench] serving_load: WARNING top-tier attainment "
            f"{out['overload_2x']['top_tier_attainment']} < 0.95 under "
            "2x overload")
    if out["overload_2x"]["total_shed"] == 0:
        log("[bench] serving_load: WARNING overload row shed nothing — "
            "the shed-confinement claim is vacuous at these SLOs")

    # Continuous batching vs the drain baseline: virtual-time replay of a
    # matched trace with the MEASURED service model (deterministic given
    # the measured per-bucket times; no thread scheduling noise).
    svc = replicas[0].scheduler.svc
    vtrace = demo.synthetic_load_trace(400, offered_rps=matched_rps,
                                       seed=seed + 3,
                                       tiers=((0, 1, curve_slo_ms),))
    cont = plan_continuous(virtual_requests(vtrace), buckets=buckets,
                           predict_s=svc.predict, shed=False)
    drain = plan_drain(virtual_requests(vtrace), buckets=buckets,
                       predict_s=svc.predict)
    keep = ("dispatches", "served", "p50_wait_ms", "p99_wait_ms")
    out["continuous_vs_drain"] = {
        "matched_rps": matched_rps,
        "service_model_ms": {str(b): round(svc.predict(b) * 1e3, 4)
                             for b in buckets},
        "continuous": {k: cont[k] for k in keep},
        "drain": {k: drain[k] for k in keep},
        "continuous_p99_lower":
            cont["p99_wait_ms"] < drain["p99_wait_ms"],
    }
    log(f"[bench] serving_load: p99 queue-wait continuous "
        f"{cont['p99_wait_ms']} ms vs drain {drain['p99_wait_ms']} ms "
        f"at {matched_rps:g} rps")
    return out


def run_pipeline(log, *, model: str = "servenet", buckets=(8, 32),
                 steady_reps: int = 40, n_replicas: int = 2,
                 capacity_loads=(600.0, 1200.0, 2000.0),
                 capacity_requests: int = 400,
                 capacity_slo_ms: float = 500.0,
                 seed: int = 0, precision: str = "f32") -> dict:
    """The dispatch pipeline's cost sheet (``serve/`` round 14): what
    double-buffered two-slot dispatch buys over the serial
    dispatch-fence-reply loop, measured three ways.

    * ``per_dispatch`` — per ladder rung: one FENCED serial dispatch
      (stage + dispatch + logits fetch, what round 13 charged every
      batch) vs the PIPELINED steady-state per-dispatch time (two
      ``infer_counts_async`` handles in flight, completions resolved in
      issue order) vs the back-to-back ``device_program_ms`` floor.
      ``gap_closed`` is the fraction of the serial-over-floor gap the
      overlap recovers.
    * ``capacity`` — goodput under seeded open-loop traces with the
      scheduler pipeline ON vs OFF (same replica layout, same traces;
      OFF is exactly the round-13 serial worker).  The acceptance row:
      pipelined capacity vs the round-9 ~440 req/s figure.
    * ``waterfall`` — the pipelined capacity point re-run under a
      recording telemetry: staging / device-compute / fetch stage
      split, the occupancy distribution from the ``serve_inflight``
      gauges (bounded by ``PIPELINE_SLOTS``), and the per-bucket
      measured-over-cost-prior ratio (round 12 measured 3.25x on
      bucket 8 — the per-dispatch tax the overlap is built to hide;
      with occupancy-honest ``serve_dispatch`` spans the ratio
      converges toward the device-program floor).

    Standalone-callable, same contract as ``run_serving_load``."""
    import time as _time

    import jax
    import numpy as np

    from cs744_ddp_tpu import models
    from cs744_ddp_tpu.obs import Telemetry, aggregate as _agg
    from cs744_ddp_tpu.obs.telemetry import percentile as _pctl
    from cs744_ddp_tpu.serve import (PIPELINE_SLOTS, EngineReplica,
                                     InferenceEngine, LoopbackClient,
                                     ReplicaRouter, demo)
    from cs744_ddp_tpu.serve.scheduler import cost_model_weights

    log = log or (lambda s: print(s, file=sys.stderr))
    buckets = tuple(buckets)
    if model == "servenet":
        models.register_model("servenet", _servenet_factory)
    out = {"backend": jax.default_backend(), "model": model,
           "buckets": list(buckets), "pipeline_slots": PIPELINE_SLOTS}

    # -- per-dispatch: serial vs pipelined vs device-program floor -------
    log(f"[bench] pipeline: building {model} ladder over {buckets} "
        f"({precision})")
    engine = InferenceEngine(model, buckets=buckets, seed=seed,
                             precisions=(precision,))
    engine.startup()
    pool = demo.request_pool(max(buckets), seed=seed + 7)
    per = {}
    for b in buckets:
        images = pool.images[:b]
        labels = pool.labels[:b]
        engine.infer_counts(images, labels, precision=precision)  # warm
        serial = float("inf")
        for _ in range(3):
            t0 = _time.time()
            engine.infer_counts(images, labels, precision=precision)
            serial = min(serial, _time.time() - t0)
        # Device-program floor: back-to-back enqueues on one staged
        # buffer, blocked once at the end (same protocol as run_serving).
        ex = engine._executable(b, precision)
        staged = engine._pad_stage(images, b)
        padded_labels = np.asarray(labels, np.int32)
        res = ex(engine.params, engine.bn_state, staged, padded_labels)
        jax.block_until_ready(res)
        t0 = _time.time()
        for _ in range(steady_reps):
            res = ex(engine.params, engine.bn_state, staged, padded_labels)
        jax.block_until_ready(res)
        floor = (_time.time() - t0) / steady_reps
        # Pipelined steady state: keep PIPELINE_SLOTS handles in flight,
        # complete in issue order — the scheduler's exact dispatch shape.
        handles = [engine.infer_counts_async(images, labels,
                                             precision=precision)]
        engine.complete(handles.pop(0))   # warm the async path
        t0 = _time.time()
        for _ in range(steady_reps):
            handles.append(engine.infer_counts_async(
                images, labels, precision=precision))
            if len(handles) == PIPELINE_SLOTS:
                engine.complete(handles.pop(0))
        while handles:
            engine.complete(handles.pop(0))
        pipe = (_time.time() - t0) / steady_reps
        gap = serial - floor
        per[str(b)] = {
            "serial_per_dispatch_ms": round(serial * 1e3, 3),
            "pipelined_per_dispatch_ms": round(pipe * 1e3, 3),
            "device_program_ms": round(floor * 1e3, 3),
            "reps": steady_reps,
            "gap_closed": round((serial - pipe) / gap, 4) if gap > 0
            else None,
        }
        log(f"[bench] pipeline: bucket {b}: serial "
            f"{per[str(b)]['serial_per_dispatch_ms']} ms -> pipelined "
            f"{per[str(b)]['pipelined_per_dispatch_ms']} ms (floor "
            f"{per[str(b)]['device_program_ms']} ms)")
    out["per_dispatch"] = per

    # -- capacity: pipeline ON vs OFF over the same seeded traces --------
    devices = jax.devices()
    pool_cap = demo.request_pool(seed=seed + 123)
    sizes = tuple(s for s in demo.SIZE_CHOICES if s <= buckets[-1])
    traces = {f"{rps:g}": demo.synthetic_load_trace(
        max(capacity_requests, min(int(rps), 2 * capacity_requests)),
        offered_rps=rps, seed=seed + 1, size_choices=sizes,
        tiers=((0, 1, capacity_slo_ms),)) for rps in capacity_loads}

    def _capacity_rows(pipeline, telemetry=None):
        reps = [EngineReplica(i, model=model,
                              device=devices[i % len(devices)],
                              buckets=buckets, precision=precision,
                              seed=seed, cost_prior=True,
                              telemetry=telemetry, pipeline=pipeline)
                for i in range(n_replicas)]
        for r in reps:
            r.startup()
        points = {}
        for key, trace in traces.items():
            router = ReplicaRouter(reps, telemetry=telemetry)
            with router:
                client = LoopbackClient(router)
                stats = demo.replay_load(client, trace, pool=pool_cap,
                                         seed=seed, drain_timeout_s=60.0)
            points[key] = {
                "offered_rps": stats["offered_rps"],
                "goodput_rps": stats["goodput_rps"],
                "attainment": stats["attainment"],
                "shed": stats["shed"],
                "queue_wait_ms": stats.get("queue_wait_ms"),
            }
            log(f"[bench] pipeline: capacity {key} rps pipeline="
                f"{'on' if pipeline else 'off'}: goodput "
                f"{stats['goodput_rps']} rps, attainment "
                f"{stats['attainment']}")
        return points

    log(f"[bench] pipeline: capacity A/B, {n_replicas} replica(s), "
        f"SLO {capacity_slo_ms:g} ms")
    rows_off = _capacity_rows(False)
    rows_on = _capacity_rows(True)
    cap_off = max(p["goodput_rps"] for p in rows_off.values())
    cap_on = max(p["goodput_rps"] for p in rows_on.values())
    out["capacity"] = {
        "replicas": n_replicas,
        "slo_ms": capacity_slo_ms,
        "pipeline_off": rows_off,
        "pipeline_on": rows_on,
        "capacity_rps_off": cap_off,
        "capacity_rps_on": cap_on,
        "round9_capacity_rps": 441.6,
        "beats_round9": cap_on > 441.6,
    }
    log(f"[bench] pipeline: capacity off {cap_off} vs on {cap_on} rps "
        f"(round-9 figure 441.6)")

    # -- waterfall at the pipelined capacity point -----------------------
    best_key = max(rows_on, key=lambda k: rows_on[k]["goodput_rps"])
    log(f"[bench] pipeline: waterfall re-run at {best_key} rps "
        f"(recording telemetry)")
    tel = Telemetry()   # in-memory; events mirrored in tel.records
    reps = [EngineReplica(i, model=model,
                          device=devices[i % len(devices)],
                          buckets=buckets, precision=precision,
                          seed=seed, cost_prior=True,
                          telemetry=tel, pipeline=True)
            for i in range(n_replicas)]
    for r in reps:
        r.startup()
    prior_flops = cost_model_weights(reps[0].engine, precision)
    router = ReplicaRouter(reps, telemetry=tel)
    with router:
        client = LoopbackClient(router)
        demo.replay_load(client, traces[best_key], pool=pool_cap,
                         seed=seed, drain_timeout_s=60.0)
    events = list(tel.records)
    stage_ms = {}
    for e in events:
        if e.get("kind") == "span" and e.get("name") in (
                "serve_stage", "serve_dispatch", "serve_fetch"):
            stage_ms.setdefault(e["name"], []).append(e["dur_s"] * 1e3)
    occ = {}
    for e in events:
        if e.get("kind") == "gauge" and e.get("name") == "serve_inflight":
            v = int(e["value"])
            occ[v] = occ.get(v, 0) + 1
    nocc = sum(occ.values())
    by_bucket = {}
    for e in events:
        if e.get("kind") == "span" and e.get("name") == "serve_dispatch" \
                and "bucket" in e:
            by_bucket.setdefault(int(e["bucket"]), []).append(
                e["dur_s"] * 1e3)
    prior = _agg.fit_cost_prior(
        [{"bucket": b, "stages": {"device_compute": ms}}
         for b, v in by_bucket.items() for ms in v], prior_flops)
    out["waterfall"] = {
        "offered_rps_point": best_key,
        "stage_ms": {n: {"p50": round(_pctl(v, 50), 3),
                         "p99": round(_pctl(v, 99), 3),
                         "count": len(v)}
                     for n, v in sorted(stage_ms.items())},
        "occupancy": {str(k): round(occ[k] / nocc, 4)
                      for k in sorted(occ)} if nocc else {},
        "max_inflight": max(occ) if occ else 0,
        "inflight_bound_ok": (max(occ) if occ else 0) <= PIPELINE_SLOTS,
        "cost_prior": prior,
    }
    if prior:
        for b, rec in prior["by_bucket"].items():
            log(f"[bench] pipeline: bucket {b} measured/prior "
                f"{rec['measured_over_prior']} (round-12 bucket-8 "
                f"figure: 3.254)")
    out["note"] = (
        "single-host CPU backend: device compute and host staging share "
        "the same cores, so the overlap the pipeline exists for cannot "
        "be banked here (per_dispatch.gap_closed can go negative); the "
        "accounting contracts — occupancy bound, issue-order spans, "
        "bitwise parity with the serial path — are what this section "
        "pins, and capacity/cost-prior are tracked vs the committed "
        "round-9/12 figures")
    return out


def run_tracing(log, *, model: str = "servenet", buckets=(8, 32),
                capacity_requests: int = 400, capacity_rps: float = 440.0,
                capacity_slo_ms: float = 500.0, capacity_repeats: int = 3,
                subprocess_requests: int = 150,
                subprocess_rps: float = 120.0,
                seed: int = 0, precision: str = "f32") -> dict:
    """Distributed tracing under load (``obs/`` round 12): what the
    tentpole costs and what it reconstructs.

    * ``capacity`` — the round-9 capacity row (~440 req/s loopback
      replay) with tracing OFF vs ON (server spans + client root
      contexts + events.jsonl writes).  The pin: tracing costs <= 5%
      goodput.  Median of ``capacity_repeats`` runs each way, same
      seeded trace.
    * ``two_process`` — the acceptance scenario: a REAL second OS
      process (tools/serve_load.py replay ``--telemetry-out``) drives
      the socket front-end; both processes' event streams are merged by
      ``obs/aggregate.py`` into skew-corrected waterfalls.  Reported:
      clock-skew estimate (bounded by RTT), complete/orphaned trace
      counts, the waterfall-sum-vs-client-measured residual, the
      device-compute join against the HLO cost-model prior, and the
      aggregation wall clock.

    Standalone-callable, same contract as ``run_serving_load``."""
    import json as _json
    import subprocess
    import tempfile
    import time as _time

    import jax

    from cs744_ddp_tpu import models
    from cs744_ddp_tpu.obs import Telemetry, aggregate as _agg
    from cs744_ddp_tpu.serve import (EngineReplica, FrontendClient,
                                     LoopbackClient, ReplicaRouter,
                                     ServingFrontend, demo)
    from cs744_ddp_tpu.serve.scheduler import cost_model_weights

    log = log or (lambda s: print(s, file=sys.stderr))
    buckets = tuple(buckets)
    if model == "servenet":
        models.register_model("servenet", _servenet_factory)
    pool = demo.request_pool(seed=seed + 123)
    sizes = tuple(s for s in demo.SIZE_CHOICES if s <= buckets[-1])
    trace = demo.synthetic_load_trace(
        capacity_requests, offered_rps=capacity_rps, seed=seed,
        size_choices=sizes, tiers=((0, 1, capacity_slo_ms),))

    def _build(telemetry=None):
        rep = EngineReplica(0, model=model, buckets=buckets,
                            precision=precision, seed=seed,
                            telemetry=telemetry, cost_prior=True)
        rep.startup()
        return rep

    def _goodput(rep, telemetry_client=None):
        router = ReplicaRouter([rep], telemetry=rep.telemetry)
        with router:
            client = LoopbackClient(router, telemetry=telemetry_client)
            # Warm every bucket outside the measured window.
            import numpy as _np
            for b in buckets:
                LoopbackClient(router).submit(
                    _np.zeros((b, 32, 32, 3), _np.uint8), tier=0,
                    slo_ms=60_000.0).result(timeout=120)
            stats = demo.replay_load(client, trace, pool=pool, seed=seed,
                                     drain_timeout_s=60.0)
        return stats

    out = {"backend": jax.default_backend(), "model": model,
           "buckets": list(buckets)}

    # -- capacity: tracing off vs on -------------------------------------
    log(f"[bench] tracing: capacity {capacity_requests} reqs @ "
        f"{capacity_rps:g} rps, {capacity_repeats}x off vs on")
    rep_off = _build(telemetry=None)
    off_runs = [_goodput(rep_off) for _ in range(capacity_repeats)]
    off = sorted(off_runs, key=lambda s: s["goodput_rps"])[len(off_runs) // 2]
    on_runs = []
    with tempfile.TemporaryDirectory() as td:
        for i in range(capacity_repeats):
            stel = Telemetry(os.path.join(td, f"srv{i}"))
            ctel = Telemetry(os.path.join(td, f"cli{i}"))
            rep_on = _build(telemetry=stel)
            on_runs.append(_goodput(rep_on, telemetry_client=ctel))
            stel.finalize()
            ctel.finalize()
    on = sorted(on_runs, key=lambda s: s["goodput_rps"])[len(on_runs) // 2]
    overhead = 1.0 - on["goodput_rps"] / max(off["goodput_rps"], 1e-9)
    out["capacity"] = {
        "offered_rps": off["offered_rps"],
        "slo_ms": capacity_slo_ms,
        "tracing_off": {"goodput_rps": off["goodput_rps"],
                        "attainment": off["attainment"],
                        "runs": [s["goodput_rps"] for s in off_runs]},
        "tracing_on": {"goodput_rps": on["goodput_rps"],
                       "attainment": on["attainment"],
                       "runs": [s["goodput_rps"] for s in on_runs]},
        "overhead_frac": round(overhead, 4),
        "overhead_budget": 0.05,
        "within_budget": overhead <= 0.05,
    }
    log(f"[bench] tracing: goodput off {off['goodput_rps']} vs on "
        f"{on['goodput_rps']} rps -> overhead {overhead:.1%}")
    if overhead > 0.05:
        log(f"[bench] tracing: WARNING overhead {overhead:.1%} exceeds "
            "the 5% budget")

    # -- two OS processes -> one skew-corrected waterfall ----------------
    log(f"[bench] tracing: two-process run, serve_load.py subprocess "
        f"{subprocess_requests} reqs @ {subprocess_rps:g} rps")
    with tempfile.TemporaryDirectory() as td:
        srv_dir = os.path.join(td, "server")
        cli_dir = os.path.join(td, "client")
        stel = Telemetry(srv_dir)
        rep = _build(telemetry=stel)
        prior_flops = cost_model_weights(rep.engine, precision)
        router = ReplicaRouter([rep], telemetry=stel)
        replay = None
        with router:
            with ServingFrontend(router, telemetry=stel) as fe:
                import numpy as _np
                with FrontendClient(fe.address) as warm:
                    for b in buckets:
                        warm.submit(_np.zeros((b, 32, 32, 3), _np.uint8),
                                    tier=0, slo_ms=60_000.0).result(120)
                # Safe beside a parent that holds the chip: the replay
                # client never initialises a jax backend (pinned in
                # tests/test_tracing.py).
                proc = subprocess.run(
                    [sys.executable,
                     os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "tools", "serve_load.py"),
                     "replay", "--port", str(fe.address[1]),
                     "--rps", f"{subprocess_rps:g}",
                     "--requests", str(subprocess_requests),
                     "--max-size", str(buckets[-1]),
                     "--seed", str(seed + 7),
                     "--telemetry-out", cli_dir, "--timeout", "120"],
                    capture_output=True, text=True, timeout=300)
                if proc.returncode == 0:
                    replay = _json.loads(proc.stdout.strip().splitlines()[-1])
                else:
                    log("[bench] tracing: WARNING replay subprocess failed: "
                        + proc.stderr[-500:])
        stel.finalize()
        t0 = _time.time()
        report = _agg.aggregate_run_dirs([srv_dir, cli_dir],
                                         prior_flops=prior_flops,
                                         max_waterfalls=2)
        agg_wall_s = _time.time() - t0
    two = {
        "replay": ({k: replay[k] for k in ("n_requests", "goodput_rps",
                                           "attainment")}
                   if replay else None),
        "aggregate_wall_s": round(agg_wall_s, 4),
        "traces": report["traces"],
        "complete": report["complete"],
        "orphaned": report["orphaned"],
        "skew": {n: p for n, p in report["processes"].items()
                 if p["skew_estimated"] and p["skew_pairs"]},
        "stage_ms": report["stage_ms"],
        "residual_ms": report.get("client_minus_stages_ms"),
        "cost_prior": report.get("cost_prior"),
        "waterfall_example": (report["waterfalls"][0]
                              if report["waterfalls"] else None),
    }
    out["two_process"] = two
    if two["residual_ms"]:
        log(f"[bench] tracing: {two['complete']} complete waterfalls, "
            f"client-minus-stages residual p50 "
            f"{two['residual_ms']['p50']} ms, aggregation "
            f"{agg_wall_s * 1e3:.0f} ms")
    return out


def run_hotswap(log, *, model: str = "servenet", buckets=None,
                n_replicas: int = 2, n_requests: int = 400,
                offered_rps: float = 600.0, slo_ms: float = 2000.0,
                queue_images: int = 4096, publishes_per_row: int = 3,
                seed: int = 0, precision: str = "f32") -> dict:
    """Train-to-serve weight hot-swap under load (``publish/`` round 10).

    One steady row (no publishes) and two swap rows (rolling vs
    all-at-once) replay the SAME seeded open-loop trace through the
    replicated router while a background thread publishes fresh weight
    bundles at fixed fractions of the trace span and a ``WeightWatcher``
    installs each one at the schedulers' dispatch boundaries:

    * ``swap_ms`` p50/p99/max — per-replica publish-pointer-seen ->
      flip-landed latency from the watcher's own samples;
    * ``in_flight_at_publish`` — queued images + predicted outstanding
      seconds sampled across all replicas at each publish instant (the
      work the swap must not tear);
    * ``goodput_dip_pct`` — each swap row's goodput vs the steady row at
      matched offered load (what a swap costs the SLO);
    * ``recompiles`` — growth of every engine's executable cache across
      the row; the AOT ladder treats weights as arguments, so this is
      pinned at 0 (zero_recompiles rides in the section).

    Standalone-callable, same contract as ``run_serving_load``."""
    import tempfile
    import threading
    import time as _time

    import jax
    import numpy as np

    from cs744_ddp_tpu import models
    from cs744_ddp_tpu.models import get_model
    from cs744_ddp_tpu.publish import WeightPublisher, WeightWatcher
    from cs744_ddp_tpu.serve import (BUCKETS, EngineReplica, LoopbackClient,
                                     ReplicaRouter, demo)
    from cs744_ddp_tpu.train.step import init_train_state

    log = log or (lambda s: print(s, file=sys.stderr))
    buckets = tuple(buckets) if buckets else BUCKETS
    if model == "servenet":
        models.register_model("servenet", _servenet_factory)
    devices = jax.devices()
    log(f"[bench] hotswap: building {n_replicas} {model} replicas over "
        f"{len(devices)} device(s)")
    replicas = [EngineReplica(i, model=model,
                              device=devices[i % len(devices)],
                              buckets=buckets, precision=precision,
                              seed=seed, cost_prior=True,
                              max_queue_images=queue_images)
                for i in range(n_replicas)]
    for r in replicas:
        r.startup()
    pool = demo.request_pool(seed=seed + 123)
    init_fn, _ = get_model(model)
    # Fresh (independently initialised) weights per publish: a swap that
    # installed identical bytes would be unobservable.
    states = [init_train_state(init_fn, jax.random.PRNGKey(seed + 1 + k))
              for k in range(2 * publishes_per_row)]
    trace = demo.synthetic_load_trace(n_requests, offered_rps=offered_rps,
                                      seed=seed + 7,
                                      tiers=((0, 1, slo_ms),))
    span_s = trace[-1][0]
    drain_s = 2.0 + 3.0 * slo_ms / 1e3

    def _replay():
        router = ReplicaRouter(replicas)
        with router:
            client = LoopbackClient(router)
            stats = demo.replay_load(client, trace, pool=pool, seed=seed,
                                     drain_timeout_s=drain_s)
        return stats, router.stats()

    def _swap_row(rolling, row_states):
        exec_counts = [len(r.engine._exec) for r in replicas]
        samples = []
        scheds = [r.scheduler for r in replicas]

        with tempfile.TemporaryDirectory() as pub_dir:
            pub = WeightPublisher(pub_dir, fingerprint={"model": model})
            watcher = WeightWatcher(pub_dir, replicas, rolling=rolling,
                                    poll_interval_s=0.02)

            def _publish_mid():
                t_start = _time.time()
                for k, state in enumerate(row_states):
                    target = span_s * (k + 1) / (len(row_states) + 1.0)
                    dt = t_start + target - _time.time()
                    if dt > 0:
                        _time.sleep(dt)
                    samples.append({
                        "queued_images": sum(s.queue_depth()
                                             for s in scheds),
                        "outstanding_s": round(sum(s.outstanding_s()
                                                   for s in scheds), 6),
                    })
                    pub.publish(state)

            router = ReplicaRouter(replicas)
            with router:
                client = LoopbackClient(router)
                watcher.start()
                th = threading.Thread(target=_publish_mid, daemon=True)
                th.start()
                stats = demo.replay_load(client, trace, pool=pool,
                                         seed=seed, drain_timeout_s=drain_s)
                th.join()
                # Deterministic close: the last publish may land between
                # background polls — one awaited poll before stopping.
                watcher.poll_once(wait=True)
                watcher.stop()
            rstats = router.stats()
            rep = watcher.report()

        swap_ms = sorted(rep["swap_ms"])
        return {
            "rolling": rolling,
            "publishes": len(row_states),
            "installs": rep["installed"],
            "installed_version": rep["installed_version"],
            "weights_versions": [e["weights_version"]
                                 for e in rstats["replicas"]],
            "swap_ms_p50": round(float(np.percentile(swap_ms, 50)), 3),
            "swap_ms_p99": round(float(np.percentile(swap_ms, 99)), 3),
            "swap_ms_max": round(swap_ms[-1], 3),
            "swap_samples": len(swap_ms),
            "in_flight_at_publish": samples,
            "recompiles": sum(len(r.engine._exec) - c
                              for r, c in zip(replicas, exec_counts)),
            "goodput_rps": stats["goodput_rps"],
            "attainment": stats["attainment"],
            "replies": stats["replies"],
            "unresolved": stats["unresolved"],
        }

    out = {
        "backend": jax.default_backend(),
        "model": model,
        "replicas": n_replicas,
        "offered_rps": round(n_requests / max(span_s, 1e-9), 1),
        "slo_ms": slo_ms,
        "provenance": (
            "same single-physical-core host caveat as serving_load; the "
            "swap rows replay the steady row's exact trace while a "
            "background publisher lands fresh bundles at fixed fractions "
            "of the span, so the goodput dip is attributable to the swap "
            "machinery alone."),
    }

    log(f"[bench] hotswap: steady row, {n_requests} reqs @ "
        f"{out['offered_rps']} rps, SLO {slo_ms:g} ms")
    steady, _rs = _replay()
    out["steady"] = {"goodput_rps": steady["goodput_rps"],
                     "attainment": steady["attainment"],
                     "replies": steady["replies"],
                     "unresolved": steady["unresolved"]}

    for rolling in (True, False):
        name = "rolling" if rolling else "all_at_once"
        row_states = states[:publishes_per_row] if rolling \
            else states[publishes_per_row:]
        log(f"[bench] hotswap: {name} swap row, "
            f"{publishes_per_row} publishes mid-trace")
        row = _swap_row(rolling, row_states)
        row["goodput_dip_pct"] = round(
            100.0 * (1.0 - row["goodput_rps"]
                     / max(steady["goodput_rps"], 1e-9)), 2)
        out[name] = row
        log(f"[bench] hotswap: {name} swap_ms p50 {row['swap_ms_p50']} "
            f"p99 {row['swap_ms_p99']}, recompiles {row['recompiles']}, "
            f"goodput dip {row['goodput_dip_pct']}%")

    out["zero_recompiles"] = (out["rolling"]["recompiles"] == 0
                              and out["all_at_once"]["recompiles"] == 0)
    if not out["zero_recompiles"]:
        log("[bench] hotswap: WARNING executable caches GREW across a "
            "swap row — the weights-as-arguments contract is broken")
    return out


def run_elastic(log, *, headline_model: str = "vgg11", ndev=None,
                global_batch: int = 256, data_dir: str = "./data",
                max_iters: int = 50, microshards: int = 4) -> dict:
    """Elastic-layer numbers (``cs744_ddp_tpu/elastic/``), measured:

    * ``shrink`` — an injected mid-epoch ``rank_death`` at full world: the
      emergency-checkpoint + coordinator-shrink + rebuild-and-resume wall
      clock, the world transition, and the steps-lost accounting (strong
      scaling replays only the interrupted window — the step counter
      itself carries over unchanged).
    * ``grow`` — the shrunk run's checkpoint resumed back at the full
      world: resume-plan numbers plus the rebuild+catch-up wall clock.
    * ``degraded_throughput`` — steady-state throughput of the strong-
      scaling microshard window at world 1 (the ladder's synchronous
      fallback) vs the full mesh: what you KEEP while degraded.

    Standalone-callable, like ``run_robustness``."""
    import tempfile
    import time as _time

    import jax

    from cs744_ddp_tpu.elastic import ElasticCoordinator
    from cs744_ddp_tpu.ft import ChaosPlan, FTConfig
    from cs744_ddp_tpu.utils.metrics import WINDOW

    log = log or (lambda s: print(s, file=sys.stderr))
    ndev = ndev or len(jax.devices())
    # The pinned program exists at worlds dividing the microshard count.
    world = max(w for w in range(1, min(ndev, microshards) + 1)
                if microshards % w == 0 and global_batch % w == 0)
    lim = max(max_iters, 2 * WINDOW)
    out = {"protocol": "strong", "microshards": microshards,
           "world": world, "global_batch": global_batch}

    def mk(w, ft=None):
        return _make_trainer(headline_model, "allreduce", w,
                             global_batch=global_batch, data_dir=data_dir,
                             log=lambda s: None, limit_train_batches=lim,
                             limit_eval_batches=1, ft=ft, elastic="strong")

    if world < 2:
        log("[bench] elastic: single-device host — shrink/grow ladder "
            "needs world >= 2; measuring degraded throughput only")
    else:
        # Shrink: rank (world-1) dies mid-epoch; the coordinator walks the
        # ladder and the resumed run finishes the epoch at the new world.
        death_step = lim // 2
        log(f"[bench] elastic: shrink — rank_death at step {death_step} "
            f"of {lim}, world {world}")
        chaos = ChaosPlan([("rank_death", death_step, world - 1)])
        with tempfile.TemporaryDirectory() as ckpt:
            coord = ElasticCoordinator(
                lambda w: mk(w, ft=FTConfig(chaos=chaos)),
                world=world, global_batch=global_batch,
                microshards=microshards, chaos=chaos, log=lambda s: None)
            t0 = _time.time()
            tr = coord.run(1, ckpt)
            total_s = _time.time() - t0
            ev = next(e for e in coord.events if e["kind"] == "shrink")
            plan = tr.resume_plan
            out["shrink"] = {
                "from_world": ev["from_world"],
                "to_world": ev["to_world"],
                "death_step": ev["step"],
                # Coordinator decision latency (probe + plan + membership
                # transition) vs the full recovery including trainer
                # rebuild, re-staging and the resumed epoch remainder.
                "coordinator_recovery_s": round(ev["recovery_s"], 3),
                "total_run_s": round(total_s, 3),
                # Strong scaling: the step counter is world-invariant, so
                # the only re-executed work is the interrupted window.
                "steps_lost": (ev["step"] - plan.start_step
                               if plan is not None else 0),
            }

            # Grow: resume the shrunk run's checkpoint back at full world.
            log(f"[bench] elastic: grow — resuming at world {world}")
            t0 = _time.time()
            tr2 = mk(world)
            tr2.run(2, checkpoint_dir=ckpt)
            out["grow"] = {
                "to_world": world,
                "resume_run_s": round(_time.time() - t0, 3),
            }

    # Degraded-mode throughput: the pinned program at world 1 vs world N.
    def _ips(w):
        tr = mk(w)
        return max(tr.steady_state_throughput(
                       max_iters=max_iters, window_iters="epoch")[0]
                   for _ in range(2))

    log("[bench] elastic: degraded-mode throughput (world 1 fallback)")
    degraded = _ips(1)
    full = _ips(world) if world > 1 else degraded
    out["degraded_throughput"] = {
        "world1_images_per_sec": round(degraded, 2),
        f"world{world}_images_per_sec": round(full, 2),
        "degraded_fraction": round(degraded / full, 3) if full else None,
    }
    return out


# The compression cost sheet's tiers: the uncompressed controls first
# (per-param = the byte baseline the ISSUE ratios are against; ddp and
# overlap share its bytes and differ in schedule), then the lossy tiers.
COMPRESSION_TIERS = ("allreduce", "ddp", "overlap",
                     "compress-bf16", "compress-int8", "powersgd")


def run_compression(log, *, headline_model: str = "vgg11", ndev=None,
                    global_batch: int = 256, data_dir: str = "./data",
                    max_iters: int = 100,
                    tiers=COMPRESSION_TIERS) -> Optional[dict]:
    """Compression-tier cost sheet (round 7) on THIS host's mesh:

    * ``comm_result_mib`` — MEASURED collective result bytes from each
      tier's pre-optimization step lowering (the same accounting the
      audit's byte contracts certify — static, immune to host noise),
      with the ratio vs the uncompressed per-param tier,
    * ``wall_clock_s_best`` / ``images_per_sec_per_chip`` — interleaved
      min-over-rounds epoch wall clock: each round visits every tier
      once, so a host-contention burst inflates all tiers equally
      instead of landing on one entry (the test_spectrum_wallclock
      noise discipline), and
    * ``convergence_delta_pct`` — test accuracy after an IDENTICAL
      warm+timed training schedule per tier, minus the uncompressed
      ``allreduce`` tier's accuracy: the lossy tiers' accuracy cost,
      measured rather than promised.

    None (with a logged reason) on a single-device host — every tier's
    sync is a no-op there, so the sheet would be noise around zero."""
    import time as _time

    import jax

    from cs744_ddp_tpu.analysis import audit as auditlib

    log = log or (lambda s: print(s, file=sys.stderr))
    ndev = ndev or len(jax.devices())
    if ndev < 2:
        log("[bench] compression: single-device host — tiers collapse to "
            "no-op sync; section omitted")
        return None

    # Static comm bytes: one step-path lowering per tier (no compile).
    try:
        zoo = auditlib.audit_zoo(
            model=headline_model, global_batch=global_batch,
            strategies=tiers, paths=("step",), include_eval=False,
            num_devices=ndev)
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] compression: static lowering failed ({e!r}); "
            "section omitted")
        return None
    comm_mib = {
        r.program.rsplit("/", 1)[-1]:
            sum(r.stats.get("result_bytes", {}).values()) / 2**20
        for r in zoo.reports}

    lim = min(max_iters, 30)
    try:
        trainers = {}
        for t in tiers:
            log(f"[bench] compression: staging {headline_model}/{t} "
                f"on {ndev} device(s)")
            trainers[t] = _make_trainer(
                headline_model, t, ndev, global_batch=global_batch,
                data_dir=data_dir, log=lambda s: None,
                limit_train_batches=lim, limit_eval_batches=4)
        tr0 = trainers[tiers[0]]
        nfull, tail_per = tr0._per_rank_batch_counts()
        images = (min(lim, nfull) * global_batch
                  + (tail_per * tr0.world
                     if lim > nfull and tail_per else 0))
        for t in tiers:
            trainers[t].train_model(0)      # compile + warm
        best = {t: float("inf") for t in tiers}
        for _ in range(3):
            for t in tiers:
                t0 = _time.time()
                trainers[t].train_model(0)
                best[t] = min(best[t], _time.time() - t0)
        acc = {}
        for t in tiers:
            _, _, acc[t] = trainers[t].test_model()
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] compression: measurement failed ({e!r}); "
            "section omitted")
        return None

    base_mib = comm_mib.get("allreduce")
    out = {
        "protocol": f"{lim} batches/epoch, 1 warm + 3 interleaved timed "
                    f"epochs (min over rounds), global batch "
                    f"{global_batch}, f32",
        "world": ndev,
        "baseline_tier": "allreduce",
        "per_tier": {},
    }
    for t in tiers:
        out["per_tier"][t] = {
            "wall_clock_s_best": round(best[t], 3),
            "images_per_sec_per_chip": round(images / best[t] / ndev, 2),
            "comm_result_mib": round(comm_mib.get(t, 0.0), 4),
            "comm_ratio_vs_allreduce": (
                round(base_mib / comm_mib[t], 2)
                if base_mib and comm_mib.get(t) else None),
            "test_accuracy_pct": round(acc[t], 2),
            "convergence_delta_pct": round(acc[t] - acc["allreduce"], 2),
        }
    return out


def _zoo_result(log, *, headline_model: str, global_batch: int,
                collect_hlo: bool = False):
    """Lower + audit the shipped-program zoo once (shared by the audit
    and attribution sections — one set of lowerings feeds both); None
    with a logged reason on failure."""
    import jax

    from cs744_ddp_tpu.analysis import audit as auditlib

    ndev = len(jax.devices())
    log(f"[bench] audit: program zoo for {headline_model} on {ndev} "
        "device(s)")
    try:
        return auditlib.audit_zoo(model=headline_model,
                                  global_batch=global_batch,
                                  serve_buckets=(1, 8),
                                  num_devices=ndev,
                                  collect_hlo=collect_hlo)
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] audit: zoo audit failed ({e!r}); section omitted")
        return None


def run_audit(log, *, headline_model: str = "vgg11",
              global_batch: int = 256, zoo=None) -> Optional[dict]:
    """Static program audit (``cs744_ddp_tpu/analysis/audit.py``) over the
    full shipped-program zoo on THIS host's devices: every train path x
    strategy, the eval window and the serving ladder, certified against
    their per-strategy cost contracts (collective shapes + the depth
    ladder, dtype leaks, donation, host syncs, baked constants).  The
    bench artifact carries the certification next to the numbers it
    certifies.  None (with a logged reason) when auditing fails — the
    section is advisory, never fatal to a finished measurement run."""
    log = log or (lambda s: print(s, file=sys.stderr))
    res = zoo if zoo is not None else _zoo_result(
        log, headline_model=headline_model, global_batch=global_batch)
    if res is None:
        return None
    for line in res.format_lines():
        log(f"[bench] {line}")
    return res.summary()


def run_attribution(log, *, headline_model: str = "vgg11",
                    headline_strategy: str = "ddp", ndev=None,
                    global_batch: int = 256, data_dir: str = "./data",
                    max_iters: int = 100, zoo=None) -> Optional[dict]:
    """Performance attribution (round 8): the static cost model
    (``analysis/costmodel.py``) walked over every zoo program's lowering
    — analytic FLOPs, HBM bytes, collective wire bytes -> per-program
    roofline bound, MFU ceiling and comm/compute ratio — plus a MEASURED
    join on the headline windowed program: per-dispatch wall clock from a
    real steady-state run against the same program's analytic flops,
    yielding achieved MFU on the numbers the audit section certifies.
    The ``overlap`` tier additionally reports its exposed-communication
    upper bound against ``ddp``'s chained plan.  None (logged reason)
    when any leg fails — advisory, never fatal."""
    import jax

    from cs744_ddp_tpu.analysis import audit as auditlib
    from cs744_ddp_tpu.analysis import costmodel
    from cs744_ddp_tpu.obs import attribution as attrlib

    log = log or (lambda s: print(s, file=sys.stderr))
    ndev = ndev or len(jax.devices())
    res = zoo
    if res is None or not res.hlo:
        res = _zoo_result(log, headline_model=headline_model,
                          global_batch=global_batch, collect_hlo=True)
    if res is None:
        return None
    try:
        out = auditlib.zoo_attribution(res)
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] attribution: static leg failed ({e!r}); "
            "section omitted")
        return None
    log(f"[bench] attribution: {len(out['programs'])} programs "
        "cost-modeled")

    # Measured join: steady-state per-step wall clock of the headline
    # windowed program vs the SAME lowering's analytic per-device flops.
    prog = f"train/window/{headline_strategy}"
    try:
        rep = costmodel.cost_report(res.hlo[prog], prog)
        trips = max(rep.trip_counts.values(), default=1)
        log(f"[bench] attribution: measured join on {prog} "
            f"({headline_model}, {ndev} device(s))")
        trainer = _make_trainer(headline_model, headline_strategy, ndev,
                                global_batch=global_batch,
                                data_dir=data_dir, log=lambda s: None)
        ips_per_chip = trainer.steady_state_throughput(
            max_iters=max_iters, window_iters="epoch")[1]
        step_s = global_batch / (ips_per_chip * ndev)
        out["measured"] = {
            "protocol": f"{headline_model}/{headline_strategy} on {ndev} "
                        f"device(s), global batch {global_batch}; "
                        "steady-state per-step wall clock vs the audited "
                        "window lowering's per-device analytic flops",
            "images_per_sec_per_chip": round(ips_per_chip, 2),
            **attrlib.attribute(
                rep, measured_s=step_s * trips,
                device_kind=trainer.mesh.devices.flat[0].device_kind),
        }
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] attribution: measured join failed ({e!r}); "
            "static leg kept")
        out.pop("measured", None)
    return out


def run_memory(log, *, headline_model: str = "vgg11",
               global_batch: int = 256, zoo=None,
               planner_worlds=(1, 2, 8),
               planner_window: int = 4) -> Optional[dict]:
    """Memory certification (round 20): the static liveness certifier
    (``analysis/memlife.py``) over every zoo lowering — peak HBM
    residency per program vs the single-sourced v5e capacity — plus a
    compiled differential on the headline train window (static peak must
    clear XLA's ``memory_analysis()`` temp+output floor and stay within
    the declared band), the process's live-array gauge as a runtime
    cross-check, and the K-epoch feasibility table
    (``analysis/megaplan.max_feasible_K``) at 16 GiB for the mega-program
    ROADMAP item.  None (logged reason) when certification fails —
    advisory, never fatal."""
    import jax

    from cs744_ddp_tpu.analysis import (audit as auditlib, costmodel,
                                        megaplan, memlife)

    log = log or (lambda s: print(s, file=sys.stderr))
    res = zoo
    if res is None or not res.hlo:
        res = _zoo_result(log, headline_model=headline_model,
                          global_batch=global_batch, collect_hlo=True)
    if res is None:
        return None
    try:
        reports = {name: memlife.mem_report(text, name)
                   for name, text in res.hlo.items()}
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] memory: liveness sweep failed ({e!r}); "
            "section omitted")
        return None
    budget = costmodel.V5E_HBM_CAPACITY_BYTES
    fattest = max(reports.values(), key=lambda r: r.peak_bytes)
    log(f"[bench] memory: {len(reports)} programs certified; fattest "
        f"{fattest.name} at {fattest.peak_bytes / 2**20:.1f} MiB of "
        f"{budget / 2**20:.0f} MiB")
    out = {
        "protocol": "static buffer-liveness peak per zoo lowering "
                    "(analysis/memlife.py) vs the single-sourced v5e HBM "
                    "capacity; compiled differential on the headline "
                    "window; K-epoch planner (analysis/megaplan.py)",
        "budget_mib": round(budget / 2**20, 1),
        "peak_mib_by_program": {
            name: round(r.peak_bytes / 2**20, 3)
            for name, r in sorted(reports.items())},
        "max_peak": {
            "program": fattest.name,
            "peak_mib": round(fattest.peak_bytes / 2**20, 3),
            "headroom_mib": round(
                (budget - fattest.peak_bytes) / 2**20, 3),
        },
    }

    # Compiled differential: the same window the attribution section
    # measures, compiled here so the artifact records the static bound
    # sitting on the right side of XLA's own accounting.
    try:
        ndev = len(jax.devices())
        lowered, name = megaplan.lower_window(
            headline_model, world=ndev, global_batch=global_batch,
            strategy="ddp" if ndev > 1 else "single")
        rep = memlife.mem_report(auditlib._hlo_text(lowered), name)
        ms = lowered.compile().memory_analysis()
        bad = memlife.check_against_compiled(rep, ms, windowed=True)
        floor = ((getattr(ms, "temp_size_in_bytes", 0) or 0)
                 + (getattr(ms, "output_size_in_bytes", 0) or 0))
        out["compiled_check"] = {
            "program": name,
            "static_peak_mib": round(rep.peak_bytes / 2**20, 3),
            "compiled_floor_mib": round(floor / 2**20, 3),
            "band": memlife.COMPILED_BAND,
            "clean": not bad,
            "findings": bad,
        }
        log(f"[bench] memory: compiled check on {name} "
            f"{'clean' if not bad else 'FAILED'} (static "
            f"{rep.peak_bytes / 2**20:.1f} MiB vs floor "
            f"{floor / 2**20:.1f} MiB)")
    except Exception as e:   # noqa: BLE001 - advisory section
        log(f"[bench] memory: compiled differential failed ({e!r}); "
            "static sweep kept")

    # Runtime cross-check: what this process actually holds live on
    # device right now (the per-run gauge lives in telemetry; tier-1
    # pins gauge <= certificate on a real windowed run).
    try:
        live = jax.live_arrays()
        out["runtime_live_mib"] = round(
            sum(int(getattr(a, "nbytes", 0) or 0) for a in live) / 2**20,
            2)
        out["runtime_live_arrays"] = len(live)
    except Exception:   # noqa: BLE001 - backend without the API
        pass

    # K-epoch mega-program feasibility (ROADMAP item 3 entry criterion).
    plans = {}
    for w in planner_worlds:
        try:
            plan = megaplan.plan_feasibility(
                headline_model, w, planner_window,
                global_batch=global_batch)
            plans[str(w)] = plan.to_dict()
            log(f"[bench] memory: planner {headline_model} world {w} "
                f"window {planner_window} -> max_k {plan.max_k} "
                f"(saves {plan.round_trips_saved} round-trips)")
        except Exception as e:   # noqa: BLE001 - advisory section
            log(f"[bench] memory: planner world {w} failed ({e!r})")
    if plans:
        out["planner"] = {"model": headline_model,
                          "window": planner_window,
                          "per_world": plans}
    return out


def run_bench(*, matrix: bool = True, sweep: bool = True,
              peak: bool = True, convergence: bool = True,
              convergence_epochs: int = 3,
              spectrum: bool = True, host_pipeline: bool = True,
              compression: bool = True,
              robustness: bool = True, serving: bool = True,
              serving_load: bool = True,
              pipeline: bool = True,
              hotswap: bool = True,
              tracing: bool = True,
              elastic: bool = True,
              audit: bool = True,
              attribution: bool = True,
              memory: bool = True,
              serving_kwargs=None,
              max_iters: int = 100,
              global_batch: int = 256,
              models=MODELS, strategies=STRATEGIES, deep_rows=DEEP_ROWS,
              spectrum_deep_rows=(("resnet34", "allreduce"),
                                  ("resnet34", "ddp")),
              headline_model: str = "vgg11",
              peak_batch_candidates=(1536, 2048),
              log=None) -> dict:
    import jax

    log = log or (lambda s: print(s, file=sys.stderr))
    data_dir = os.environ.get("CIFAR_DATA_DIR", "./data")
    ndev = len(jax.devices())

    # Headline: the flagship config on all chips (ddp when the mesh is
    # non-trivial; Part-1 'single' semantics on one chip), best of
    # HEADLINE_RUNS independent runs with median/min recorded — see module
    # docstring and BASELINE.md for the one-sided-noise rationale.
    headline_strategy = "ddp" if ndev > 1 else "single"
    log(f"[bench] headline: {headline_model}/{headline_strategy} "
        f"on {ndev} device(s), best of {HEADLINE_RUNS}")
    headline_runs = []
    headline_flops = None
    for _ in range(HEADLINE_RUNS):
        ips, fl = _throughput(headline_model, headline_strategy, ndev,
                              global_batch=global_batch, max_iters=max_iters,
                              data_dir=data_dir, log=lambda s: None,
                              want_flops=headline_flops is None, repeats=2,
                              flops_log=log)
        headline_runs.append(ips)
        headline_flops = headline_flops or fl
    headline = max(headline_runs)

    result = {
        "metric": f"cifar10_{headline_model}_images_per_sec_per_chip",
        "value": round(headline, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(headline / TORCH_CPU_BASELINE_IPS, 2),
        "num_devices": ndev,
        "headline_stats": {
            "runs": [round(r, 2) for r in headline_runs],
            "best": round(max(headline_runs), 2),
            "median": round(statistics.median(headline_runs), 2),
            "min": round(min(headline_runs), 2),
        },
        **_mfu_fields(headline, headline_flops),
    }

    # Convergence oracle — the reference's own correctness signal (test
    # accuracy after training, /root/reference/src/Part 1/main.py:74-76),
    # tracked per round so the artifact carries it, not just a test
    # assertion — and as a TRAJECTORY (per-epoch accuracy over
    # ``convergence_epochs``; a half-broken step can luck into one
    # above-chance epoch, not a rising multi-epoch trend — VERDICT r4
    # item 3).  On this egress-less bench host the dataset is the
    # deterministic synthetic fallback (real_data=false; class-templated
    # noisy images, recalibrated round 7 so the reference config learns
    # GRADUALLY — rising epoch over epoch, between the 10% chance floor
    # and the label-noise ceiling); real-CIFAR accuracy remains
    # unverifiable here (BASELINE.md).
    if convergence:
        log(f"[bench] convergence: {headline_model}/{headline_strategy}, "
            f"{convergence_epochs} epochs @ reference config")
        # In-memory telemetry recorder (no out_dir): the section's steady-
        # state step-time percentiles ride along in the bench artifact.
        from cs744_ddp_tpu.obs import Telemetry
        conv_tel = Telemetry()
        trainer = _make_trainer(headline_model, headline_strategy, ndev,
                                global_batch=global_batch, data_dir=data_dir,
                                log=lambda s: None, telemetry=conv_tel)
        per_epoch = []
        first_loss = None
        for ep in range(convergence_epochs):
            timers = trainer.train_model(ep)
            if first_loss is None:
                first_loss = timers.losses[0]
            avg_loss, _, acc = trainer.test_model()
            per_epoch.append({
                "train_loss_last": round(timers.losses[-1], 4),
                "test_avg_loss": round(avg_loss, 4),
                "test_accuracy_pct": round(acc, 2),
            })
        result["convergence"] = {
            "protocol": f"{convergence_epochs} epochs, reference config "
                        f"(global batch {global_batch}, SGD 0.1/0.9/1e-4, "
                        "f32)",
            "train_loss_first": round(first_loss, 4),
            "train_loss_last": per_epoch[-1]["train_loss_last"],
            "test_avg_loss": per_epoch[-1]["test_avg_loss"],
            "test_accuracy_pct": per_epoch[-1]["test_accuracy_pct"],
            "per_epoch": per_epoch,
            "real_data": trainer.real_data,
            "telemetry_summary": conv_tel.finalize(
                global_batch=global_batch),
        }
        # Companion entry at a stable lr: a faster-learning 1-epoch control
        # next to the reference-lr trajectory.  On the ROUND-7 recalibrated
        # synthetic task (data/cifar10.py knob comments) the reference
        # lr=0.1 no longer collapses the net — it climbs epoch over epoch
        # (tiny @ 12.8k imgs: 16% -> 32% -> 35%) — but it starts slow, so
        # the CI learning floor rides on this lr=0.01 entry, which clears
        # chance decisively within one epoch (tiny @ 12.8k imgs: 50%).
        # Round-5 history (single-template task: lr 0.1 froze VGG-11 at
        # 19.7%, lr 0.01 hit 100% in one epoch) is preserved in BASELINE.md.
        from cs744_ddp_tpu.ops import sgd as _sgd
        stable_cfg = _sgd.SGDConfig(lr=0.01)
        log(f"[bench] convergence: {headline_model}/{headline_strategy}, "
            f"1 epoch @ stable lr {stable_cfg.lr}")
        tr2 = _make_trainer(headline_model, headline_strategy, ndev,
                            global_batch=global_batch, data_dir=data_dir,
                            log=lambda s: None, sgd_cfg=stable_cfg)
        timers2 = tr2.train_model(0)
        avg_loss2, _, acc2 = tr2.test_model()
        result["convergence"]["stable_lr"] = {
            "protocol": f"1 epoch, SGD {stable_cfg.lr}/"
                        f"{stable_cfg.momentum}/"
                        f"{stable_cfg.weight_decay}, f32",
            "train_loss_last": round(timers2.losses[-1], 4),
            "test_avg_loss": round(avg_loss2, 4),
            "test_accuracy_pct": round(acc2, 2),
        }

    if spectrum:
        # Always the full 3-tier cross (STRATEGIES default): the section's
        # information IS the tier contrast, so it does not follow a pruned
        # matrix ``strategies``.
        spec = _collect_spectrum(log, headline_model, global_batch,
                                 deep_rows=spectrum_deep_rows)
        if spec is not None:
            result["spectrum"] = spec

    if matrix:
        result["matrix"] = {}
        # flops depend on (model, batch) only — strategies and precision
        # share (a bf16 matmul performs the same multiply-adds).
        model_flops = {headline_model: headline_flops}
        for model, strategy in _matrix_pairs(ndev, models, strategies,
                                             deep_rows):
            entry_key = f"{model}/{strategy}"
            if model == headline_model and strategy == headline_strategy:
                # Iteration-for-iteration identical to a headline run —
                # reuse one run instead of another measurement.
                ips = headline_runs[0]
            else:
                log(f"[bench] matrix: {entry_key} on {ndev} device(s)")
                ips, fl = _throughput(
                    model, strategy, ndev, global_batch=global_batch,
                    max_iters=max_iters, data_dir=data_dir,
                    log=lambda s: None,
                    want_flops=model not in model_flops, repeats=2,
                    flops_log=log)
                model_flops.setdefault(model, fl)
            result["matrix"][entry_key] = {
                "images_per_sec_per_chip": round(ips, 2),
                **_mfu_fields(ips, model_flops.get(model)),
            }
        # One deep row in bf16 mixed precision at the parity batch: the
        # parity matrix is f32-only and the peak entry changes batch AND
        # precision at once, so neither isolates what mixed precision buys
        # a DEEP model at the reference's config (VERDICT r5 satellite).
        if deep_rows:
            bmodel, bstrat = deep_rows[-1]
            entry_key = f"{bmodel}/{bstrat}/bf16"
            log(f"[bench] matrix: {entry_key} on {ndev} device(s)")
            ips, fl = _throughput(
                bmodel, bstrat, ndev, global_batch=global_batch,
                max_iters=max_iters, data_dir=data_dir, log=lambda s: None,
                precision="bf16", want_flops=bmodel not in model_flops,
                repeats=2, flops_log=log)
            model_flops.setdefault(bmodel, fl)
            result["matrix"][entry_key] = {
                "images_per_sec_per_chip": round(ips, 2),
                "precision": "bf16",
                **_mfu_fields(ips, model_flops.get(bmodel)),
            }

    # Peak throughput: the parity protocol pins global batch 256 / f32
    # (the reference's config), which underfills the MXU on one chip; this
    # reports the frontier with both constraints lifted (bf16 mixed
    # precision, large per-chip batch) — same measurement design.  The
    # frontier is a SEARCH over the two best measured batch candidates
    # (1536 then 2048 images/chip; the day-long sweep measured
    # 1536 > 2048 > 2560 > 3072 on v5e, within a couple % of each other),
    # reporting the winning config — which also shields the headline peak
    # from a single moment of host contention.
    if peak:
        best, best_ips = None, None
        for per_chip_batch in dict.fromkeys(peak_batch_candidates):
            peak_global = per_chip_batch * ndev
            log(f"[bench] peak: {headline_model}/bf16/batch{peak_global} "
                f"on {ndev} device(s)")
            ips, fl = _throughput(
                headline_model, headline_strategy, ndev,
                global_batch=peak_global, max_iters=max(max_iters // 3, 2),
                data_dir=data_dir, log=lambda s: None,
                precision="bf16", want_flops=True, repeats=2,
                flops_log=log)
            # Compare UNROUNDED ips (the stored value is rounded; a
            # near-tie within the rounding step could otherwise pick a
            # candidate inconsistent with the reported numbers).
            if best_ips is None or ips > best_ips:
                best_ips = ips
                best = {
                    "config": f"{headline_model}/bf16/"
                              f"global_batch={peak_global}",
                    "images_per_sec_per_chip": round(ips, 2),
                    **_mfu_fields(ips, fl),
                }
        result["peak"] = best

    # Host-pipeline throughput: the --host-augment mode (the reference's
    # DataLoader-worker model — C++ crop/flip on host, windowed uint8
    # staging since round 5).  Regression-tracked here because its wins
    # were previously hand-measured only; bounded by the host->device
    # link, not the chip.
    if host_pipeline:
        log(f"[bench] host_pipeline: {headline_model}/{headline_strategy}/"
            "--host-augment, chunked windowed")
        # Cap at 98 batches (~half an epoch at batch 256): the path is
        # host->device-link-bound (BASELINE.md), so a full --max-iters
        # run would spend its time measuring the link for no extra
        # information.
        lim = min(max_iters, 98)
        if lim < max_iters:
            log(f"[bench] host_pipeline: capped at {lim} batches "
                f"(link-bound path; --max-iters {max_iters} applies to "
                "the device-bound sections)")
        from cs744_ddp_tpu.obs import Telemetry as _Telemetry
        host_tel = _Telemetry()   # in-memory; summary attached below
        trh = _make_trainer(headline_model, headline_strategy, ndev,
                            global_batch=global_batch, data_dir=data_dir,
                            log=lambda s: None, host_augment=True,
                            limit_train_batches=lim, telemetry=host_tel)
        # Images actually trained per epoch: the limit may exceed the
        # epoch's full-batch count (large global batches), in which case
        # the ragged tail trains too — assuming lim batches would inflate
        # the rate.
        nfull, tail_per = trh._per_rank_batch_counts()
        images = (min(lim, nfull) * global_batch
                  + (tail_per * trh.world
                     if lim > nfull and tail_per else 0))
        import time as _time
        trh.train_model(0)  # compile + warm
        best_ips = 0.0
        for _ in range(3):
            t0 = _time.time()
            trh.train_model(0)
            best_ips = max(best_ips, images / (_time.time() - t0))
        # Chunk-count sweep: K=1 is round 5's whole-window staging (the
        # degenerate control — no overlap), larger K trades per-put
        # fixed cost for compute/transfer overlap.  1 warm epoch +
        # best-of-2 per point (vs best-of-3 for the main K above).
        chunk_sweep = {str(trh.host_chunks): round(best_ips / ndev, 2)}
        for k in (1, 2, 8):
            if k == trh.host_chunks:
                continue
            log(f"[bench] host_pipeline: chunk_sweep K={k}")
            trk = _make_trainer(headline_model, headline_strategy, ndev,
                                global_batch=global_batch,
                                data_dir=data_dir, log=lambda s: None,
                                host_augment=True, host_chunks=k,
                                limit_train_batches=lim)
            trk.train_model(0)
            k_ips = 0.0
            for _ in range(2):
                t0 = _time.time()
                trk.train_model(0)
                k_ips = max(k_ips, images / (_time.time() - t0))
            chunk_sweep[str(k)] = round(k_ips / ndev, 2)
        from cs744_ddp_tpu.data import native as _native
        result["host_pipeline"] = {
            "mode": "chunked uint8 staging (fl_gather_augment_u8 into a "
                    "reusable arena, per-chunk device_put overlapped with "
                    "the previous window's compute, on-device "
                    "concatenate), normalize fused on device",
            # False = the C++ library failed to load and the NumPy
            # fallback ran — a much slower number that must not be read
            # as a regression of the native path.
            "native_lib": _native.available(),
            "host_chunks": trh.host_chunks,
            "images_per_sec_per_chip": round(best_ips / ndev, 2),
            # The pure-device_put ceiling this achieved number is judged
            # against (BASELINE.md VERDICT item 3 closure).
            "link_floor": measure_link_floor(
                log, global_batch=global_batch, ndev=ndev),
            "chunk_sweep": chunk_sweep,
            # Spans cover host_augment / chunk_put / chunk_wait wall
            # clock; percentiles cover the timed epochs' steady windows.
            "telemetry_summary": host_tel.finalize(
                global_batch=global_batch),
        }

    # Compression-tier cost sheet: measured comm bytes, interleaved
    # wall clock and the convergence delta vs the uncompressed tier
    # (round 7; the static byte CONTRACTS are certified by the audit
    # section — this is the measured companion).
    if compression:
        comp = run_compression(
            log, headline_model=headline_model, ndev=ndev,
            global_batch=global_batch, data_dir=data_dir,
            max_iters=max_iters)
        if comp is not None:
            result["compression"] = comp

    # Fault-tolerance cost/benefit: guard overhead, degraded-staging
    # fraction, emergency checkpoint wall clock, skip-policy demo.
    if robustness:
        result["robustness"] = run_robustness(
            log, headline_model=headline_model,
            headline_strategy=headline_strategy, ndev=ndev,
            global_batch=global_batch, data_dir=data_dir,
            max_iters=max_iters)

    # Serving fast path: ladder throughput curve, open-loop latency,
    # cold/warm startup (cs744_ddp_tpu/serve/).
    if serving:
        result["serving"] = run_serving(log, model=headline_model,
                                        **(serving_kwargs or {}))

    # Serving tier under load (round 9): replica scaling at fixed SLO,
    # goodput-vs-offered saturation, 2x tiered overload with confined
    # shedding, continuous-vs-drain queue-wait (cs744_ddp_tpu/serve/).
    if serving_load:
        result["serving_load"] = run_serving_load(log)

    # Dispatch pipeline (round 14): serial vs pipelined vs device-program
    # floor per rung, capacity A/B with the scheduler pipeline on/off,
    # stage waterfall + occupancy at the pipelined capacity point
    # (cs744_ddp_tpu/serve/ two-slot dispatch).
    if pipeline:
        result["pipeline"] = run_pipeline(log)

    # Train-to-serve weight hot-swap (round 10): swap latency p50/p99,
    # in-flight work at each publish instant, goodput dip vs the steady
    # row, rolling vs all-at-once — zero recompiles pinned
    # (cs744_ddp_tpu/publish/).
    if hotswap:
        result["hotswap"] = run_hotswap(log)

    # Distributed tracing (round 12): capacity with tracing off vs on
    # (<= 5% overhead pin), and a real two-OS-process run reconstructed
    # into skew-corrected waterfalls by obs/aggregate.py.
    if tracing:
        result["tracing"] = run_tracing(log)

    # Elastic layer: shrink/grow resume latency, steps lost, and
    # degraded single-rank throughput (cs744_ddp_tpu/elastic/).
    if elastic:
        result["elastic"] = run_elastic(
            log, headline_model=headline_model, ndev=ndev,
            global_batch=global_batch, data_dir=data_dir,
            max_iters=max_iters)

    # Static program audit + cost-model attribution + memory
    # certification: ONE set of zoo lowerings feeds all three sections —
    # the certification and the numbers cannot drift apart.
    if audit or attribution or memory:
        zoo = _zoo_result(log, headline_model=headline_model,
                          global_batch=global_batch,
                          collect_hlo=attribution or memory)
        if audit:
            audit_summary = run_audit(log, headline_model=headline_model,
                                      global_batch=global_batch, zoo=zoo)
            if audit_summary is not None:
                result["audit"] = audit_summary
        if attribution:
            attr = run_attribution(
                log, headline_model=headline_model,
                headline_strategy=headline_strategy, ndev=ndev,
                global_batch=global_batch, data_dir=data_dir,
                max_iters=max_iters, zoo=zoo)
            if attr is not None:
                result["attribution"] = attr
        if memory:
            mem = run_memory(log, headline_model=headline_model,
                             global_batch=global_batch, zoo=zoo)
            if mem is not None:
                result["memory"] = mem

    if sweep:
        # WEAK scaling: per-chip batch held at ``global_batch`` while the
        # mesh grows (global = global_batch x n).  The north star is
        # images/sec/CHIP efficiency (BASELINE.json >=90% at 1->8), which
        # is a constant-per-chip-work metric: at the reference's fixed
        # global 256 on 8 chips the per-chip batch would be 32 against a
        # full 37 MB gradient all-reduce per step — comm-dominated by
        # construction, measuring the protocol rather than the framework.
        # The reference's own strong-scaling config (global 256 divided
        # across workers) is what the MATRIX measures.
        counts = [n for n in (1, 2, 4, 8, 16, 32, 64) if n <= ndev]
        if counts[-1] != ndev:
            counts.append(ndev)
        per_chip, sweep_flops = {}, {}
        for n in counts:
            strat_n = "ddp" if n > 1 else "single"
            # n=1 with per-chip batch == global_batch is exactly a headline
            # run's config on a 1-chip host: reuse one run's value (same
            # best-of-2-per-trainer statistic as fresh sweep points).
            if n == 1 and ndev == 1 and strat_n == headline_strategy:
                per_chip[n] = headline_runs[0]
                sweep_flops[n] = headline_flops
                continue
            log(f"[bench] sweep: {headline_model}/{strat_n} on {n} "
                f"device(s), global batch {global_batch * n}")
            per_chip[n], sweep_flops[n] = _throughput(
                headline_model, strat_n, n, global_batch=global_batch * n,
                max_iters=max_iters, data_dir=data_dir, log=lambda s: None,
                repeats=2, want_flops=True, flops_log=log)
        base = per_chip[1]
        result["scaling"] = {
            "protocol": f"weak scaling, {global_batch} images/chip",
            "images_per_sec_per_chip": {str(n): round(v, 2)
                                        for n, v in per_chip.items()},
            "efficiency_vs_1chip": {str(n): round(v / base, 3)
                                    for n, v in per_chip.items()},
        }
        sweep_mfu = {
            str(n): _mfu_fields(v, sweep_flops[n]).get("mfu_vs_bf16_peak")
            for n, v in per_chip.items()}
        if any(v is not None for v in sweep_mfu.values()):
            result["scaling"]["mfu_vs_bf16_peak"] = sweep_mfu

        # STRONG scaling — the reference's own protocol (global batch 256
        # DIVIDED across workers, Part 2a/main.py:22): the per-chip batch
        # shrinks as the mesh grows, so comm exposure rises by construction
        # (BASELINE.md "Scaling protocol").  Reported alongside the weak
        # sweep so both protocols are on the record; efficiency is
        # global-throughput(n) / (n x global-throughput(1)), which reduces
        # to the same per-chip ratio as the weak formula.
        strong_counts = [n for n in counts if global_batch % n == 0]
        strong = {}
        for n in strong_counts:
            strat_n = "ddp" if n > 1 else "single"
            if n == 1 and 1 in per_chip:
                strong[n] = per_chip[1]   # identical config: reuse
                continue
            log(f"[bench] sweep(strong): {headline_model}/{strat_n} on {n} "
                f"device(s), global batch {global_batch}")
            strong[n], _ = _throughput(
                headline_model, strat_n, n, global_batch=global_batch,
                max_iters=max_iters, data_dir=data_dir, log=lambda s: None,
                repeats=2)
        result["scaling"]["strong"] = {
            "protocol": f"strong scaling, global batch {global_batch} "
                        "(the reference's config)",
            "images_per_sec": {str(n): round(v * n, 2)
                               for n, v in strong.items()},
            "efficiency_vs_1chip": {str(n): round(v / strong[1], 3)
                                    for n, v in strong.items()},
        }
    return result


# The compact head's keys (module docstring "Emission contract"): the
# driver tail-captures ~2000 bytes of stdout and JSON-parses the LAST
# line, so the head carries only the fixed-size summary fields plus a
# pointer to the sidecar with everything else.
CONTRACT_KEYS = ("metric", "value", "unit", "vs_baseline", "num_devices",
                 "headline_stats", "tflops_per_sec", "mfu_vs_bf16_peak")
HEAD_LINE_BUDGET = 1800   # bytes, < the driver's ~2000-byte tail capture


def emit_result(result: dict, sidecar_path: str, out=print) -> dict:
    """Emit a bench result per the driver contract: full payload FIRST (one
    stdout line + the ``sidecar_path`` file), compact head as the FINAL
    stdout line.  Rounds 4/5 printed the full payload as the last line and
    overflowed the driver's tail capture ("parsed": null in rounds 4/5)
    — hence the split, and the hard size check on the head.  Returns the
    head dict; tests/test_bench.py pins both emissions."""
    payload = json.dumps(result)
    # Self-validate before emitting: a non-serializable value (numpy
    # scalar, NaN under a strict parser) must fail HERE with a clear
    # error, not downstream in the consumer.
    reparsed = json.loads(payload)
    if reparsed.keys() != result.keys():
        raise RuntimeError("bench JSON round-trip dropped keys: "
                           f"{set(result) ^ set(reparsed)}")
    # Atomic sidecar publish: a bench killed (or preempted) mid-write must
    # leave the previous BENCH_FULL.json intact, never a torn one — the
    # committed artifact is read by drivers and tests.
    tmp = f"{sidecar_path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w") as f:
            f.write(payload + "\n")
        os.replace(tmp, sidecar_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    out(payload)
    head = {k: result[k] for k in CONTRACT_KEYS if k in result}
    head["full_payload_file"] = os.path.basename(sidecar_path)
    head_line = json.dumps(head)
    if len(head_line) > HEAD_LINE_BUDGET:
        raise RuntimeError(
            f"bench head line is {len(head_line)} bytes, over the "
            f"{HEAD_LINE_BUDGET}-byte driver budget; trim CONTRACT_KEYS")
    out(head_line)
    return head


def _enable_compilation_cache() -> None:
    """Persist XLA compilations (the matrix compiles six train-window
    programs, ~40 s each on TPU, identical across bench invocations)."""
    from cs744_ddp_tpu.utils.compcache import \
        enable_persistent_compilation_cache
    enable_persistent_compilation_cache()


def main(argv=None) -> None:
    p = argparse.ArgumentParser("bench")
    p.add_argument("--no-matrix", action="store_true",
                   help="headline metric only (fast driver mode; also "
                        "skips the peak, convergence and spectrum "
                        "sections)")
    p.add_argument("--no-sweep", action="store_true",
                   help="skip the 1..N-device scaling sweep")
    p.add_argument("--no-peak", action="store_true",
                   help="skip the bf16 large-batch peak-throughput entry")
    p.add_argument("--no-convergence", action="store_true",
                   help="skip the 1-epoch accuracy (convergence oracle) "
                        "entry")
    p.add_argument("--no-spectrum", action="store_true",
                   help="skip the static per-strategy collective-stats "
                        "section (v5e-8 AOT lowering)")
    p.add_argument("--no-host-pipeline", action="store_true",
                   help="skip the windowed --host-augment throughput entry")
    p.add_argument("--no-compression", action="store_true",
                   help="skip the compression-tier cost sheet (measured "
                        "comm bytes, interleaved wall clock, convergence "
                        "delta vs the uncompressed tier)")
    p.add_argument("--no-robustness", action="store_true",
                   help="skip the fault-tolerance cost/benefit section "
                        "(guard overhead, degraded staging, emergency "
                        "checkpoint timing, skip-policy demo)")
    p.add_argument("--no-serving", action="store_true",
                   help="skip the serving fast-path section (bucket "
                        "throughput curve, open-loop latency, cold/warm "
                        "startup)")
    p.add_argument("--no-serving-load", action="store_true",
                   help="skip the serving-tier load section (replica "
                        "scaling at fixed SLO, goodput-vs-offered curve, "
                        "2x tiered overload with confined shedding, "
                        "continuous-vs-drain queue-wait)")
    p.add_argument("--no-pipeline", action="store_true",
                   help="skip the dispatch-pipeline section (serial vs "
                        "pipelined vs device-program floor per rung, "
                        "capacity A/B with the scheduler pipeline on/off, "
                        "stage waterfall + two-slot occupancy)")
    p.add_argument("--no-hotswap", action="store_true",
                   help="skip the weight hot-swap section (swap latency "
                        "p50/p99, in-flight work at publish, goodput dip "
                        "vs steady, rolling vs all-at-once, zero-recompile "
                        "pin)")
    p.add_argument("--no-tracing", action="store_true",
                   help="skip the distributed-tracing section (capacity "
                        "tracing off vs on with the 5% overhead pin, "
                        "two-OS-process waterfall reconstruction, "
                        "aggregation wall clock)")
    p.add_argument("--no-elastic", action="store_true",
                   help="skip the elastic section (shrink/grow resume "
                        "latency, steps lost, degraded single-rank "
                        "throughput)")
    p.add_argument("--no-audit", action="store_true",
                   help="skip the static program-zoo audit section "
                        "(analysis/audit.py cost-shape certification)")
    p.add_argument("--no-attribution", action="store_true",
                   help="skip the cost-model attribution section "
                        "(analysis/costmodel.py analytic FLOPs/bytes per "
                        "zoo program + the measured MFU join on the "
                        "headline windowed program)")
    p.add_argument("--no-memory", action="store_true",
                   help="skip the memory certification section "
                        "(analysis/memlife.py peak-HBM liveness per zoo "
                        "program, the compiled differential, and the "
                        "analysis/megaplan.py K-epoch feasibility table)")
    p.add_argument("--max-iters", type=int, default=100,
                   help="minimum steady-state iterations per config")
    p.add_argument("--global-batch", type=int, default=256)
    p.add_argument("--require-real-data", action="store_true",
                   help="fail before measuring anything if CIFAR_DATA_DIR "
                        "(default ./data) holds no real CIFAR-10 pickle "
                        "batches — the right mode for any bench whose "
                        "convergence numbers will be read as CIFAR-10 "
                        "results (throughput is data-independent)")
    p.add_argument("--full-out", default=None,
                   help="path for the full-payload JSON sidecar (default: "
                        "BENCH_FULL.json next to this script; the compact "
                        "final-stdout-line head names it in "
                        "full_payload_file)")
    args = p.parse_args(argv)

    if args.require_real_data:
        from cs744_ddp_tpu.data import cifar10
        data_dir = os.environ.get("CIFAR_DATA_DIR", "./data")
        if not cifar10.has_real_data(data_dir):
            raise SystemExit(
                f"--require-real-data: no CIFAR-10 pickle batches under "
                f"{data_dir!r} (expected "
                f"{data_dir}/cifar-10-batches-py/data_batch_*); refusing "
                "to bench against the synthetic stand-in")

    _enable_compilation_cache()
    result = run_bench(matrix=not args.no_matrix, sweep=not args.no_sweep,
                       peak=not (args.no_peak or args.no_matrix),
                       convergence=not (args.no_convergence
                                        or args.no_matrix),
                       spectrum=not (args.no_spectrum or args.no_matrix),
                       host_pipeline=not (args.no_host_pipeline
                                          or args.no_matrix),
                       compression=not (args.no_compression
                                        or args.no_matrix),
                       robustness=not (args.no_robustness
                                       or args.no_matrix),
                       serving=not (args.no_serving or args.no_matrix),
                       serving_load=not (args.no_serving_load
                                         or args.no_matrix),
                       pipeline=not (args.no_pipeline or args.no_matrix),
                       hotswap=not (args.no_hotswap or args.no_matrix),
                       tracing=not (args.no_tracing or args.no_matrix),
                       elastic=not (args.no_elastic or args.no_matrix),
                       audit=not (args.no_audit or args.no_matrix),
                       attribution=not (args.no_attribution
                                        or args.no_matrix),
                       memory=not (args.no_memory or args.no_matrix),
                       max_iters=args.max_iters,
                       global_batch=args.global_batch)
    emit_result(result, args.full_out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_FULL.json"))


if __name__ == "__main__":
    main()
