"""CLI — the reference's argparse surface plus strategy/model selection.

Reference flags (``/root/reference/src/Part 2a/main.py:156-175``):
``--master`` (coordinator IP, required there), ``--num-nodes``, ``--rank``,
``--epochs`` (default 1); port 6585 and global batch 256 hardcoded.  Here the
same knobs exist (with modern aliases), plus:

  * ``--strategy {single,gather,allreduce,ddp,overlap,compress-bf16,
    compress-int8,powersgd}`` selects the gradient-sync strategy: the
    Part-1/2a/2b/3 reference equivalents plus the round-7 extensions
    (overlapped bucketed DDP and the compressed collectives —
    error-feedback bf16/int8 quantization and PowerSGD low-rank);
  * ``--model {vgg11,resnet18}`` selects the model (resnet18 = the
    BASELINE.json stress config);
  * ``--num-devices`` restricts the mesh (e.g. to compare 1 vs 8 chips).

Run: ``python -m cs744_ddp_tpu.cli --strategy ddp --epochs 1``
"""

from __future__ import annotations

import argparse

from .ft import FTConfig, ChaosPlan, guard as ftguard
from .obs import NULL, Telemetry
from .utils import compcache
from .ops import sgd
from .parallel import mesh as meshlib
from .train.loop import GLOBAL_BATCH, Trainer


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("cs744_ddp_tpu")
    p.add_argument("--master", "--coordinator", dest="master", default=None,
                   help="coordinator address for multi-host runs "
                        "(reference --master)")
    p.add_argument("--num-nodes", "--num-processes", dest="num_nodes",
                   type=int, default=1,
                   help="number of host processes (reference --num-nodes)")
    p.add_argument("--rank", "--process-id", dest="rank", type=int, default=0,
                   help="this process's id (reference --rank)")
    p.add_argument("--epochs", type=int, default=1,
                   help="epochs to run (reference default 1)")
    p.add_argument("--strategy", default="allreduce",
                   choices=["single", "gather", "allreduce", "ddp",
                            "overlap", "compress-bf16", "compress-int8",
                            "powersgd"],
                   help="gradient sync strategy: Part 1/2a/2b/3 equivalents "
                        "(single/gather/allreduce/ddp) plus overlapped "
                        "bucketed DDP (overlap) and the compressed "
                        "collectives (compress-bf16/compress-int8 with "
                        "error feedback, powersgd low-rank)")
    p.add_argument("--compress-rank", type=int, default=None,
                   help="PowerSGD approximation rank (default 4); only "
                        "meaningful with --strategy powersgd")
    p.add_argument("--model", default="vgg11",
                   help="vgg11/13/16/19, resnet18/34, a decoder of "
                        "models.DECODERS (sdar-30b-a3b: block diffusion, "
                        "MoE; qwen3-next-80b-a3b: linear + full attention, "
                        "MoE with a shared expert; xing4.0-29b-a4b: latent "
                        "attention, a 4-stream hyper-connected residual, "
                        "sigmoid-routed MoE; sdar-tiny / qwen3-next-tiny / "
                        "xing4-tiny: their CPU test sizes; see "
                        "'decoder share'), or any name "
                        "registered via models.register_model (validated "
                        "by the model zoo, not argparse, so plugged-in "
                        "models work everywhere the built-ins do)")
    lm = p.add_argument_group(
        "decoder share",
        "what THIS chip holds of a decoder (one chip's share of the "
        "published model at its published widths) and the sequences it "
        "trains on; defaults, sdar-30b-a3b: 6 of 48 layers, experts 0-15 of "
        "128, 4096 tokens in blocks of 4; qwen3-next-80b-a3b: 4 of 48 "
        "layers (whole periods of linear, linear, linear, full: the kind "
        "of a layer comes from the configuration), experts 0-31 of 512, "
        "8192 tokens; both 18,992 rows of the vocabulary; xing4.0-29b-a4b: "
        "5 of 40 layers of which the first is dense, experts 0-7 of 64, "
        "4096 tokens, 16,384 rows.  Data: "
        "<data-dir>/tokens/train.npy + heldout.npy ([N, L] int32) or a "
        "synthetic stream; an epoch is --limit-train-batches steps of the "
        "stream")
    lm.add_argument("--lm-layers", type=int, default=None)
    lm.add_argument("--lm-dense-layers", type=int, default=None,
                    help="how many of --lm-layers, the leading ones, have "
                         "a dense feed-forward (a decoder with leading "
                         "dense layers only)")
    lm.add_argument("--lm-experts-held", default=None, metavar="IDS",
                    help="expert ids held here, e.g. 0-15 or 0,3,5")
    lm.add_argument("--lm-vocab", type=int, default=None,
                    help="rows of the embedding and the head held here; "
                         "the last id is never data (a block-diffusion "
                         "decoder's mask id)")
    lm.add_argument("--lm-seq-len", type=int, default=None)
    lm.add_argument("--lm-block", type=int, default=None,
                    help="block length of the diffusion objective (a "
                         "block-diffusion decoder only)")
    p.add_argument("--init-seed", type=int, default=None,
                   help="seed of the initial weights where it is not the "
                        "data's (default: the trainer's one seed)")
    p.add_argument("--batch-size", type=int, default=GLOBAL_BATCH,
                   help="GLOBAL batch (divided across workers, as in the "
                        "reference: Part 2a/main.py:22)")
    p.add_argument("--num-devices", type=int, default=None,
                   help="use only the first N local devices")
    p.add_argument("--data-dir", default="./data")
    p.add_argument("--require-real-data", action="store_true",
                   help="fail loudly if --data-dir holds no real CIFAR-10 "
                        "pickle batches instead of silently training on the "
                        "deterministic synthetic fallback (the right mode "
                        "for any run whose accuracy numbers will be read "
                        "as CIFAR-10 results)")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--no-augment", action="store_true")
    p.add_argument("--host-augment", action="store_true",
                   help="run the train transform in the C++ host pipeline "
                        "(data/native.py, the reference's DataLoader-worker "
                        "model), staged as uint8 window buffers and "
                        "dispatched as scanned windows (per-batch f32 under "
                        "--profile-phases); default keeps the transform "
                        "fused on device")
    p.add_argument("--precision", default="f32", choices=["f32", "bf16"],
                   help="compute precision: f32 = reference parity; bf16 = "
                        "mixed precision (f32 master weights/optimizer/BN "
                        "stats/loss, bf16 matmul+conv — the MXU native mode)")
    p.add_argument("--profile-phases", action="store_true",
                   help="additionally time a forward-only program to report "
                        "the reference's fwd/bwd split. NOTE: this per-step "
                        "mode pays one host dispatch + fetch per call, so "
                        "phase times can dwarf the fused windowed step time "
                        "the default mode reports; use --profile-dir for a "
                        "real trace")
    p.add_argument("--limit-train-batches", type=int, default=None,
                   help="cap train iterations per epoch (smoke runs/benches)")
    p.add_argument("--limit-eval-batches", type=int, default=None,
                   help="cap evaluation batches (smoke runs/benches)")
    p.add_argument("--port", type=int, default=6585,
                   help="coordinator port (reference hardcodes 6585)")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the first trained "
                        "epoch (XPlane, TensorBoard/Perfetto-viewable) - the "
                        "superset of the print-based timers (SURVEY.md §5)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save TrainState after each epoch and auto-resume "
                        "from the latest checkpoint (beyond-parity: the "
                        "reference has no checkpointing)")
    p.add_argument("--publish-dir", default=None,
                   help="publish the serving weights (params + BN stats) "
                        "as a versioned crc-checksummed bundle into this "
                        "directory every --publish-every completed epochs; "
                        "a serving process started with "
                        "--serve-publish-dir on the same directory "
                        "hot-swaps each version between dispatches with "
                        "zero recompiles (publish/)")
    p.add_argument("--publish-every", type=int, default=1, metavar="K",
                   help="publish every K completed epochs (default 1); "
                        "only meaningful with --publish-dir")
    p.add_argument("--metrics-ring", type=int, default=None, metavar="N",
                   help="device-resident metric ring capacity for the "
                        "windowed train paths (obs/ringbuf.py): per-step "
                        "loss/grad-norm/ok rows are written on device and "
                        "drained ONCE per window instead of per step. "
                        "Default on (capacity 64); 0 disables (per-step "
                        "fetch of stacked window losses); N >= 20 sets "
                        "the capacity")
    p.add_argument("--telemetry-out", default=None,
                   help="write structured run telemetry to this directory: "
                        "manifest.json (run header), events.jsonl (per-step "
                        "events, spans, gauges) and summary.json (steady-"
                        "state percentiles); render with "
                        "tools/telemetry_report.py. Off by default (zero "
                        "overhead); the stdout print schedule is unchanged "
                        "either way")
    ft = p.add_argument_group(
        "fault tolerance (ft/)",
        "preemption-safe resume, supervised staging, non-finite guard and "
        "the deterministic chaos harness; all off by default (the hot path "
        "pays nothing)")
    ft.add_argument("--nonfinite", default="off", choices=ftguard.POLICIES,
                    help="per-step finiteness guard on loss + global grad "
                         "norm: halt = raise (the bad update is never "
                         "applied), skip = keep prior params and continue, "
                         "restore = roll back to the last checkpoint "
                         "snapshot; off (default) compiles no guard at all")
    ft.add_argument("--chaos", action="append", default=None,
                    metavar="SITE:step[:seed]",
                    help="inject a deterministic fault once at the given "
                         "step (repeatable); sites: producer_crash, "
                         "put_delay, put_fail, corrupt_slot, nonfinite_grad "
                         "(requires --nonfinite != off), preempt (requires "
                         "--checkpoint-dir). Rank-level sites (the third "
                         "field is the target RANK, not a seed — "
                         "SITE:step:rank): rank_death, slow_rank; "
                         "coordinator_loss fires on recovery progress "
                         "(requires --elastic). Replica-level sites (third "
                         "field is the target REPLICA, step counts its own "
                         "dispatches): replica_death, slow_replica, and "
                         "swap_mid_batch (a pending publish races a live "
                         "dispatch: the racing dispatch is answered by the "
                         "OLD weights, the next by the new) "
                         "(requires --serve-frontend). Publish-level sites "
                         "(step counts the publisher's own publishes, "
                         "third field is a payload seed): publish_torn "
                         "(bundle corrupted after rename — rejected on "
                         "crc, old version keeps serving), publish_stale "
                         "(re-announces the previous version — skipped) "
                         "(require --publish-dir)")
    ft.add_argument("--ft-put-timeout", type=float, default=30.0,
                    metavar="SECONDS",
                    help="watchdog deadline on each staged chunk device_put")
    ft.add_argument("--ft-put-retries", type=int, default=3,
                    help="attempts per chunk device_put (exponential "
                         "backoff between attempts)")
    ft.add_argument("--ft-stall-timeout", type=float, default=120.0,
                    metavar="SECONDS",
                    help="consumer-side staging stall deadline; exceeding "
                         "it triggers producer restart, then degraded "
                         "synchronous staging (stream bit-identical)")
    ft.add_argument("--ft-verify-chunks", action="store_true",
                    help="checksum every staged batch at fill time and "
                         "re-stage any row whose bytes changed by transfer "
                         "time (auto-enabled by corrupt_slot chaos)")
    el = p.add_argument_group(
        "elastic (elastic/)",
        "checkpoint-based world-resize resume: a run interrupted at "
        "world=N resumes at world=M with re-sharded data order; rank-level "
        "chaos drives the retry -> shrink -> single-rank degradation "
        "ladder (requires --checkpoint-dir)")
    el.add_argument("--elastic", default="off",
                    choices=["off", "weak", "strong"],
                    help="weak = pinned per-chip batch (global batch scales "
                         "with the world; deterministic, example-measured "
                         "resume); strong = pinned global batch re-bucketed "
                         "across the world with bitwise world-invariant "
                         "math (microshard window, elastic/step_elastic.py)")
    el.add_argument("--resume-world", type=int, default=None, metavar="M",
                    help="run/resume at world size M (overrides "
                         "--num-devices): checkpointed progress from any "
                         "previous world is re-planned onto M under the "
                         "--elastic protocol")
    sv = p.add_argument_group(
        "serving (serve/)",
        "single-chip inference: AOT bucket ladder + micro-batching + "
        "warm-start executable cache; --serve-demo replays a seeded "
        "open-loop request trace and prints the stats sheet as one JSON "
        "line instead of training")
    sv.add_argument("--serve-demo", action="store_true",
                    help="serve mode: build the executable ladder for "
                         "--model, replay the seeded synthetic request "
                         "trace at each --serve-load, print startup + "
                         "latency/throughput JSON")
    sv.add_argument("--serve-buckets", default="1,8,32,128,256",
                    help="comma list of batch buckets for the AOT ladder")
    sv.add_argument("--serve-precision", default="f32",
                    choices=["f32", "bf16"])
    sv.add_argument("--serve-requests", type=int, default=200,
                    help="requests per offered-load replay")
    sv.add_argument("--serve-load", action="append", type=float,
                    default=None, metavar="RPS",
                    help="offered load in requests/sec (repeatable; "
                         "default one replay at 20 rps)")
    sv.add_argument("--serve-max-wait-ms", type=float, default=5.0,
                    help="micro-batcher deadline: max time the oldest "
                         "queued request waits before dispatch")
    sv.add_argument("--serve-cache-dir", default=None,
                    help="warm-start executable cache directory (a "
                         "restarted server loads serialized executables "
                         "instead of compiling)")
    sv.add_argument("--serve-seed", type=int, default=0,
                    help="seed for the synthetic request trace AND the "
                         "demo model init")
    sv.add_argument("--serve-frontend", action="store_true",
                    help="serve mode: start --serve-replicas device-pinned "
                         "engine replicas behind the least-loaded router "
                         "and the socket front-end, replay the seeded "
                         "TIERED trace over a real socket at each "
                         "--serve-load, print goodput/SLO-attainment JSON")
    sv.add_argument("--serve-replicas", type=int, default=1, metavar="N",
                    help="engine replicas, one per mesh device "
                         "(round-robin when N exceeds the device count)")
    sv.add_argument("--serve-slo-ms", type=float, default=None,
                    metavar="MS",
                    help="flatten the trace to ONE tier with this SLO "
                         "(default: the 3-tier 75/200/600 ms mixture)")
    sv.add_argument("--serve-port", type=int, default=0, metavar="PORT",
                    help="front-end TCP port (0 = ephemeral; the bound "
                         "address is in the output JSON — tools/"
                         "serve_load.py replays against it)")
    sv.add_argument("--serve-pipeline", default="on", choices=["on", "off"],
                    help="double-buffered dispatch pipeline in each "
                         "replica's scheduler: stage + issue batch N+1 "
                         "while batch N computes (off = the serial "
                         "dispatch-fence-reply loop, exactly the round-13 "
                         "path; only with --serve-frontend)")
    sv.add_argument("--serve-shed", default="on", choices=["on", "off"],
                    help="deadline-aware load shedding in the scheduler "
                         "(off = serve everything, late replies included "
                         "— the no-shed ablation)")
    sv.add_argument("--serve-publish-dir", default=None, metavar="DIR",
                    help="watch DIR for published weight bundles (a "
                         "--publish-dir training run's output) and "
                         "hot-swap every replica to each new version "
                         "between dispatches — zero restarts, zero "
                         "recompiles; replies carry the serving "
                         "model_version (only with --serve-frontend)")
    sv.add_argument("--serve-publish-poll-ms", type=float, default=50.0,
                    metavar="MS",
                    help="publish-directory poll interval for "
                         "--serve-publish-dir (default 50 ms)")
    sv.add_argument("--serve-trace-client", default=None, metavar="DIR",
                    help="write the in-process load client's distributed-"
                         "trace spans (events.jsonl) to DIR — a second "
                         "stream for tools/trace_waterfall.py; server "
                         "spans ride --telemetry-out (only with "
                         "--serve-frontend)")
    sv.add_argument("--serve-alerts", default="on", choices=["on", "off"],
                    help="attach the streaming SLO alert engine "
                         "(obs/alerts.py) to the server telemetry; the "
                         "fired-rule summary lands in the manifest and "
                         "the output JSON (default on; needs "
                         "--telemetry-out)")
    au = p.add_argument_group(
        "static analysis (analysis/)",
        "HLO/jaxpr program audit: certify each compiled program's cost "
        "shape (collective contract per strategy, dtype leaks, donation "
        "misses, host syncs in loop bodies, baked constants) before any "
        "step runs; results land in the telemetry manifest")
    au.add_argument("--audit", default="off",
                    choices=["off", "warn", "strict"],
                    help="audit the programs this run will dispatch "
                         "(train: the configured strategy's step/window/"
                         "host-window + eval; serve: the bucket ladder). "
                         "warn prints findings and continues; strict "
                         "exits 2 on any unwaived finding")
    au.add_argument("--audit-zoo", action="store_true",
                    help="audit the FULL program zoo (all 8 strategies x "
                         "3 train paths, eval, the serving ladder at "
                         "--serve-buckets) and exit without training; "
                         "combine with --audit strict for the CI gate")
    au.add_argument("--audit-waive", action="append", default=None,
                    metavar="RULE[@GLOB]",
                    help="waive an audit rule, optionally only for "
                         "programs matching a glob, e.g. "
                         "baked-constants@serve/* (repeatable); waived "
                         "findings are reported but don't fail strict")
    au.add_argument("--verify-static", action="store_true",
                    help="run the whole-repo static verification gate "
                         "and exit: repo lints, the lock-order deadlock "
                         "detector (certified acquisition order), wire-"
                         "protocol schema conformance against serve/"
                         "wire.py, the full program-zoo audit (incl. the "
                         "peak-HBM liveness certificate vs the v5e "
                         "budget), and the static host-round-trip "
                         "certificate; prints a JSON summary, exits 2 "
                         "on any finding")
    return p


def model_from_args(args):
    """--model as the Trainer takes it: the name, or for a decoder given
    any of its share's flags the (init_fn, apply_fn) pair of that share."""
    share = {}
    for flag, field in (("lm_layers", "layers"), ("lm_vocab", "vocab"),
                        ("lm_dense_layers", "dense_layers"),
                        ("lm_seq_len", "seq_len"), ("lm_block", "block")):
        if getattr(args, flag) is not None:
            share[field] = getattr(args, flag)
    if args.lm_experts_held is not None:
        held = []
        for part in args.lm_experts_held.split(","):
            lo, _, hi = part.partition("-")
            held += range(int(lo), int(hi or lo) + 1)
        share["held"] = tuple(held)
    if not share:
        return args.model
    from . import models as model_zoo
    return model_zoo.get_model(args.model, **share)


def ft_config_from_args(args) -> "FTConfig | None":
    """FTConfig when any ft surface is requested, else None (the Trainer's
    ft=None fast path — no supervision wrappers, no guard compiled)."""
    defaults = (args.nonfinite == "off" and not args.chaos
                and args.ft_put_timeout == 30.0 and args.ft_put_retries == 3
                and args.ft_stall_timeout == 120.0
                and not args.ft_verify_chunks)
    if defaults:
        return None
    return FTConfig(
        nonfinite=args.nonfinite,
        chaos=ChaosPlan.parse(args.chaos),
        put_timeout_s=args.ft_put_timeout,
        put_retries=args.ft_put_retries,
        stall_timeout_s=args.ft_stall_timeout,
        verify_chunks=args.ft_verify_chunks,
    )


def _apply_audit(args, telemetry, result) -> None:
    """Shared --audit plumbing: print the report, record it in the run
    manifest (enabled recorders only — see analysis.audit.record_audit),
    exit 2 under strict when any unwaived finding remains."""
    from .analysis import audit as auditlib

    for line in result.format_lines():
        print(line)
    auditlib.record_audit(telemetry, result)
    if args.audit == "strict" and not result.clean:
        raise SystemExit(2)


def audit_main(args, telemetry) -> None:
    """--audit-zoo: certify the full shipped-program matrix and exit.
    With an enabled recorder the same lowerings also get a static
    cost-model attribution pass (analysis/costmodel) recorded under
    manifest["attribution"] — audit and attribution read ONE set of
    programs, so they cannot drift."""
    from .analysis import audit as auditlib
    from .serve import demo

    collect = getattr(telemetry, "enabled", False)
    result = auditlib.audit_zoo(
        model=args.model, global_batch=args.batch_size,
        precision=args.precision,
        serve_buckets=demo.parse_buckets(args.serve_buckets),
        serve_precision=args.serve_precision, serve_swap_recert=True,
        num_devices=args.num_devices, waive=args.audit_waive or (),
        metrics_ring=args.metrics_ring != 0, collect_hlo=collect)
    if collect:
        auditlib.record_attribution(
            telemetry, auditlib.zoo_attribution(result))
    _apply_audit(args, telemetry, result)


def verify_static_main(args, telemetry) -> None:
    """--verify-static: one gate over every static analyzer.  Repo lints
    + lock-order deadlock detection + wire schema conformance run first
    (pure AST, fast); then the full zoo is lowered once and shared by
    the program audit and the host-round-trip certificate.  The summary
    lands on stdout as JSON (and in the manifest for enabled recorders);
    any finding anywhere exits 2 — this is the CI front door
    tests/test_analysis.py::test_repo_static_verification pins."""
    import json
    import os

    from .analysis import audit as auditlib
    from .analysis import costmodel, memlife
    from .analysis import dispatch as dispatchlib
    from .analysis import lockgraph, wire_schema
    from .analysis.pylint_rules import DEFAULT_TARGETS, lint_paths
    from .serve import demo, wire
    from .utils.metrics import WINDOW

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = lint_paths([os.path.join(repo, t) for t in DEFAULT_TARGETS])
    graph = lockgraph.build_repo_graph(repo)
    findings += lockgraph.check_graph(graph)
    findings += wire_schema.check_wire(repo)
    result = auditlib.audit_zoo(
        model=args.model, global_batch=args.batch_size,
        precision=args.precision,
        serve_buckets=demo.parse_buckets(args.serve_buckets),
        serve_precision=args.serve_precision,
        num_devices=args.num_devices, waive=args.audit_waive or (),
        metrics_ring=args.metrics_ring != 0, collect_hlo=True)
    cert = dispatchlib.certify_zoo(result, window=4,
                                   nbatches=WINDOW + WINDOW // 4,
                                   include_eval=True)
    findings += memlife.check_memory(repo)
    for f in findings:
        print(f"[verify-static] {f.rule}: {f.path}:{f.line} {f.message}")
    for line in result.format_lines():
        print(line)
    peaks = {r.program: r.stats.get("peak_mib", 0.0)
             for r in result.reports}
    fattest = max(peaks, key=peaks.get) if peaks else None
    summary = {
        "clean": (not findings and result.clean and cert["clean"]),
        "lint_findings": len(findings),
        "lock_graph": lockgraph.graph_summary(graph),
        "wire_schema": wire.schema_summary(),
        "audit": {"clean": result.clean, "n_programs": len(result.reports),
                  "n_findings": len(result.findings())},
        "dispatch": cert,
        # Compact memory certificate: the zoo-wide peak vs the
        # single-sourced per-chip budget (the peak-memory audit rule is
        # what fails "clean"; this entry is the headline number).
        "memory": {
            "budget_mib": round(
                costmodel.V5E_HBM_CAPACITY_BYTES / 2**20, 1),
            "max_peak_mib": max(peaks.values(), default=0.0),
            "max_peak_program": fattest,
        },
    }
    print(json.dumps(summary))
    auditlib.record_audit(telemetry, result)
    if getattr(telemetry, "enabled", False):
        telemetry.update_manifest({"verify_static": {
            k: summary[k] for k in ("clean", "lint_findings", "audit")}})
    if not summary["clean"]:
        raise SystemExit(2)


def elastic_main(args, telemetry) -> None:
    """--elastic: train under the ElasticCoordinator's degradation ladder.
    The coordinator rebuilds the trainer at each membership generation;
    ``--resume-world M`` starts (or resumes a checkpointed run) at world M.
    Requires --checkpoint-dir — recovery and resize both go through the
    emergency checkpoint protocol."""
    import json

    from .elastic import ElasticCoordinator
    from .ft import NULL_CHAOS

    if args.checkpoint_dir is None:
        raise SystemExit("--elastic requires --checkpoint-dir (recovery "
                         "and world-resize resume go through checkpoints)")
    world = args.resume_world or args.num_devices or \
        meshlib.make_mesh(None).devices.size
    ft = ft_config_from_args(args)
    # ONE chaos plan shared by trainer and coordinator: entries are
    # one-shot across membership generations, so an injected fault fires
    # in exactly one generation.
    chaos = ft.chaos if ft is not None else NULL_CHAOS

    def make_trainer(w: int) -> Trainer:
        return Trainer(
            model=args.model, strategy=args.strategy, num_devices=w,
            compress_rank=args.compress_rank,
            global_batch=args.batch_size, data_dir=args.data_dir,
            augment=not args.no_augment, precision=args.precision,
            sgd_cfg=sgd.SGDConfig(lr=args.lr, momentum=args.momentum,
                                  weight_decay=args.weight_decay),
            limit_train_batches=args.limit_train_batches,
            limit_eval_batches=args.limit_eval_batches,
            metrics_ring=args.metrics_ring,
            telemetry=telemetry, ft=ft, elastic=args.elastic)

    coord = ElasticCoordinator(
        make_trainer, world=world, global_batch=args.batch_size,
        protocol=args.elastic, chaos=chaos)
    coord.run(args.epochs, checkpoint_dir=args.checkpoint_dir)
    report = coord.report()
    telemetry.update_manifest({"elastic_report": report})
    print("elastic report: " + json.dumps(report))


def build_replicas(args, telemetry, chaos) -> list:
    """The --serve-frontend replica set: ``--serve-replicas`` engines,
    replica i pinned to local device i (round-robin past the device
    count), all on one executable-cache dir."""
    import jax

    from .serve import demo
    from .serve.replica import EngineReplica

    devices = jax.devices()
    return [
        EngineReplica(i, args.model, device=devices[i % len(devices)],
                      buckets=demo.parse_buckets(args.serve_buckets),
                      precision=args.serve_precision,
                      seed=args.serve_seed, telemetry=telemetry,
                      cache_dir=args.serve_cache_dir, chaos=chaos,
                      shed=args.serve_shed == "on",
                      pipeline=args.serve_pipeline == "on")
        for i in range(max(1, args.serve_replicas))]


def serve_frontend_main(args, telemetry) -> dict:
    """--serve-frontend: replicated serving tier end-to-end — N
    device-pinned engine replicas behind the least-loaded router and the
    socket front-end; replay the seeded tiered trace over a REAL socket
    at each offered load, print ONE JSON line (startup + per-load
    goodput/attainment stats) and return the same record."""
    import json

    from .ft import NULL_CHAOS
    from .serve import demo
    from .serve.frontend import FrontendClient, ServingFrontend
    from .serve.router import ReplicaRouter

    ft = ft_config_from_args(args)
    chaos = ft.chaos if ft is not None else NULL_CHAOS
    buckets = demo.parse_buckets(args.serve_buckets)
    shed = args.serve_shed == "on"
    pipeline = args.serve_pipeline == "on"
    alerts = None
    if telemetry.enabled and args.serve_alerts == "on":
        from .obs import AlertEngine
        alerts = AlertEngine(telemetry)
        telemetry.add_tap(alerts.observe)
    client_tel = None
    if args.serve_trace_client is not None:
        client_tel = Telemetry(args.serve_trace_client)
        client_tel.write_manifest({"mode": "serve-frontend-client"})
    replicas = build_replicas(args, telemetry, chaos)
    telemetry.write_manifest({
        "mode": "serve-frontend", "model": args.model,
        "buckets": list(buckets), "precision": args.serve_precision,
        "replicas": len(replicas), "shed": shed, "pipeline": pipeline,
        "slo_ms": args.serve_slo_ms,
        "requests": args.serve_requests, "seed": args.serve_seed,
        "chaos": chaos.spec() if chaos.enabled else [],
    })
    startup = {f"replica{r.index}": r.startup() for r in replicas}
    tiers = demo.DEFAULT_TIERS if args.serve_slo_ms is None \
        else ((0, 1, float(args.serve_slo_ms)),)
    router = ReplicaRouter(replicas, telemetry=telemetry)
    watcher = None
    if args.serve_publish_dir is not None:
        from .publish import WeightWatcher
        watcher = WeightWatcher(
            args.serve_publish_dir, replicas, telemetry=telemetry,
            chaos=chaos,
            poll_interval_s=args.serve_publish_poll_ms / 1e3)
    stats = {}
    sizes = tuple(s for s in demo.SIZE_CHOICES if s <= buckets[-1])
    address = None
    with router:
        if watcher is not None:
            watcher.start()
        frontend = ServingFrontend(router, port=args.serve_port,
                                   telemetry=telemetry)
        try:
            with frontend:
                address = frontend.address
                pool = demo.request_pool()
                for rps in (args.serve_load or [20.0]):
                    trace = demo.synthetic_load_trace(
                        args.serve_requests, offered_rps=rps,
                        seed=args.serve_seed, size_choices=sizes, tiers=tiers)
                    with FrontendClient(frontend.address,
                                        telemetry=client_tel) as client:
                        stats[f"{rps:g}rps"] = demo.replay_load(
                            client, trace, pool=pool, seed=args.serve_seed)
        finally:
            if watcher is not None:
                watcher.stop()
            if client_tel is not None:
                client_tel.finalize()
    out = {"address": list(address), "startup": startup,
           "router": router.stats(), "load": stats}
    if watcher is not None:
        out["publish"] = watcher.report()
    if alerts is not None:
        out["alerts"] = alerts.summary()
    if telemetry.enabled:
        telemetry.update_manifest({"router": router.stats()})
        if watcher is not None:
            telemetry.update_manifest({"publish": watcher.report()})
        if alerts is not None:
            telemetry.update_manifest({"alerts": alerts.summary()})
    print(json.dumps(out))
    return out


def serve_main(args, telemetry) -> None:
    """--serve-demo: build the ladder, replay the seeded trace at each
    offered load, print ONE JSON line (startup report + per-load stats)."""
    import json

    from .serve import InferenceEngine, demo

    buckets = demo.parse_buckets(args.serve_buckets)
    engine = InferenceEngine(
        args.model, buckets=buckets, precisions=(args.serve_precision,),
        cache_dir=args.serve_cache_dir, seed=args.serve_seed,
        telemetry=telemetry)
    telemetry.write_manifest({
        "mode": "serve", "model": args.model, "buckets": list(buckets),
        "precision": args.serve_precision,
        "max_wait_ms": args.serve_max_wait_ms,
        "requests": args.serve_requests, "seed": args.serve_seed,
    })
    if args.audit != "off":
        from .analysis import audit as auditlib
        result = auditlib.AuditResult(reports=auditlib.audit_serving(
            engine=engine, precision=args.serve_precision,
            waive=args.audit_waive or ()))
        _apply_audit(args, telemetry, result)
    startup = engine.startup()
    loads = args.serve_load or [20.0]
    stats = {}
    for rps in loads:
        stats[f"{rps:g}rps"] = demo.run_demo(
            engine, n_requests=args.serve_requests, offered_rps=rps,
            seed=args.serve_seed, max_wait_ms=args.serve_max_wait_ms,
            precision=args.serve_precision)
    print(json.dumps({"startup": startup, "demo": stats}))


def main(argv=None):
    """Parse ``argv`` and run the selected mode.  Returns what an
    in-process caller (chip_smoke.py) needs to inspect the run beyond its
    telemetry files: the ``Trainer`` after a training run, the output
    record after ``--serve-frontend``; None for the other modes."""
    args = build_parser().parse_args(argv)
    # Persistent XLA compilation cache, unconditionally: repeated CLI runs
    # of the same config skip multi-second XLA compiles; hit/miss counts
    # land in the manifest.
    compcache.enable_persistent_compilation_cache()
    if args.require_real_data:
        from .data import cifar10
        if not cifar10.has_real_data(args.data_dir):
            raise SystemExit(
                f"--require-real-data: no CIFAR-10 pickle batches under "
                f"{args.data_dir!r} (expected "
                f"{args.data_dir}/cifar-10-batches-py/data_batch_*); "
                "refusing to fall back to the synthetic stand-in")
    meshlib.initialize_distributed(args.master, args.num_nodes, args.rank,
                                   port=args.port)
    telemetry = (Telemetry(args.telemetry_out)
                 if args.telemetry_out is not None else NULL)
    if args.verify_static:
        try:
            verify_static_main(args, telemetry)
        finally:
            telemetry.update_manifest(
                {"compilation_cache": compcache.cache_stats()})
            telemetry.finalize()
        return
    if args.audit_zoo:
        try:
            audit_main(args, telemetry)
        finally:
            telemetry.update_manifest(
                {"compilation_cache": compcache.cache_stats()})
            telemetry.finalize()
        return
    if args.serve_frontend:
        try:
            return serve_frontend_main(args, telemetry)
        finally:
            telemetry.update_manifest(
                {"compilation_cache": compcache.cache_stats()})
            telemetry.finalize()
    if args.serve_demo:
        try:
            serve_main(args, telemetry)
        finally:
            telemetry.update_manifest(
                {"compilation_cache": compcache.cache_stats()})
            telemetry.finalize()
        return
    if args.resume_world is not None and args.elastic == "off":
        raise SystemExit("--resume-world requires --elastic (weak|strong): "
                         "without a declared protocol there is no defined "
                         "mapping of saved progress onto a new world size")
    if args.elastic != "off":
        try:
            elastic_main(args, telemetry)
        finally:
            telemetry.update_manifest(
                {"compilation_cache": compcache.cache_stats()})
            telemetry.finalize(global_batch=args.batch_size)
        return
    trainer = Trainer(
        model=model_from_args(args),
        strategy=args.strategy,
        num_devices=args.num_devices,
        compress_rank=args.compress_rank,
        global_batch=args.batch_size,
        data_dir=args.data_dir,
        init_seed=args.init_seed,
        augment=not args.no_augment,
        precision=args.precision,
        sgd_cfg=sgd.SGDConfig(lr=args.lr, momentum=args.momentum,
                              weight_decay=args.weight_decay),
        profile_phases=args.profile_phases,
        host_augment=args.host_augment,
        limit_train_batches=args.limit_train_batches,
        limit_eval_batches=args.limit_eval_batches,
        metrics_ring=args.metrics_ring,
        telemetry=telemetry,
        ft=ft_config_from_args(args),
    )
    try:
        if args.audit != "off":
            # Certify the programs THIS run dispatches (configured
            # strategy's three train paths + eval) before any step runs;
            # strict exits 2 with nothing trained.  After the Trainer's
            # manifest write so the audit record merges instead of being
            # clobbered.
            from .analysis import audit as auditlib
            _apply_audit(args, telemetry, auditlib.audit_zoo(
                model=args.model, global_batch=args.batch_size,
                precision=args.precision,
                strategies=(args.strategy,),
                num_devices=args.num_devices,
                waive=args.audit_waive or (),
                metrics_ring=bool(trainer.metrics_ring)))
        trainer.run(args.epochs, checkpoint_dir=args.checkpoint_dir,
                    profile_dir=args.profile_dir,
                    publish_dir=args.publish_dir,
                    publish_every=args.publish_every)
    finally:
        # summary.json even on an interrupted run — partial runs are the
        # ones whose artifact is most needed.  Cache hit/miss tallies are
        # only final once every compile has happened, hence manifest
        # UPDATE here rather than a field at construction.
        telemetry.update_manifest(
            {"compilation_cache": compcache.cache_stats()})
        telemetry.finalize(global_batch=args.batch_size)
    return trainer


if __name__ == "__main__":
    main()
