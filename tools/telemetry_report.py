"""Render a telemetry run directory (obs/) into a human summary.

A ``--telemetry-out`` run leaves three artifacts: ``manifest.json`` (the run
header), ``events.jsonl`` (per-step events, spans, gauges, counters) and
``summary.json`` (steady-state percentiles).  This tool prints them as one
readable report — run header, step-time table, span totals, counters and
the last value of every gauge — recomputing the summary from the raw events
when ``summary.json`` is missing (interrupted runs).

Run:  python tools/telemetry_report.py <run-dir> [--json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cs744_ddp_tpu.obs import read_run, summarize_events  # noqa: E402
from cs744_ddp_tpu.obs.telemetry import (percentile,  # noqa: E402
                                         read_events_jsonl)


def _fmt_ms(seconds: float) -> str:
    return f"{seconds * 1e3:.2f} ms"


def _serving_lines(events) -> list:
    """Serving-path rendering (serve/ + --serve-demo runs): the queue-depth
    trace and per-bucket client-latency percentiles, both rebuilt from raw
    gauge events (``queue_depth``; ``serve_latency_ms`` with its ``bucket``
    attr).  Returns [] for runs with no serving events — training-run
    reports are unchanged."""
    depth, lat = [], {}
    for e in events:
        if e.get("kind") != "gauge":
            continue
        if e.get("name") == "queue_depth":
            depth.append(e["value"])
        elif e.get("name") == "serve_latency_ms":
            lat.setdefault(e.get("bucket", "?"), []).append(e["value"])
    if not depth and not lat:
        return []
    lines = ["== serving =="]
    if depth:
        lines.append(f"  queue_depth (images)   samples {len(depth)}  "
                     f"max {max(depth)}  "
                     f"mean {sum(depth) / len(depth):.1f}  "
                     f"last {depth[-1]}")
    if lat:
        lines.append("  request latency by bucket (client-side, "
                     "enqueue -> logits):")
        for b in sorted(lat, key=str):
            v = lat[b]
            lines.append(f"    bucket {b!s:<6} x{len(v):<6} "
                         f"p50 {percentile(v, 50):8.2f} ms  "
                         f"p95 {percentile(v, 95):8.2f} ms  "
                         f"p99 {percentile(v, 99):8.2f} ms")
    lines.append("")
    return lines


def _wire_ext_lines(events) -> list:
    """Wire extension-block health: unknown TLV tags skipped and torn
    trailing fields dropped by the codec (``wire_ext_skipped`` counter,
    per frame kind).  Non-zero numbers mean a peer on a different
    protocol build is talking to this process — the cross-version drift
    signal ROADMAP item 1 needs.  Returns [] when no frame ever skipped
    a field — same-build runs are unchanged."""
    per = {}
    for e in events:
        if e.get("kind") == "counter" and e.get("name") == "wire_ext_skipped":
            key = e.get("frame", "?")
            unknown, torn = per.get(key, (0, 0))
            per[key] = (unknown + e.get("unknown", 0),
                        torn + e.get("torn", 0))
    if not per:
        return []
    lines = ["== wire extension skips =="]
    for frame in sorted(per):
        unknown, torn = per[frame]
        lines.append(f"  {frame:<10} unknown tags skipped {unknown:<6} "
                     f"torn fields dropped {torn}")
    lines.append("")
    return lines


def _elastic_lines(events, manifest) -> list:
    """Elastic-mode rendering (``--elastic`` runs): per-rank step-time
    percentiles from the raw ``rank_step_time_s`` gauges, straggler flags,
    and the rank-death count — the report-side face of the round-6
    world-resize layer.  Returns [] for runs with no elastic signal —
    non-elastic reports are unchanged."""
    per, flags, deaths = {}, {}, 0
    for e in events:
        kind, name = e.get("kind"), e.get("name")
        if kind == "gauge" and name == "rank_step_time_s":
            per.setdefault(e.get("rank", "?"), []).append(e["value"])
        elif kind == "counter" and name == "straggler_flagged":
            flags[e.get("rank", "?")] = \
                flags.get(e.get("rank", "?"), 0) + e.get("inc", 1)
        elif kind == "counter" and name == "rank_deaths":
            deaths = e["total"]
    cfg = (manifest or {}).get("elastic")
    if not per and not flags and not deaths and not cfg:
        return []
    lines = ["== elastic =="]
    if cfg:
        proto = cfg.get("protocol")
        ms = cfg.get("microshards")
        lines.append(f"  protocol               {proto}"
                     + (f" (microshards {ms})" if ms else ""))
    if per:
        lines.append("  per-rank step time (window-boundary attribution):")
        for r in sorted(per, key=str):
            v = per[r]
            mark = f"  straggler x{flags[r]}" if r in flags else ""
            lines.append(f"    rank {r!s:<4} x{len(v):<6} "
                         f"p50 {_fmt_ms(percentile(v, 50)):>12}  "
                         f"max {_fmt_ms(max(v)):>12}{mark}")
    if deaths:
        lines.append(f"  rank deaths            {deaths}")
    lines.append("")
    return lines


def _audit_lines(manifest) -> list:
    """Program-audit rendering (``--audit`` runs write
    ``manifest["audit"]`` via analysis/audit.py's ``record_audit``):
    verdict, per-program rule grid and any findings.  Returns [] when the
    manifest carries no audit record — older runs render unchanged."""
    audit = (manifest or {}).get("audit")
    if not isinstance(audit, dict):
        return []
    lines = ["== program audit =="]
    verdict = "CLEAN" if audit.get("clean") else "DIRTY"
    lines.append(f"  {verdict}: {audit.get('n_programs', 0)} programs, "
                 f"{audit.get('n_findings', 0)} findings, "
                 f"{audit.get('n_waived', 0)} waived")
    for prog, rec in sorted((audit.get("programs") or {}).items()):
        rules = rec.get("rules") or {}
        failed = sorted(r for r, v in rules.items() if v == "fail")
        waived = sorted(r for r, v in rules.items() if v == "waived")
        status = "FAIL " + ",".join(failed) if failed else "pass"
        if waived:
            status += f"  (waived {','.join(waived)})"
        depth = rec.get("chain_depth")
        lines.append(f"  {prog:<28} depth {depth!s:<4} {status}")
    for f in audit.get("findings") or []:
        lines.append(f"    !! {f.get('program')}: [{f.get('rule')}] "
                     f"{f.get('message')}")
    ladder = audit.get("ladder")
    if ladder:
        lines.append(f"  strategy depth ladder    {ladder}")
    lines.append("")
    return lines


def _attribution_lines(manifest) -> list:
    """Cost-model attribution rendering (round 8: ``--audit-zoo`` with a
    telemetry dir records ``manifest["attribution"]`` via
    analysis/audit.record_attribution): per-program analytic
    FLOPs/HBM/wire with the roofline verdict, the measured MFU join when
    present, and overlap's exposed-comm bound vs ddp.  Returns [] when
    the manifest carries no attribution record — older runs render
    unchanged."""
    attr = (manifest or {}).get("attribution")
    if not isinstance(attr, dict):
        return []
    lines = ["== attribution (static cost model) =="]
    progs = attr.get("programs") or {}
    if progs:
        lines.append(f"  {'program':<28} {'gflops':>9} {'hbm_mib':>9} "
                     f"{'wire_mib':>9}  bound      comm/compute")
        for name, rec in sorted(progs.items()):
            ratio = rec.get("comm_compute_ratio")
            lines.append(
                f"  {name:<28} {rec.get('gflops', 0):>9} "
                f"{rec.get('hbm_mib', 0):>9} {rec.get('wire_mib', 0):>9}  "
                f"{rec.get('roofline_bound', '?'):<9}  "
                f"{ratio if ratio is not None else '-'}")
    measured = attr.get("measured")
    if isinstance(measured, dict):
        lines.append(f"  measured join          {measured.get('program')}: "
                     f"{measured.get('images_per_sec_per_chip')} img/s/chip, "
                     f"mfu {measured.get('mfu_vs_bf16_peak')}, "
                     f"{measured.get('roofline_bound')}-bound")
    ov = attr.get("overlap_vs_ddp")
    if isinstance(ov, dict):
        lines.append(f"  overlap exposed comm   <= "
                     f"{ov.get('overlap_exposed_bytes_upper_bound')} B vs "
                     f"ddp chained {ov.get('ddp_chained_bytes')} B "
                     f"(hiding ratio >= {ov.get('hiding_ratio_lower_bound')})")
    lines.append("")
    return lines


def _memory_lines(events, manifest) -> list:
    """Memory rendering (round 20): the runtime ``memory`` gauges that
    ``train/loop.emit_memory_gauges`` records at window/epoch boundaries
    (peak host RSS, live device bytes via ``jax.live_arrays``) joined
    against the static peak-HBM certificate the audit attaches per
    program (``peak_mib`` from analysis/memlife.py).  A measured device
    residency above the fattest certified peak means the liveness model
    missed a buffer — the same inequality tier-1 pins.  Returns [] for
    runs with neither signal — older runs render unchanged."""
    rss, live_mib, live_n = [], [], []
    for e in events:
        if e.get("kind") != "gauge" or e.get("name") != "memory":
            continue
        v = e.get("value")
        if not isinstance(v, dict):
            continue
        if "host_rss_peak_mib" in v:
            rss.append(v["host_rss_peak_mib"])
        if "device_live_mib" in v:
            live_mib.append(v["device_live_mib"])
        if "device_live_arrays" in v:
            live_n.append(v["device_live_arrays"])
    certified = {}
    for prog, rec in (((manifest or {}).get("audit") or {})
                      .get("programs") or {}).items():
        if isinstance(rec, dict) and rec.get("peak_mib") is not None:
            certified[prog] = rec["peak_mib"]
    if not rss and not live_mib and not certified:
        return []
    lines = ["== memory (measured vs certified) =="]
    if live_mib:
        lines.append(f"  device live (gauge)    x{len(live_mib):<6} "
                     f"max {max(live_mib):10.2f} MiB  "
                     f"last {live_mib[-1]:10.2f} MiB"
                     + (f"  ({live_n[-1]} arrays)" if live_n else ""))
    if rss:
        lines.append(f"  host RSS peak          x{len(rss):<6} "
                     f"max {max(rss):10.1f} MiB")
    if certified:
        fattest = max(certified, key=certified.get)
        lines.append(f"  certified peak (max)   {certified[fattest]:10.3f} "
                     f"MiB  ({fattest}, static liveness bound)")
        if live_mib:
            if max(live_mib) <= certified[fattest]:
                lines.append(f"  verdict                measured within "
                             f"certificate (headroom "
                             f"{certified[fattest] - max(live_mib):.2f} MiB)")
            else:
                lines.append(f"  !! measured device residency "
                             f"{max(live_mib):.2f} MiB EXCEEDS the "
                             f"certified peak — liveness model missed "
                             f"a buffer")
    lines.append("")
    return lines


def _trace_lines(events) -> list:
    """Serving-causality rendering (round 8): per-request trace ids ride
    the enqueue -> batch -> dispatch -> fetch spans, and two per-request
    gauges split client latency into queue wait vs service time.  Returns
    [] for runs with no trace signal — older runs render unchanged."""
    trace_reqs = set()
    dispatch_spans = 0
    dispatch_traced = 0
    qw, svc = [], []
    for e in events:
        kind, name = e.get("kind"), e.get("name")
        if kind == "span" and name == "serve_enqueue" and "trace" in e:
            trace_reqs.add(e["trace"])
        elif kind == "span" and name == "serve_dispatch":
            dispatch_spans += 1
            if e.get("traces"):
                dispatch_traced += 1
        elif kind == "gauge" and name == "serve_queue_wait_ms":
            qw.append(e["value"])
        elif kind == "gauge" and name == "serve_service_ms":
            svc.append(e["value"])
    if not trace_reqs and not qw and not svc:
        return []
    lines = ["== traces (request causality) =="]
    if trace_reqs:
        lines.append(f"  traced requests        {len(trace_reqs)}")
    if dispatch_spans:
        lines.append(f"  dispatch spans         {dispatch_spans} "
                     f"({dispatch_traced} carrying trace ids)")
    for label, v in (("queue wait", qw), ("service time", svc)):
        if v:
            lines.append(f"  {label:<12} x{len(v):<6} "
                         f"p50 {percentile(v, 50):8.2f} ms  "
                         f"p95 {percentile(v, 95):8.2f} ms  "
                         f"mean {sum(v) / len(v):8.2f} ms")
    lines.append("")
    return lines


def _slo_lines(events) -> list:
    """SLO scheduling rendering (round 9): per-tier attainment from the
    scheduler's ``serve_latency_ms`` gauges (``tier``/``met`` attrs),
    shed counts by tier/reason, failovers, and per-replica utilization.
    Returns [] for runs with no SLO signal — older runs render
    unchanged."""
    tiers = {}
    shed_reasons = {}
    failovers = 0
    deaths = 0
    util = {}
    for e in events:
        kind, name = e.get("kind"), e.get("name")
        if kind == "gauge" and name == "serve_latency_ms" and "met" in e \
                and "tier" in e:
            agg = tiers.setdefault(e["tier"], {"served": 0, "met": 0,
                                               "shed": 0})
            agg["served"] += 1
            agg["met"] += 1 if e["met"] else 0
        elif kind == "counter" and name == "serve_shed":
            if "tier" in e:
                agg = tiers.setdefault(e["tier"], {"served": 0, "met": 0,
                                                   "shed": 0})
                agg["shed"] += int(e.get("inc", 1))
            reason = str(e.get("reason", "unknown"))
            shed_reasons[reason] = shed_reasons.get(reason, 0) \
                + int(e.get("inc", 1))
        elif kind == "counter" and name == "serve_failover":
            failovers += int(e.get("inc", 1))
        elif kind == "counter" and name == "replica_death":
            deaths += int(e.get("inc", 1))
        elif kind == "gauge" and name == "replica_util" and "replica" in e:
            util[e["replica"]] = e["value"]
    if not tiers and not shed_reasons and not util:
        return []
    lines = ["== slo (tiered attainment) =="]
    for tier in sorted(tiers):
        agg = tiers[tier]
        offered = agg["served"] + agg["shed"]
        att = agg["met"] / offered if offered else 0.0
        lines.append(f"  tier {tier!s:<4} served {agg['served']:<6} "
                     f"met {agg['met']:<6} late "
                     f"{agg['served'] - agg['met']:<5} "
                     f"shed {agg['shed']:<5} attainment {att:7.2%}")
    if shed_reasons:
        detail = ", ".join(f"{r} {n}" for r, n in sorted(shed_reasons.items()))
        lines.append(f"  shed by reason         {detail}")
    if deaths or failovers:
        lines.append(f"  replica deaths         {deaths} "
                     f"({failovers} requests failed over)")
    if util:
        detail = "  ".join(f"r{k} {v:.2f}" for k, v in sorted(util.items()))
        lines.append(f"  replica utilization    {detail}")
    lines.append("")
    return lines


def _publish_lines(events) -> list:
    """Weight hot-swap rendering (round 10, ``publish/``): publish/install
    counters from both sides of the pipeline (publisher counts, installs,
    crc/signature rejections, stale skips), per-replica swap-latency
    percentiles from the watcher's ``swap_ms`` gauges, and the last
    published vs installed version.  Returns [] for runs with no publish
    signal — older runs render unchanged."""
    counts = {}
    swap_ms = []
    published = installed = None
    for e in events:
        kind, name = e.get("kind"), e.get("name")
        if kind == "counter" and name in (
                "publish_count", "publish_installed", "publish_rejected",
                "publish_stale_skipped", "publish_chaos_injected",
                "weights_installed"):
            counts[name] = e["total"]
        elif kind == "gauge" and name == "swap_ms":
            swap_ms.append(e["value"])
        elif kind == "gauge" and name == "publish_version":
            published = e["value"]
        elif kind == "gauge" and name == "installed_version":
            installed = e["value"]
    if not counts and not swap_ms and published is None \
            and installed is None:
        return []
    lines = ["== publish (weight hot-swap) =="]
    for name in ("publish_count", "publish_installed", "publish_rejected",
                 "publish_stale_skipped", "publish_chaos_injected",
                 "weights_installed"):
        if name in counts:
            lines.append(f"  {name:<22} {counts[name]}")
    if published is not None or installed is not None:
        lines.append(f"  version                published {published}  "
                     f"installed {installed}")
    if swap_ms:
        lines.append(f"  swap latency x{len(swap_ms):<6} "
                     f"p50 {percentile(swap_ms, 50):8.2f} ms  "
                     f"p99 {percentile(swap_ms, 99):8.2f} ms  "
                     f"max {max(swap_ms):8.2f} ms")
    lines.append("")
    return lines


def _pipeline_lines(events) -> list:
    """Dispatch-pipeline rendering (round 14): the scheduler's
    ``serve_inflight`` gauge traces per-replica pipeline occupancy (0..
    ``PIPELINE_SLOTS``) after every issue/completion — the occupancy
    distribution says how often batch N+1 actually overlapped batch N.
    ``serve_dispatch_fault`` counts completion-side faults that were
    isolated to one batch (explicit error replies, worker survived).
    Returns [] for runs with no pipeline signal — serial-mode and older
    runs render unchanged."""
    occ = {}
    faults = 0
    for e in events:
        kind, name = e.get("kind"), e.get("name")
        if kind == "gauge" and name == "serve_inflight":
            per = occ.setdefault(e.get("replica", "?"), {})
            v = int(e.get("value", 0))
            per[v] = per.get(v, 0) + 1
        elif kind == "counter" and name == "serve_dispatch_fault":
            faults += int(e.get("inc", 1))
    if not occ and not faults:
        return []
    lines = ["== dispatch pipeline =="]
    for replica in sorted(occ, key=str):
        per = occ[replica]
        n = sum(per.values())
        detail = "  ".join(f"{d} slots {per[d] / n:.0%}"
                           for d in sorted(per))
        lines.append(f"  replica {replica!s:<4} occupancy x{n:<6} "
                     f"max {max(per)}  {detail}")
    if faults:
        lines.append(f"  dispatch faults        {faults} "
                     f"(isolated: error replies, worker survived)")
    lines.append("")
    return lines


def _waterfall_lines(out_dir: str, events) -> list:
    """Distributed-trace rendering (round 12, ``obs/aggregate.py``): when
    the run carries ``trace_id``-stamped spans, reconstruct this one
    stream's request waterfalls (single-process view — use
    tools/trace_waterfall.py across N run dirs for the skew-corrected
    cross-process merge).  Returns [] for untraced runs."""
    if not any(e.get("kind") == "span" and e.get("trace_id")
               for e in events):
        return []
    from cs744_ddp_tpu.obs import aggregate as agg
    rep = agg.aggregate_streams(
        [agg.ProcessStream(os.path.basename(os.path.normpath(out_dir))
                           or out_dir, events)])
    lines = ["== waterfall (distributed traces, this stream) =="]
    lines.append(f"  traces                 {rep['traces']} "
                 f"({rep['complete']} complete, {rep['orphaned']} "
                 f"orphaned/partial)")
    for stage, a in rep["stage_ms"].items():
        lines.append(f"  {stage:<16} x{a['count']:<6} "
                     f"p50 {a['p50']:8.2f} ms  p99 {a['p99']:8.2f} ms")
    dom = rep["critical_path"].get("dominant")
    if dom:
        share = rep["critical_path"]["share"].get(dom)
        lines.append(f"  critical path          {dom} "
                     f"({share:.0%} of stage time)")
    lines.append("")
    return lines


# The dispatch loop's spans (train/loop.py default path, test_model), in the
# order an epoch opens them.
LOOP_SPANS = ("epoch_train", "stage_lookup", "ring_alloc", "train_window",
              "window_dispatch", "window_drain", "window_host", "obs_emit",
              "tail_step", "tail_dispatch", "tail_fetch", "eval",
              "eval_stage_lookup", "eval_dispatch", "eval_fetch")
SLOW_SPAN_MS = 30.0


def _moe_lines(events) -> list:
    """A decoder's expert layer, per epoch: the rows this chip's experts
    computed against what even routing would have sent here, the rows of
    the dropless buffer they were computed on (the prefix ops/moe.py chose)
    over them, and the tokens its loss was taken on (train/loop.py
    `_tally_extras`)."""
    per = {}
    for e in events:
        if e.get("kind") == "counter" and e.get("name") in (
                "moe_rows_local", "moe_rows_expected", "moe_rows_touched",
                "tokens_masked", "tokens_predicted"):
            row = per.setdefault(e.get("epoch"), {})
            row[e["name"]] = row.get(e["name"], 0) + e.get("inc", 0)
    if not per:
        return []
    lines = ["== moe (per epoch) =="]
    for epoch in sorted(per, key=lambda x: (x is None, x)):
        row = per[epoch]
        rows, exp = row.get("moe_rows_local", 0), \
            row.get("moe_rows_expected", 0)
        share = f"{rows / exp:.4f}" if exp else "n/a"
        touched = row.get("moe_rows_touched", 0)
        over = f"{touched / rows:.3f}" if touched and rows else "n/a"
        # the loss's tokens: a block-diffusion decoder's masked ones, a
        # next-token decoder's predicted ones
        kind = "predicted" if "tokens_predicted" in row else "masked"
        lines.append(f"  epoch {epoch}: rows here {rows:,.0f} of "
                     f"{exp:,.0f} expected (share {share}), buffer rows "
                     f"touched {touched:,.0f} (touched / live {over}), "
                     f"{kind} tokens {row.get('tokens_' + kind, 0):,.0f}")
    lines.append("")
    return lines


def _attn_lines(events) -> list:
    """A decoder's attention kernels, as built (ops/attention.py
    `tile_tally` and its siblings), grouped by the rule they compute (a
    model with two rules emits both): the tiles each visits, those of them
    partly allowed, and the scores it computes over the scores the mask
    allows; whether the layers' checkpoint keeps the kernels' output
    (`attn_residuals_kept`; older runs lack it)."""
    per, kept = {}, None
    for e in events:
        if e.get("kind") != "gauge":
            continue
        if e.get("name") in ("attn_tiles_visited", "attn_tiles_partial",
                             "attn_visited_over_allowed"):
            per.setdefault((e.get("rule"), e.get("kernel")), {})[
                e["name"]] = e["value"]
        elif e.get("name") == "attn_residuals_kept":
            kept = e["value"]
    if not per:
        return []
    lines = ["== attention tiles (per rule and kernel) =="]
    for (rule, kernel), row in per.items():
        lines.append(
            f"  {rule or '':<9} {kernel:<4} visits "
            f"{row.get('attn_tiles_visited', 0):,} "
            f"tiles, {row.get('attn_tiles_partial', 0):,} partly allowed; "
            f"visited / allowed scores "
            f"{row.get('attn_visited_over_allowed', 0.0):.3f}")
    if kept is not None:
        lines.append(
            "  the kernels' output and logsumexp kept across the layers' "
            "checkpoint: " + ("yes (the backward pass does not run the "
                              "forward kernel again)" if kept else "no"))
    lines.append("")
    return lines


def _gdn_lines(events) -> list:
    """A hybrid decoder's linear-attention layers, as built (ops/gdn.py):
    which form of the gated delta rule runs, the chunk it is computed in
    and the chunks the state is carried through a sequence; the diagonal
    block a chunk's inversion starts from (`gdn_inverse_block`) and which
    form of the front runs before it (`gdn_front_kernel`), each where the
    run recorded it."""
    g = {e["name"]: e["value"] for e in events if e.get("kind") == "gauge"
         and e.get("name") in ("gdn_kernel", "gdn_chunk",
                               "gdn_chunks_per_sequence", "gdn_inverse_block",
                               "gdn_front_kernel")}
    if not g:
        return []
    form = lambda on: "the Pallas kernels" if on else "jax.numpy"
    lines = ["== linear attention ==",
             f"  the gated delta rule ({form(g.get('gdn_kernel'))}) in "
             f"chunks of {g.get('gdn_chunk', 0):,} positions, "
             f"{g.get('gdn_chunks_per_sequence', 0):,} a sequence"]
    if "gdn_inverse_block" in g:
        lines.append("  a chunk's triangular system inverted from diagonal "
                     f"blocks of {g['gdn_inverse_block']:,} positions "
                     "(substitution), then by doubling")
    if "gdn_front_kernel" in g:
        lines.append("  the front (convolution, silu, q / k norms): "
                     + form(g["gdn_front_kernel"]))
    return lines + [""]


def _window_lines(events) -> list:
    """A window-and-full decoder's sliding layers, as built (ops/attention.py
    `window_attention`): the form that runs and the keys a query sees."""
    g = {e["name"]: e["value"] for e in events if e.get("kind") == "gauge"
         and e.get("name") in ("window_kernel", "attn_window")}
    if not g:
        return []
    form = "the Pallas kernels" if g.get("window_kernel") else "jax.numpy"
    return ["== window attention ==",
            f"  sliding-window attention ({form}): a query sees the "
            f"{g.get('attn_window', 0):,} keys that end at its own position",
            ""]


def _mla_lines(events) -> list:
    """A latent-attention decoder's attention, as built (ops/mla.py): the
    form that runs and the key and value sizes it runs at."""
    g = {e["name"]: e["value"] for e in events if e.get("kind") == "gauge"
         and e.get("name") in ("mla_kernel", "mla_qk_dim", "mla_v_dim")}
    if not g:
        return []
    form = "the Pallas kernels" if g.get("mla_kernel") else "jax.numpy"
    return ["== latent attention ==",
            f"  causal attention ({form}), a key/value head a query head: "
            f"keys of {g.get('mla_qk_dim', 0):,}, values of "
            f"{g.get('mla_v_dim', 0):,}",
            ""]


def _mhc_lines(events) -> list:
    """A hyper-connected residual, as built (ops/hyper.py), and how far
    from doubly stochastic the worst H_res of any step was (the step
    events' `mhc_res_gap`, a maximum)."""
    g = {e["name"]: e["value"] for e in events if e.get("kind") == "gauge"
         and e.get("name") in ("mhc_streams", "mhc_sinkhorn_iters",
                               "mhc_kernel", "mhc_tile")}
    if not g:
        return []
    gaps = [e["mhc_res_gap"] for e in events
            if e.get("kind") == "step" and "mhc_res_gap" in e]
    worst = f"{max(gaps):.3g} over {len(gaps):,} steps" if gaps else "n/a"
    form = (f"the Pallas kernels on tiles of {g.get('mhc_tile', 0):,} "
            "positions" if g.get("mhc_kernel") else "jax.numpy")
    return ["== hyper-connections ==",
            f"  {g.get('mhc_streams', 0):,} streams ({form}), "
            f"{g.get('mhc_sinkhorn_iters', 0):,} Sinkhorn iterations a "
            f"position; largest |row or column sum - 1| of H_res {worst}",
            ""]


def _loop_lines(events) -> list:
    """Dispatch-loop rendering: per span name of the default windowed path
    its count, median, longest and total per epoch, then every span that
    ran ``SLOW_SPAN_MS`` or more over its name's median (a host stall shows
    as one slow ``window_host`` or ``*_fetch``, not as a slower median).
    Returns [] for runs without these spans (host-fed, per-step, serving,
    runs recorded before the spans existed)."""
    by_name, name_of = {}, {}
    for e in events:
        if e.get("kind") != "span":
            continue
        name_of[e.get("id")] = e.get("name")
        if e.get("name") in LOOP_SPANS and "dur_ns" in e:
            by_name.setdefault(e["name"], []).append(e)
    if not by_name:
        return []
    epochs = {e.get("epoch") for e in by_name.get("epoch_train", ())} \
        or {e.get("epoch") for spans in by_name.values() for e in spans}
    lines = [f"== loop (dispatch-loop spans, {len(epochs)} epoch(s)) ==",
             f"  {'span':<18} {'count':>6} {'p50':>12} {'max':>12} "
             f"{'total/epoch':>14}"]
    slow = []
    for name in LOOP_SPANS:
        spans = by_name.get(name)
        if not spans:
            continue
        ms = [e["dur_ns"] / 1e6 for e in spans]
        p50 = percentile(ms, 50)
        lines.append(f"  {name:<18} {len(ms):>6} {p50:>9.3f} ms "
                     f"{max(ms):>9.3f} ms {sum(ms) / len(epochs):>11.3f} ms")
        slow += [(e, p50) for e in spans
                 if e["dur_ns"] / 1e6 - p50 >= SLOW_SPAN_MS]
    for e, p50 in sorted(slow, key=lambda x: x[0].get("t_ns", 0)):
        lines.append(f"  slow: {e['name']} {e['dur_ns'] / 1e6:.3f} ms "
                     f"(+{e['dur_ns'] / 1e6 - p50:.3f} over its median) "
                     f"epoch {e.get('epoch')} "
                     f"parent {name_of.get(e.get('parent_id'))}")
    lines.append("")
    return lines


def _alert_lines(events) -> list:
    """Alert-engine rendering (round 12, ``obs/alerts.py``): structured
    ``kind: alert`` events grouped by deterministic rule id.  Returns []
    for runs with no alerts — quiet runs render unchanged."""
    by_rule = {}
    for e in events:
        if e.get("kind") != "alert":
            continue
        agg = by_rule.setdefault(e.get("rule", "?"), {
            "count": 0, "severity": e.get("severity", "?"),
            "first_t": e.get("t")})
        agg["count"] += 1
        agg["last_t"] = e.get("t")
    if not by_rule:
        return []
    lines = ["== alerts =="]
    for rule, agg in sorted(by_rule.items()):
        span_s = (agg["last_t"] or 0) - (agg["first_t"] or 0)
        lines.append(f"  {rule:<14} [{agg['severity']}]  x{agg['count']:<5}"
                     f" over {span_s:.1f} s")
    lines.append("")
    return lines


def render(out_dir: str) -> str:
    manifest, events, summary = read_run(out_dir)
    # A preempted/killed run legitimately truncates the final event line;
    # count and surface it rather than failing the report (the report may
    # be the only diagnostic artifact such a run leaves).
    _, n_bad = read_events_jsonl(
        os.path.join(out_dir, "events.jsonl"),
        warn=lambda msg: print(f"warning: {msg}", file=sys.stderr))
    if summary is None:
        # Interrupted run: recompute from the raw events so a partial run
        # still renders (the report may be the only diagnostic artifact).
        gb = (manifest or {}).get("global_batch")
        summary = summarize_events(events, global_batch=gb)
    lines = [f"telemetry run: {out_dir}", ""]
    if n_bad:
        lines.append(f"  !! {n_bad} undecodable event line(s) skipped "
                     f"(run killed mid-write?)")
        lines.append("")

    if manifest:
        lines.append("== run manifest ==")
        order = ["model", "strategy", "world_size", "global_batch",
                 "precision", "augment", "host_augment", "jax_version",
                 "backend", "device_kind", "git_sha"]
        for k in order:
            if k in manifest:
                lines.append(f"  {k:<22} {manifest[k]}")
        native = manifest.get("native_loader")
        if native is not None:
            status = "available" if native.get("available") else \
                f"UNAVAILABLE ({native.get('error')})"
            lines.append(f"  {'native_loader':<22} {status}")
        lines.append("")

    lines.append("== steady-state steps ==")
    lines.append(f"  steps recorded         {summary.get('num_steps', 0)} "
                 f"({summary.get('num_steady_steps', 0)} steady)")
    st = summary.get("steady_step_time_s")
    if st:
        for q in ("p50", "p95", "p99", "mean", "min", "max"):
            lines.append(f"  step time {q:<12} {_fmt_ms(st[q])}")
    ips = summary.get("steady_images_per_sec")
    if ips:
        lines.append(f"  images/sec             {ips:,.0f}")
    if "final_loss" in summary:
        lines.append(f"  final loss             {summary['final_loss']:.4f}")
    lines.append("")

    if summary.get("spans"):
        lines.append("== spans (total wall clock) ==")
        for name, agg in sorted(summary["spans"].items(),
                                key=lambda kv: -kv[1]["total_s"]):
            lines.append(f"  {name:<22} x{agg['count']:<5} "
                         f"{_fmt_ms(agg['total_s'])}")
        lines.append("")

    if summary.get("counters"):
        lines.append("== counters (final) ==")
        for name, total in sorted(summary["counters"].items()):
            lines.append(f"  {name:<34} {total}")
        lines.append("")

    lines.extend(_loop_lines(events))
    lines.extend(_moe_lines(events))
    lines.extend(_attn_lines(events))
    lines.extend(_gdn_lines(events))
    lines.extend(_window_lines(events))
    lines.extend(_mla_lines(events))
    lines.extend(_mhc_lines(events))
    lines.extend(_wire_ext_lines(events))

    lines.extend(_serving_lines(events))
    lines.extend(_elastic_lines(events, manifest))
    lines.extend(_audit_lines(manifest))
    lines.extend(_attribution_lines(manifest))
    lines.extend(_memory_lines(events, manifest))
    lines.extend(_trace_lines(events))
    lines.extend(_slo_lines(events))
    lines.extend(_publish_lines(events))
    lines.extend(_pipeline_lines(events))
    lines.extend(_waterfall_lines(out_dir, events))
    lines.extend(_alert_lines(events))

    gauges = {}
    for e in events:
        if e.get("kind") == "gauge":
            gauges[e["name"]] = e["value"]   # last write wins
    if gauges:
        lines.append("== gauges (last value) ==")
        for name, value in sorted(gauges.items()):
            lines.append(f"  {name:<22} {value}")
        lines.append("")

    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="render a --telemetry-out run directory")
    p.add_argument("run_dir", help="directory holding manifest.json / "
                                   "events.jsonl / summary.json")
    p.add_argument("--json", action="store_true",
                   help="emit the (re)computed summary as JSON instead of "
                        "the human table")
    args = p.parse_args(argv)
    if not os.path.isdir(args.run_dir):
        p.error(f"not a directory: {args.run_dir}")
    if args.json:
        manifest, events, summary = read_run(args.run_dir)
        if summary is None:
            summary = summarize_events(
                events, global_batch=(manifest or {}).get("global_batch"))
        print(json.dumps(summary, indent=2))
    else:
        print(render(args.run_dir))
    return 0


if __name__ == "__main__":
    sys.exit(main())
