"""Rule engine certifying every compiled program's cost shape.

The paper's subject IS the cost structure of each gradient-sync tier —
gather→scatter pays two chained collectives per leaf with world-x traffic,
per-param all-reduce one per leaf, bucketed DDP one per ~25 MB bucket —
and until now that structure was only *reported*
(``tools/bench_strategy_spectrum.py``), never *checked*.  This module
audits the pre-optimization HLO (via the
:mod:`analysis.hlo_ir` graph IR) plus the jaxpr of each shipped program
against a declared :class:`ProgramContract`, so a regression in comms
shape, precision, buffer donation or host syncs fails CI before any
hardware run.

Rules (each one catches a deliberately seeded violation in
tests/test_analysis.py):

- ``collective-contract`` — per-strategy count / byte / chain-depth
  certification: ``single`` (and every world-1 or serving program)
  lowers zero collectives; ``gather`` >= nleaves all-gathers with
  world-amplified result bytes and a 2-per-leaf chain; ``allreduce``
  >= nleaves all-reduces chained >= nleaves deep; ``ddp`` all-reduces
  chained exactly per-bucket — STRICTLY shallower than per-param when
  there are fewer buckets than leaves (the DDP fusion win, Li et al.,
  VLDB 2020).  The ``overlap`` tier keeps ddp's bucket count but must
  lower a chain depth of exactly 1 (no collective consumes another's
  result — the single post-backward chain is what defeats XLA's
  latency-hiding scheduler) and at least one bucket's operand cone must
  exclude part of the backward (``stats.collective_dot_cones``).  The
  compressed tiers (``compress-bf16`` / ``compress-int8`` /
  ``powersgd``) must keep their gradient wire bytes under
  ``param_bytes / compress_ratio`` (+ declared ``aux_bytes`` for BN
  pmeans, loss psums and the int8 shared-scale pmax): >= 2x / 4x /
  rank-r low-rank reduction vs the per-param f32 floor, certified on
  the lowering, not the docs.  The cross-strategy depth ladder
  (ddp < allreduce < gather) is certified whenever several strategies
  are audited together.
- ``dtype-leak`` — no f32/f64 ``dot``/``convolution`` in a
  bf16-declared program (a silent promotion doubles MXU cost).
- ``donation`` — programs declared to donate the train state must
  donate >= n_state_leaves entry buffers (``buffer_donor`` /
  ``input_output_alias`` module header); a miss doubles peak HBM.
  Donation is additionally proven as an ALIASED-BYTES equality
  (:func:`memlife.donation_alias_findings`): every donated entry
  buffer must have a same-size output leaf to alias, or XLA quietly
  copies and the in-place update is fiction.
- ``peak-memory`` — the static buffer-liveness bound
  (:func:`memlife.mem_report`) must fit the contract's
  ``hbm_budget_bytes`` (default: the single-sourced v5e chip capacity,
  :data:`costmodel.V5E_HBM_CAPACITY_BYTES`).  The fattest live set is
  named in the finding, so an over-budget program says WHAT to shrink.
- ``host-sync`` — no infeed/outfeed/send/recv or host-callback
  custom-calls inside ``while`` bodies (HLO side), and no callback
  primitives inside ``scan``/``while`` sub-jaxprs (jaxpr side): a host
  round-trip per scanned step serializes the window pipeline.
- ``baked-constants`` — no single constant larger than the contract's
  ``max_constant_bytes`` baked into the executable (weights and data
  must arrive as arguments, not literals).
- ``ingest-edge`` — programs declaring ``u8_edge`` (the serving ladder's
  fused-ingest rungs) must take the raw uint8 image bytes as an entry
  parameter and convert them to float IN-program: a float image-shaped
  entry parameter means the normalize leaked back to the host (one
  full-size f32 copy per request), and a missing u8->float convert
  means the program isn't consuming the wire bytes it claims to.

Waiver syntax (CLI ``--audit-waive``, tests): ``RULE`` waives a
rule everywhere, ``RULE@GLOB`` only for programs matching the fnmatch
glob, e.g. ``baked-constants@serve/*``.  Waived findings are still
reported and recorded in the telemetry manifest, they just don't fail
``--audit strict``.
"""

from __future__ import annotations

import fnmatch
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import costmodel, hlo_ir, memlife, stats

DEFAULT_MAX_CONSTANT_BYTES = 1 << 20     # 1 MiB: far above any mask/iota
                                         # table, far below weights/data

_HOST_SYNC_OPS = frozenset(
    {"infeed", "outfeed", "send", "recv", "send-done", "recv-done"})
_CALLBACK_TARGET_RE = re.compile(r"callback|host", re.IGNORECASE)
_LOOP_PRIMITIVES = frozenset({"while", "scan"})


@dataclass(frozen=True)
class Finding:
    rule: str
    program: str
    message: str


@dataclass
class ProgramContract:
    """What a program's lowering is REQUIRED to look like."""
    name: str
    strategy: Optional[str] = None       # single/gather/allreduce/ddp/
                                         # overlap/compress-*/powersgd/eval;
                                         # None = no collectives expected
    world: int = 1
    nleaves: int = 0                     # parameter (grad) leaves
    nbuckets: int = 0                    # ddp bucket count
    param_bytes: int = 0                 # total parameter bytes (f32 master)
    n_state_leaves: int = 0              # TrainState leaves (donation floor)
    donates_state: bool = False
    precision: str = "f32"
    max_constant_bytes: int = DEFAULT_MAX_CONSTANT_BYTES
    compress_ratio: float = 1.0          # required param_bytes / grad wire
    aux_bytes: int = 0                   # non-gradient collective allowance
                                         # (BN pmean, loss psum, int8 pmax)
    u8_edge: bool = False                # fused-ingest contract: uint8
                                         # images at the program edge,
                                         # normalize in-program
    hbm_budget_bytes: int = 0            # static peak-HBM budget; 0 =
                                         # the v5e chip capacity


@dataclass
class AuditReport:
    program: str
    findings: List[Finding] = field(default_factory=list)
    waived: List[Finding] = field(default_factory=list)
    rules: Dict[str, str] = field(default_factory=dict)  # rule -> pass/fail/waived
    stats: Dict = field(default_factory=dict)            # collective shape record

    @property
    def passed(self) -> bool:
        return not self.findings


def _waived(finding: Finding, waivers: Sequence[str]) -> bool:
    for w in waivers:
        rule, _, prog_glob = w.partition("@")
        if rule != finding.rule:
            continue
        if not prog_glob or fnmatch.fnmatch(finding.program, prog_glob):
            return True
    return False


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

def _rule_collective_contract(module: hlo_ir.Module, jaxpr,
                              c: ProgramContract) -> List[Finding]:
    s = stats.collective_stats(module)
    by = stats.collective_bytes(module)
    depth = stats.collective_chain_depth(module)
    counts = {op: e["count"] for op, e in s["ops"].items()}
    total = s["total_count"]
    out: List[Finding] = []

    def bad(msg: str) -> None:
        out.append(Finding("collective-contract", c.name, msg))

    if c.strategy is None or c.strategy == "single":
        if total:
            bad(f"expected a collective-free program, found {counts} "
                f"(chain depth {depth})")
        return out
    if c.world <= 1:
        # A grad-sync strategy degraded to a one-chip world (the elastic
        # single-rank fallback) keeps its psums; over a single replica
        # they are no-ops, not contract violations.
        return out

    ar = counts.get("all-reduce", 0)
    ag = counts.get("all-gather", 0)
    others = {op: n for op, n in counts.items()
              if op not in ("all-reduce", "all-gather")}

    if c.strategy == "eval":
        if ag or others:
            bad(f"eval must reduce only (all-reduce); found {counts}")
        if ar < 1:
            bad("eval on a multi-device mesh must psum its counts; "
                "found no all-reduce")
        if depth > 2:
            bad(f"eval collective chain depth {depth} > 2: eval reductions "
                f"must not serialize")
        return out

    if c.strategy == "gather":
        if ag < c.nleaves:
            bad(f"gather tier must all-gather every grad leaf: "
                f"{ag} all-gather < {c.nleaves} leaves")
        if ar < 1:
            bad("gather tier reduces gathered grads; found no all-reduce")
        if depth < 2 * c.nleaves:
            bad(f"gather tier chains two collectives per leaf: depth "
                f"{depth} < {2 * c.nleaves}")
        want = c.world * c.param_bytes
        if c.param_bytes and by.get("all-gather", 0) < want:
            bad(f"gather traffic amplification missing: all-gather result "
                f"bytes {by.get('all-gather', 0)} < world x params = {want}")
        return out

    if c.strategy == "allreduce":
        if ag or others:
            bad(f"per-param all-reduce tier must emit only all-reduce; "
                f"found {counts}")
        if ar < c.nleaves:
            bad(f"per-param tier reduces every leaf: {ar} all-reduce < "
                f"{c.nleaves} leaves")
        if depth < c.nleaves:
            bad(f"per-param tier chains one collective per leaf: depth "
                f"{depth} < {c.nleaves}")
        if c.param_bytes and by.get("all-reduce", 0) < c.param_bytes:
            bad(f"all-reduce result bytes {by.get('all-reduce', 0)} < "
                f"total param bytes {c.param_bytes}")
        return out

    if c.strategy == "ddp":
        if ag or others:
            bad(f"ddp tier must emit only all-reduce; found {counts}")
        if ar < c.nbuckets:
            bad(f"ddp tier reduces every bucket: {ar} all-reduce < "
                f"{c.nbuckets} buckets")
        if depth < c.nbuckets:
            bad(f"ddp chain depth {depth} < {c.nbuckets} buckets")
        if c.nleaves > c.nbuckets and depth >= c.nleaves:
            bad(f"ddp fusion win lost: chain depth {depth} >= {c.nleaves} "
                f"leaves — bucketed reduces are serializing per leaf")
        if c.param_bytes and by.get("all-reduce", 0) < c.param_bytes:
            bad(f"all-reduce result bytes {by.get('all-reduce', 0)} < "
                f"total param bytes {c.param_bytes}")
        return out

    if c.strategy == "overlap":
        if ag or others:
            bad(f"overlapped tier must emit only all-reduce; found {counts}")
        if ar < c.nbuckets:
            bad(f"overlapped tier reduces every bucket: {ar} all-reduce < "
                f"{c.nbuckets} buckets")
        if depth > 1:
            bad(f"overlapped tier must not chain collectives: chain depth "
                f"{depth} > 1 — a single post-backward chain pins every "
                f"bucket behind the full backward and defeats latency "
                f"hiding")
        cones = stats.collective_dot_cones(module)
        if cones["total_dots"] and cones["min_cone"] >= cones["total_dots"]:
            bad(f"every collective's operand cone spans all "
                f"{cones['total_dots']} dots — no bucket reduce can be "
                f"issued before the backward completes")
        if c.param_bytes and by.get("all-reduce", 0) < c.param_bytes:
            bad(f"all-reduce result bytes {by.get('all-reduce', 0)} < "
                f"total param bytes {c.param_bytes}")
        return out

    if c.strategy in ("compress-bf16", "compress-int8", "powersgd"):
        if ag or others:
            bad(f"compressed tier must emit only all-reduce; found {counts}")
        if ar < c.nleaves:
            bad(f"compressed tier reduces every leaf: {ar} all-reduce < "
                f"{c.nleaves} leaves")
        wire = by.get("all-reduce", 0)
        if wire <= 0:
            bad("compressed tier lowered no all-reduce bytes")
        if c.param_bytes:
            grad_wire = max(0, wire - c.aux_bytes)
            ceiling = c.param_bytes / c.compress_ratio
            if grad_wire > ceiling:
                bad(f"compression is not real: gradient wire bytes "
                    f"{grad_wire} (total all-reduce {wire} - aux "
                    f"{c.aux_bytes}) exceed param_bytes / "
                    f"{c.compress_ratio:g}x = {ceiling:.0f}")
        return out

    bad(f"unknown strategy {c.strategy!r} in contract")
    return out


def _result_dtype(ins: hlo_ir.Instruction) -> Optional[str]:
    m = stats._SHAPE_RE.search(ins.result_type)
    return m.group(1) if m else None


def _rule_dtype_leak(module: hlo_ir.Module, jaxpr,
                     c: ProgramContract) -> List[Finding]:
    if c.precision != "bf16":
        return []
    out = []
    for ins in module.instructions():
        if ins.opcode in ("dot", "convolution") and \
                _result_dtype(ins) in ("f32", "f64"):
            out.append(Finding(
                "dtype-leak", c.name,
                f"{_result_dtype(ins)} {ins.opcode} {ins.name!r} in a "
                f"bf16-declared program (silent promotion doubles MXU "
                f"cost): {ins.result_type}"))
    return out


def _rule_donation(module: hlo_ir.Module, jaxpr,
                   c: ProgramContract) -> List[Finding]:
    out: List[Finding] = []
    # Aliased-bytes round-trip: whatever IS donated must be provably
    # aliasable, declared or not.
    for msg in memlife.donation_alias_findings(module, c.name):
        out.append(Finding("donation", c.name, msg))
    if not c.donates_state:
        return out
    n = module.donated_param_count()
    if n < c.n_state_leaves:
        out.append(Finding(
            "donation", c.name,
            f"declared to donate the train state but only {n} of >= "
            f"{c.n_state_leaves} entry buffers are donated "
            f"(buffer_donor/input_output_alias) — un-donated state "
            f"doubles peak HBM"))
    return out


def _rule_peak_memory(module: hlo_ir.Module, jaxpr,
                      c: ProgramContract) -> List[Finding]:
    budget = c.hbm_budget_bytes or costmodel.V5E_HBM_CAPACITY_BYTES
    rep = memlife.mem_report(module, c.name)
    if rep.peak_bytes <= budget:
        return []
    top = rep.top_sets[0] if rep.top_sets else {}
    fattest = ", ".join(
        f"{n}={b}" for n, b in top.get("members", [])[:4])
    return [Finding(
        "peak-memory", c.name,
        f"static peak HBM {rep.peak_bytes} B "
        f"({rep.peak_bytes / 2**20:.1f} MiB) exceeds the "
        f"{budget} B budget; fattest live set at "
        f"{top.get('instruction', '?')!r}: {fattest}")]


def _while_reachable(module: hlo_ir.Module) -> set:
    """Names of computations reachable from any ``while`` body/condition."""
    seeds = []
    for ins in module.instructions():
        if ins.opcode == "while":
            seeds.extend(ins.called)
    seen = set()
    stack = list(seeds)
    while stack:
        name = stack.pop()
        if name in seen or name not in module.computations:
            continue
        seen.add(name)
        for ins in module.computations[name].instructions.values():
            stack.extend(ins.called)
    return seen


def _jaxpr_host_syncs(jaxpr, in_loop: bool = False) -> List[str]:
    hits: List[str] = []
    for eqn in getattr(jaxpr, "eqns", ()):
        prim = eqn.primitive.name
        inner_loop = in_loop or prim in _LOOP_PRIMITIVES
        if in_loop and ("callback" in prim or prim in ("infeed", "outfeed")):
            hits.append(prim)
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    hits.extend(_jaxpr_host_syncs(sub, inner_loop))
    return hits


def _rule_host_sync(module: hlo_ir.Module, jaxpr,
                    c: ProgramContract) -> List[Finding]:
    out: List[Finding] = []
    loop_comps = _while_reachable(module)
    for cname in loop_comps:
        for ins in module.computations[cname].instructions.values():
            target = ins.attr("custom_call_target") or ""
            if ins.opcode in _HOST_SYNC_OPS or (
                    ins.opcode == "custom-call"
                    and _CALLBACK_TARGET_RE.search(target)):
                out.append(Finding(
                    "host-sync", c.name,
                    f"host sync {ins.opcode} {ins.name!r}"
                    f"{' -> ' + target if target else ''} inside loop "
                    f"computation {cname!r}: one host round-trip per "
                    f"scanned step serializes the window"))
    if jaxpr is not None:
        for prim in _jaxpr_host_syncs(getattr(jaxpr, "jaxpr", jaxpr)):
            out.append(Finding(
                "host-sync", c.name,
                f"callback primitive {prim!r} inside a scan/while body "
                f"(jaxpr)"))
    return out


def _rule_baked_constants(module: hlo_ir.Module, jaxpr,
                          c: ProgramContract) -> List[Finding]:
    out = []
    for ins in module.instructions():
        if ins.opcode != "constant":
            continue
        b = stats.bytes_of_type(ins.result_type)
        if b > c.max_constant_bytes:
            out.append(Finding(
                "baked-constants", c.name,
                f"constant {ins.name!r} bakes {b} bytes "
                f"({ins.result_type}) into the executable "
                f"(> {c.max_constant_bytes}); pass it as an argument"))
    return out


_IMG_SHAPE_RE = re.compile(r"\b(u8|f16|bf16|f32|f64)\[\d+,32,32,3\]")
_FLOAT_DTYPES = ("f16", "bf16", "f32", "f64")


def _rule_ingest_edge(module: hlo_ir.Module, jaxpr,
                      c: ProgramContract) -> List[Finding]:
    if not c.u8_edge:
        return []
    out: List[Finding] = []
    entry = module.entry_computation
    if entry is None:
        return [Finding("ingest-edge", c.name,
                        "program has no entry computation to certify")]
    u8_img = False
    for ins in entry.instructions.values():
        if ins.opcode != "parameter":
            continue
        m = _IMG_SHAPE_RE.search(ins.result_type)
        if m is None:
            continue
        if m.group(1) == "u8":
            u8_img = True
        else:
            out.append(Finding(
                "ingest-edge", c.name,
                f"{m.group(1)} image-shaped entry parameter {ins.name!r} "
                f"({ins.result_type}): the wire-to-device path must stay "
                f"uint8 — a float image input means the normalize left "
                f"the program and the host pays a 4x transfer"))
    if not u8_img:
        out.append(Finding(
            "ingest-edge", c.name,
            "no uint8 image-shaped entry parameter: a fused-ingest rung "
            "must take the raw u8 wire bytes at the program edge"))
        return out
    types = {ins.name: ins.result_type for ins in module.instructions()}
    converted = any(
        ins.opcode == "convert"
        and _result_dtype(ins) in _FLOAT_DTYPES
        and any(types.get(op, "").lstrip().startswith("u8[")
                for op in ins.operands)
        for ins in module.instructions())
    if not converted:
        out.append(Finding(
            "ingest-edge", c.name,
            "no in-program u8 -> float convert: the program takes uint8 "
            "images but never normalizes them on device"))
    return out


RULES = {
    "collective-contract": _rule_collective_contract,
    "dtype-leak": _rule_dtype_leak,
    "donation": _rule_donation,
    "host-sync": _rule_host_sync,
    "baked-constants": _rule_baked_constants,
    "ingest-edge": _rule_ingest_edge,
    "peak-memory": _rule_peak_memory,
}


def audit_program(hlo_text: str, contract: ProgramContract, jaxpr=None,
                  waive: Sequence[str] = ()) -> AuditReport:
    """Run every rule over one program's lowering (+ optional jaxpr)."""
    module = hlo_ir.parse(hlo_text)
    report = AuditReport(program=contract.name)
    s = stats.collective_stats(module)
    report.stats = {
        "collectives": {op: e["count"] for op, e in s["ops"].items()},
        "result_bytes": stats.collective_bytes(module),
        "chain_depth": stats.collective_chain_depth(module),
        "donated": module.donated_param_count(),
        "peak_mib": round(
            memlife.mem_report(module, contract.name).peak_bytes / 2**20,
            3),
    }
    for rule, fn in RULES.items():
        findings = fn(module, jaxpr, contract)
        kept = [f for f in findings if not _waived(f, waive)]
        dropped = [f for f in findings if _waived(f, waive)]
        report.findings.extend(kept)
        report.waived.extend(dropped)
        report.rules[rule] = ("fail" if kept else
                              "waived" if dropped else "pass")
    return report


# ---------------------------------------------------------------------------
# The program zoo: every shipped program, lowered and audited
# ---------------------------------------------------------------------------

@dataclass
class AuditResult:
    reports: List[AuditReport] = field(default_factory=list)
    ladder: Dict = field(default_factory=dict)
    ladder_findings: List[Finding] = field(default_factory=list)
    # Program name -> pre-optimization HLO text, kept only when the caller
    # asks (``collect_hlo``) — the attribution pipeline (analysis/costmodel)
    # re-walks the same lowerings the audit certified.
    hlo: Dict[str, str] = field(default_factory=dict)

    @property
    def clean(self) -> bool:
        return (not self.ladder_findings
                and all(r.passed for r in self.reports))

    def findings(self) -> List[Finding]:
        out = [f for r in self.reports for f in r.findings]
        out.extend(self.ladder_findings)
        return out

    def waived(self) -> List[Finding]:
        return [f for r in self.reports for f in r.waived]

    def summary(self) -> Dict:
        """Manifest-ready record: per-program rule pass/fail +
        waivers, the strategy depth ladder, and every finding message."""
        return {
            "clean": self.clean,
            "n_programs": len(self.reports),
            "n_findings": len(self.findings()),
            "n_waived": len(self.waived()),
            "programs": {
                r.program: {"rules": r.rules, **r.stats}
                for r in self.reports},
            "findings": [
                {"rule": f.rule, "program": f.program,
                 "message": f.message[:300]}
                for f in self.findings()],
            "waived": [
                {"rule": f.rule, "program": f.program,
                 "message": f.message[:300]}
                for f in self.waived()],
            **({"ladder": self.ladder} if self.ladder else {}),
        }

    def format_lines(self) -> List[str]:
        lines = []
        for r in self.reports:
            mark = "PASS" if r.passed else "FAIL"
            extra = f"  waived={len(r.waived)}" if r.waived else ""
            lines.append(f"[audit] {mark} {r.program}  "
                         f"collectives={r.stats.get('collectives', {})} "
                         f"depth={r.stats.get('chain_depth')}{extra}")
            for f in r.findings + r.waived:
                tag = "waived " if f in r.waived else ""
                lines.append(f"[audit]   {tag}{f.rule}: {f.message}")
        for f in self.ladder_findings:
            lines.append(f"[audit] FAIL {f.program} {f.rule}: {f.message}")
        if self.ladder:
            lines.append(f"[audit] strategy depth ladder: {self.ladder}")
        lines.append(f"[audit] {'CLEAN' if self.clean else 'DIRTY'}: "
                     f"{len(self.reports)} programs, "
                     f"{len(self.findings())} findings, "
                     f"{len(self.waived())} waived")
        return lines


def _certify_ladder(depths: Dict[str, int], nleaves: int, nbuckets: int,
                    program: str) -> Tuple[Dict, List[Finding]]:
    """Cross-strategy certification: the paper's ordering of chain depths
    (bucketed ddp < per-param allreduce < chained gather) must hold on
    the lowered programs themselves whenever several tiers are audited
    together on a multi-device mesh."""
    ladder = dict(depths)
    findings: List[Finding] = []

    def bad(msg):
        findings.append(Finding("collective-contract", program, msg))

    if "allreduce" in depths and "gather" in depths:
        if not depths["gather"] > depths["allreduce"]:
            bad(f"gather depth {depths['gather']} must exceed allreduce "
                f"depth {depths['allreduce']} (two chained collectives "
                f"per leaf vs one)")
    if "allreduce" in depths and "ddp" in depths and nleaves > nbuckets:
        if not depths["ddp"] < depths["allreduce"]:
            bad(f"ddp depth {depths['ddp']} must be shallower than "
                f"allreduce depth {depths['allreduce']} with {nbuckets} "
                f"buckets over {nleaves} leaves")
    return ladder, findings


def _train_sds(mesh, state_sds, global_batch: int, window: int,
               ring_capacity: int = 0):
    """ShapeDtypeStructs for the train step/window/eval signatures on
    ``mesh`` (mirrors the Trainer's staging shapes).  ``ring_capacity``
    > 0 adds the metric-ring pair (obs/ringbuf.py) the ring-carrying
    window variants take as their donated second argument."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("data"))
    epoch = NamedSharding(mesh, P(None, "data"))

    def share(sds, sharding):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sharding)

    state = jax.tree_util.tree_map(lambda s: share(s, rep), state_sds)
    comm = getattr(state.opt_state, "comm", None)
    if comm is not None:
        # Compression carry-state (error-feedback residuals / PowerSGD
        # factors) is stacked (world, ...) and lives row-sharded so each
        # worker owns its slice — mirror the Trainer's placement.
        state = state._replace(opt_state=state.opt_state._replace(
            comm=jax.tree_util.tree_map(lambda s: share(s, row), comm)))
    ring = None
    if ring_capacity:
        from ..obs import ringbuf
        ring = (jax.ShapeDtypeStruct((ring_capacity, ringbuf.N_METRICS),
                                     jnp.float32, sharding=rep),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    b, w = global_batch, window
    return {
        "state": state,
        "ring": ring,
        "key": jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        "images": jax.ShapeDtypeStruct((b, 32, 32, 3), jnp.uint8,
                                       sharding=row),
        "labels": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=row),
        "epoch_images": jax.ShapeDtypeStruct((w, b, 32, 32, 3), jnp.uint8,
                                             sharding=epoch),
        "epoch_labels": jax.ShapeDtypeStruct((w, b), jnp.int32,
                                             sharding=epoch),
        "start": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        "lengths": jax.ShapeDtypeStruct((w,), jnp.int8, sharding=rep),
    }


def _hlo_text(lowered) -> str:
    return lowered.compiler_ir(dialect="hlo").as_hlo_text()


def audit_zoo(*, model: str = "vgg11", global_batch: int = 256,
              window: int = 4, precision: str = "f32",
              strategies: Sequence[str] = ("single", "gather",
                                           "allreduce", "ddp", "overlap",
                                           "compress-bf16", "compress-int8",
                                           "powersgd"),
              paths: Sequence[str] = ("step", "window", "host_window"),
              include_eval: bool = True,
              serve_buckets: Sequence[int] = (),
              serve_precision: Optional[str] = None,
              serve_swap_recert: bool = False,
              num_devices: Optional[int] = None,
              waive: Sequence[str] = (),
              max_constant_bytes: int = DEFAULT_MAX_CONSTANT_BYTES,
              metrics_ring: bool = True,
              collect_hlo: bool = False,
              hbm_budget_bytes: int = 0,
              ) -> AuditResult:
    """Lower and audit the shipped program zoo: the 3 train paths for
    each strategy, the eval window, and (when ``serve_buckets`` is
    non-empty) the serving executable ladder.

    ``metrics_ring`` (default on, matching the Trainer) lowers the
    windowed paths in their ring-carrying form — the programs the Trainer
    actually dispatches — so the donation floor rises by the 2 ring
    buffers and the host-sync rule certifies that the per-step ring
    writes stay pure dynamic-update-slices (no host round-trip inside
    the scanned body).  ``collect_hlo`` keeps every program's lowering
    text on the result (``AuditResult.hlo``) for cost-model attribution.

    Lowering is ABSTRACT end to end — train state shapes come from
    ``jax.eval_shape`` so no parameters are materialized; only the
    serving entries (which reuse :class:`serve.InferenceEngine`)
    initialize real weights.
    """
    import jax

    from ..models import get_model
    from ..obs import ringbuf
    from ..ops import sgd
    from ..parallel import get_strategy, mesh as meshlib
    from ..parallel.bucketing import DEFAULT_BUCKET_BYTES, make_plan
    from ..train import step as steplib

    import jax.numpy as jnp

    compute_dtype = jnp.bfloat16 if precision == "bf16" else None
    init_fn, apply_fn = get_model(model)
    state_sds = jax.eval_shape(
        lambda k: steplib.init_train_state(init_fn, k),
        jax.random.PRNGKey(0))
    params_sds = state_sds.params
    nleaves = len(jax.tree_util.tree_leaves(params_sds))
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves(params_sds))
    nbuckets = make_plan(params_sds, DEFAULT_BUCKET_BYTES).num_buckets
    # Non-gradient collective allowance for the compressed-tier byte
    # ceilings: BN batch-stat pmeans, the int8 shared-scale pmax
    # (f32[nleaves]) and a slack word for loss/count psums.
    bn_bytes = sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(state_sds.bn_state))
    aux_bytes = bn_bytes + 4 * nleaves + 1024

    def _compress_ratio(strategy, strat):
        """Analytic wire-byte reduction of a compressed tier on THIS
        model's leaf shapes — exact from the lowering recipe, so the
        contract pins what the program must achieve, not a slogan."""
        if strategy == "compress-bf16":
            return 2.0
        if strategy == "compress-int8":
            return 4.0
        if strategy == "powersgd":
            wire = 0
            for l in jax.tree_util.tree_leaves(params_sds):
                if strat._low_rank(l.shape):
                    m = 1
                    for d in l.shape[:-1]:
                        m *= d
                    wire += 4 * strat.rank * (m + l.shape[-1])  # f32 P + Q
                else:
                    wire += 2 * l.size                          # bf16 path
            return max(1.0, param_bytes / max(1, wire))
        return 1.0

    full_mesh = meshlib.make_mesh(num_devices)
    single_mesh = meshlib.make_mesh(1)
    world = full_mesh.devices.size
    sgd_cfg = sgd.SGDConfig()
    result = AuditResult()
    window_depths: Dict[str, int] = {}

    def contract(name, strategy, w, donates, n_state, ratio):
        return ProgramContract(
            name=name, strategy=strategy, world=w, nleaves=nleaves,
            nbuckets=nbuckets, param_bytes=param_bytes,
            n_state_leaves=n_state, donates_state=donates,
            precision=precision, max_constant_bytes=max_constant_bytes,
            compress_ratio=ratio, aux_bytes=aux_bytes,
            hbm_budget_bytes=hbm_budget_bytes)

    for strategy in strategies:
        mesh = single_mesh if strategy == "single" else full_mesh
        w = mesh.devices.size
        b = max(w, (global_batch // w) * w)
        strat = get_strategy(strategy)
        # Stateful tiers carry (world, ...)-stacked compression state in
        # the optimizer — the abstract state must grow it too.
        st_sds = jax.eval_shape(
            lambda k: steplib.init_train_state(init_fn, k, strat, w),
            jax.random.PRNGKey(0))
        n_state = len(jax.tree_util.tree_leaves(st_sds))
        ratio = _compress_ratio(strategy, strat)
        ring_cap = ringbuf.DEFAULT_CAPACITY if metrics_ring else 0
        sds = _train_sds(mesh, st_sds, b, window, ring_capacity=ring_cap)
        for path in paths:
            name = f"train/{path}/{strategy}"
            ring = metrics_ring and path in ("window", "host_window")
            if path == "step":
                fn = steplib.make_train_step(
                    apply_fn, strat, mesh, sgd_cfg, augment=True,
                    compute_dtype=compute_dtype)
                args = (sds["state"], sds["key"], sds["images"],
                        sds["labels"])
                donates = False
            else:
                fn = steplib.make_train_window(
                    apply_fn, strat, mesh, sgd_cfg,
                    augment=(path == "window"), compute_dtype=compute_dtype,
                    metrics_ring=ring)
                head = ((sds["state"], sds["ring"]) if ring
                        else (sds["state"],))
                args = head + (sds["key"], sds["epoch_images"],
                               sds["epoch_labels"], sds["start"],
                               sds["lengths"])
                donates = True
            # The ring pair is donated alongside the state, so the
            # donation floor rises by its 2 entry buffers.
            n_floor = n_state + (2 if ring else 0)
            text = _hlo_text(fn.lower(*args))
            jaxpr = (jax.make_jaxpr(fn)(*args)
                     if path == "window" else None)
            result.reports.append(audit_program(
                text, contract(name, strategy, w, donates, n_floor, ratio),
                jaxpr, waive=waive))
            if collect_hlo:
                result.hlo[name] = text
            if path == "window":
                window_depths[strategy] = \
                    result.reports[-1].stats["chain_depth"]

    if include_eval:
        sds = _train_sds(full_mesh, state_sds,
                         max(world, (global_batch // world) * world),
                         window)
        ev = steplib.make_eval_window(apply_fn, full_mesh,
                                      compute_dtype=compute_dtype)
        args = (sds["state"], sds["epoch_images"], sds["epoch_labels"])
        text = _hlo_text(ev.lower(*args))
        result.reports.append(audit_program(
            text, contract("eval/window", "eval", world, False,
                           len(jax.tree_util.tree_leaves(state_sds)), 1.0),
            jax.make_jaxpr(ev)(*args), waive=waive))
        if collect_hlo:
            result.hlo["eval/window"] = text

    if serve_buckets:
        result.reports.extend(audit_serving(
            model=model, buckets=serve_buckets,
            precision=serve_precision or precision, waive=waive,
            max_constant_bytes=max_constant_bytes,
            hlo_out=result.hlo if collect_hlo else None,
            swap_recert=serve_swap_recert))

    if world > 1 and len(window_depths) > 1:
        result.ladder, result.ladder_findings = _certify_ladder(
            window_depths, nleaves, nbuckets,
            program="strategy-ladder(train/window)")
        kept = [f for f in result.ladder_findings
                if not _waived(f, waive)]
        result.ladder_findings = kept
    return result


def audit_serving(*, model: str = "vgg11",
                  buckets: Sequence[int] = (1, 8, 32, 128, 256),
                  precision: str = "f32", engine=None,
                  waive: Sequence[str] = (),
                  max_constant_bytes: int = DEFAULT_MAX_CONSTANT_BYTES,
                  hlo_out: Optional[Dict[str, str]] = None,
                  swap_recert: bool = False, swap_seed: int = 1,
                  ) -> List[AuditReport]:
    """Audit the serving executable ladder: one single-device program per
    bucket, required collective-free, precision-certified, constant-lean,
    and fused-ingest certified (``ingest-edge``: uint8 images at the
    program edge, normalize in-program, no float image inputs).
    Pass ``engine`` to audit an already-built :class:`InferenceEngine`
    (tests/test_publish.py does); otherwise one is built without
    staging or caches.  ``hlo_out`` (a dict) collects each rung's
    lowering text under its program name for cost-model attribution.

    ``swap_recert`` re-certifies the ladder under the publish/ hot-swap
    path: differently-seeded weights are installed through
    ``engine.install_weights`` (the same entry point a live swap uses)
    and every rung is re-lowered and re-audited as
    ``serve_swap/b{bucket}/{precision}`` — the baked-constants rule on
    the POST-swap program set proves the executables stay weight-
    agnostic across installs (weights remain runtime arguments, never
    folded), which is what makes the zero-recompile swap sound."""
    if engine is None:
        from ..serve import InferenceEngine
        engine = InferenceEngine(model, buckets=tuple(buckets),
                                 precisions=(precision,),
                                 use_staging=False,
                                 enable_compilation_cache=False)
    reports = []

    def _audit_rungs(prefix: str) -> None:
        for b in engine.buckets:
            name = f"{prefix}/b{b}/{precision}"
            c = ProgramContract(
                name=name, strategy=None, world=1,
                precision=precision, max_constant_bytes=max_constant_bytes,
                u8_edge=True)
            text = engine.lowered_hlo(b, precision)
            reports.append(audit_program(text, c, waive=waive))
            if hlo_out is not None:
                hlo_out[name] = text

    _audit_rungs("serve")
    if swap_recert:
        import jax
        from ..models import get_model
        from ..train.step import init_train_state
        init_fn, _ = get_model(engine.model_name)
        alt = init_train_state(init_fn, jax.random.PRNGKey(swap_seed))
        engine.install_weights(alt.params, alt.bn_state,
                               engine.weights_version + 1)
        _audit_rungs("serve_swap")
    return reports


def record_audit(telemetry, result: AuditResult) -> None:
    """Attach the audit summary to the run manifest.  The disabled
    recorder path allocates and touches NOTHING (exploding-recorder
    pinned in tests/test_analysis.py)."""
    if not getattr(telemetry, "enabled", False):
        return
    telemetry.update_manifest({"audit": result.summary()})


def zoo_attribution(result: AuditResult) -> Dict:
    """Static cost-model attribution over an audited zoo's lowerings
    (requires ``audit_zoo(..., collect_hlo=True)``): per-program analytic
    FLOPs / HBM / wire bytes -> roofline attribution, plus the
    overlap-vs-ddp exposed-communication bound when both tiers are
    present.  Pure static analysis — no dispatch, no devices."""
    from . import costmodel
    from ..obs import attribution as attrlib
    if not result.hlo:
        raise ValueError("audit result carries no HLO text; re-run "
                         "audit_zoo(..., collect_hlo=True)")
    reports = {name: costmodel.cost_report(text, name)
               for name, text in result.hlo.items()}
    programs = {name: attrlib.attribute(
                    rep, mem_report=memlife.mem_report(result.hlo[name],
                                                       name))
                for name, rep in reports.items()}
    out: Dict = {"programs": programs}
    ov, dd = (reports.get("train/window/overlap"),
              reports.get("train/window/ddp"))
    if ov is not None and dd is not None:
        out["overlap_vs_ddp"] = attrlib.overlap_vs_ddp(ov, dd)
    return out


def record_attribution(telemetry, attribution: Dict) -> None:
    """Attach a :func:`zoo_attribution` record to the run manifest; the
    disabled recorder path allocates and touches NOTHING."""
    if not getattr(telemetry, "enabled", False):
        return
    telemetry.update_manifest({"attribution": attribution})
