"""Static buffer-liveness / peak-HBM certifier over the :mod:`hlo_ir` IR.

Every other certifier in this repo bounds a *rate* (collective bytes,
host round-trips, lock orders); this one bounds the resource that
decides whether a program runs at all: device memory.  For each
computation it builds def/last-use intervals per instruction result,
threads aliasing through the ops that create views rather than buffers
(``tuple`` / ``get-tuple-element`` / ``bitcast`` / the
optimization-barrier chains the strategies emit), and sweeps a
peak-live-bytes bound:

- **Entry parameters** are argument buffers held by the caller for the
  whole dispatch: live ``[0, end]``, donated or not.
- **Constants** are baked into the executable: live from their def to
  the end (never freed).
- **`while` loops run steady-state**: the result ALIASES the carry
  operand (the in-place update buffer donation buys), and the body's
  transient peak is added ONCE — loop iterations reuse their buffers,
  so trip counts multiply FLOPs (:mod:`costmodel`) but never memory.
  The body is charged WITH its root (the freshly produced carry):
  XLA's loop double-buffering means old and new carry coexist at the
  instant the body finishes, donation or not.
- **Donation is proven in bytes, not leaf counts**: a ``while`` whose
  carry includes NON-donated entry parameters must copy them before
  overwriting (XLA copy-insertion) — the analyzer charges that copy
  (``undonated_copy_bytes``), so the donated and un-donated lowerings
  of the same window differ by exactly the carried state bytes.
- **Callees** (fusions, reducers, branches) contribute a transient
  spike at the call site: their internal peak with parameters and root
  excluded (operands and result are charged by the caller).

The bound is over whichever print the caller hands in; the audit feeds
it the PRE-optimization lowering, where entry shapes are still GLOBAL
(pre-SPMD) — so for shard_map programs the bound is per-*program*, an
upper bound on any single chip's share.  Validation is two-sided
(tests/test_memlife.py): never under ``compiled.memory_analysis()``'s
temp+output bytes on any zoo program, within :data:`COMPILED_BAND` of
it on the windowed train paths, and never under the runtime
``live_arrays`` gauge ``train/loop.emit_memory_gauges`` records.

The per-chip budget it certifies against is the single-sourced
:data:`costmodel.V5E_HBM_CAPACITY_BYTES`; :func:`check_memory` is the
jax-free repo self-check ``tools/lint_graft.py`` runs path-less (the
literals stay single-sourced, the committed fixtures keep proving the
donation delta).
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Tuple

from . import costmodel, hlo_ir, stats
from .pylint_rules import LintFinding

#: Static peak must sit within this factor of the compiled
#: ``memory_analysis()`` total (argument+output+temp) on the windowed
#: train paths — the declared tolerance band.  The static model is
#: deliberately conservative (nothing fuses, callee spikes sum, entry
#: shapes are pre-SPMD global), so the band is an over-approximation
#: ceiling, never an under-count licence; measured ratios on the CPU
#: backend sit at 1.1-2.0x.
COMPILED_BAND = 4.0

#: Ops whose result is a VIEW of operand storage — no new buffer.
_ALIAS_OPS = frozenset((
    "tuple", "get-tuple-element", "bitcast",
    "optimization-barrier", "opt-barrier", "after-all",
))

#: How many of the fattest program points a MemReport keeps.
TOP_SETS = 5
_TOP_MEMBERS = 8


@dataclass
class MemReport:
    """Static memory certificate for one program."""

    name: str
    peak_bytes: int = 0
    param_bytes: int = 0              # entry argument buffers (all live)
    donated_bytes: int = 0            # donated subset (in-place carry)
    carry_bytes: int = 0              # fattest while-carry in the entry
    undonated_copy_bytes: int = 0     # copy-insertion cost of missed donation
    constant_bytes: int = 0           # baked into the executable
    transient_peak_bytes: int = 0     # peak beyond the argument buffers
    output_bytes: int = 0             # root result (donated part aliases)
    # Top fattest live sets: {"position", "instruction", "live_bytes",
    # "members": [[buffer, bytes], ...]} — the "what do I shrink" view.
    top_sets: List[Dict] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)

    @property
    def peak_mib(self) -> float:
        return self.peak_bytes / 2**20

    def to_dict(self) -> Dict:
        return {
            "name": self.name,
            "peak_mib": round(self.peak_bytes / 2**20, 3),
            "param_mib": round(self.param_bytes / 2**20, 3),
            "donated_mib": round(self.donated_bytes / 2**20, 3),
            "carry_mib": round(self.carry_bytes / 2**20, 3),
            "undonated_copy_mib": round(
                self.undonated_copy_bytes / 2**20, 3),
            "constant_mib": round(self.constant_bytes / 2**20, 3),
            "transient_peak_mib": round(
                self.transient_peak_bytes / 2**20, 3),
            "output_mib": round(self.output_bytes / 2**20, 3),
            "top_sets": [
                {**t, "live_mib": round(t["live_bytes"] / 2**20, 3),
                 "members": [[n, round(b / 2**20, 3)]
                             for n, b in t["members"]]}
                for t in self.top_sets],
            "notes": list(self.notes),
        }


def _donated_indices(module: hlo_ir.Module) -> FrozenSet[int]:
    idxs = set()
    for key in ("buffer_donor", "input_output_alias"):
        raw = module.attr(key)
        if raw:
            idxs |= {int(i) for i in re.findall(r"\(\s*(\d+)\s*,", raw)}
    return frozenset(idxs)


class _Analyzer:
    """One pass over a module; memoizes callee transient peaks."""

    def __init__(self, module: hlo_ir.Module):
        self.module = module
        self._transient_memo: Dict[Tuple[str, bool], int] = {}

    # -- callee transient peaks -------------------------------------------

    def transient_peak(self, cname: str, *, charge_root: bool,
                       stack: Tuple[str, ...] = ()) -> int:
        """Peak live bytes INSIDE computation ``cname`` beyond what its
        caller already charges: parameters excluded always, the root
        excluded unless ``charge_root`` (while bodies charge it — the
        fresh carry coexists with the old one)."""
        key = (cname, charge_root)
        if key in self._transient_memo:
            return self._transient_memo[key]
        if cname in stack or cname not in self.module.computations:
            return 0
        peak = self._sweep(self.module.computations[cname],
                           entry_mode=False, charge_root=charge_root,
                           stack=stack + (cname,))[0]
        self._transient_memo[key] = peak
        return peak

    # -- the liveness sweep -----------------------------------------------

    def _sweep(self, comp: hlo_ir.Computation, *, entry_mode: bool,
               charge_root: bool, stack: Tuple[str, ...],
               donated: FrozenSet[int] = frozenset(),
               report: Optional[MemReport] = None):
        """Event-sweep one computation.  Returns (peak_bytes, live_curve,
        buffers, defpos, lastuse) and, in entry mode, fills ``report``."""
        instrs = list(comp.instructions.values())
        n = len(instrs)
        if n == 0:
            return 0, [], {}, {}, {}

        origins: Dict[str, FrozenSet[str]] = {}
        buffers: Dict[str, int] = {}      # buffer -> bytes
        defpos: Dict[str, int] = {}
        lastuse: Dict[str, int] = {}
        spike: Dict[int, int] = {}        # position -> callee transient
        param_buffers: Dict[str, int] = {}   # buffer -> param index
        root_name = comp.root.name if comp.root is not None else None

        def alloc(buf: str, nbytes: int, pos: int) -> None:
            buffers[buf] = nbytes
            defpos[buf] = pos
            lastuse[buf] = pos

        for pos, ins in enumerate(instrs):
            op = ins.opcode
            if op == "parameter":
                if entry_mode:
                    alloc(ins.name, hlo_ir.result_bytes(ins), 0)
                    lastuse[ins.name] = n - 1   # caller-held argument
                    try:
                        param_buffers[ins.name] = int(ins.operand_raw[0])
                    except (IndexError, ValueError):
                        param_buffers[ins.name] = -1
                    origins[ins.name] = frozenset((ins.name,))
                else:
                    origins[ins.name] = frozenset()   # caller-owned
                continue

            used: set = set()
            for ref in ins.operands:
                used |= origins.get(ref, frozenset())
            for buf in used:
                lastuse[buf] = pos

            if op == "constant":
                alloc(ins.name, hlo_ir.result_bytes(ins), pos)
                lastuse[ins.name] = n - 1       # executable image, not freed
                origins[ins.name] = frozenset((ins.name,))
                continue
            if op in _ALIAS_OPS:
                origins[ins.name] = frozenset(used)
                continue

            if op == "while":
                body = costmodel._called_comp(ins, "body")
                cond = costmodel._called_comp(ins, "condition")
                extra = 0
                if body:
                    extra += self.transient_peak(body, charge_root=True,
                                                 stack=stack)
                if cond:
                    extra += self.transient_peak(cond, charge_root=False,
                                                 stack=stack)
                spike[pos] = spike.get(pos, 0) + extra
                carry = frozenset(used)
                if report is not None:
                    report.carry_bytes = max(
                        report.carry_bytes,
                        sum(buffers.get(b, 0) for b in carry))
                if entry_mode:
                    undonated = frozenset(
                        b for b in carry
                        if b in param_buffers
                        and param_buffers[b] not in donated)
                    copy_bytes = sum(buffers[b] for b in undonated)
                    if copy_bytes:
                        cbuf = ins.name + ":carry-copy"
                        alloc(cbuf, copy_bytes, pos)
                        carry = (carry - undonated) | {cbuf}
                        if report is not None:
                            report.undonated_copy_bytes += copy_bytes
                            report.notes.append(
                                f"while {ins.name}: {copy_bytes} carry "
                                f"bytes enter through non-donated entry "
                                f"parameters — copy-insertion charges a "
                                f"fresh buffer (donate them to erase it)")
                origins[ins.name] = carry
                continue

            # Generic allocating op (fusions, calls, reduces, branches,
            # custom-calls, copies, dots, ...): callee internals spike
            # at the call site, the result is a fresh buffer.
            for callee in ins.called:
                spike[pos] = spike.get(pos, 0) + self.transient_peak(
                    callee, charge_root=False, stack=stack)
            alloc(ins.name, hlo_ir.result_bytes(ins), pos)
            origins[ins.name] = frozenset((ins.name,))

        # Root results are live at the end (the caller fetches them).
        if root_name is not None:
            root_origins = origins.get(root_name, frozenset())
            for buf in root_origins:
                lastuse[buf] = n - 1
            if not charge_root:
                # Callee mode: the caller charges the result bytes.
                for buf in root_origins:
                    if buf in buffers and buf not in param_buffers:
                        buffers[buf] = 0

        # Event sweep: +bytes at def, -bytes after last use, plus the
        # per-position callee spike.
        delta = [0] * (n + 1)
        for buf, nbytes in buffers.items():
            delta[defpos[buf]] += nbytes
            delta[lastuse[buf] + 1] -= nbytes
        live = []
        running = 0
        for pos in range(n):
            running += delta[pos]
            live.append(running + spike.get(pos, 0))
        peak = max(live) if live else 0

        if report is not None:
            report.param_bytes = sum(
                buffers[b] for b in param_buffers)
            report.donated_bytes = sum(
                buffers[b] for b, i in param_buffers.items()
                if i in donated)
            report.constant_bytes = sum(
                nbytes for buf, nbytes in buffers.items()
                if comp.instructions.get(buf) is not None
                and comp.instructions[buf].opcode == "constant")
            if comp.root is not None:
                report.output_bytes = hlo_ir.result_bytes(comp.root)
            top = sorted(range(n), key=lambda p: live[p],
                         reverse=True)[:TOP_SETS]
            for p in top:
                members = sorted(
                    ((buf, nbytes) for buf, nbytes in buffers.items()
                     if defpos[buf] <= p <= lastuse[buf] and nbytes),
                    key=lambda kv: kv[1], reverse=True)[:_TOP_MEMBERS]
                if spike.get(p):
                    members = ([("(callee transients)", spike[p])]
                               + members)[:_TOP_MEMBERS]
                report.top_sets.append({
                    "position": p,
                    "instruction": instrs[p].name,
                    "live_bytes": live[p],
                    "members": members,
                })
        return peak, live, buffers, defpos, lastuse


def mem_report(hlo: stats.ModuleOrText, name: str = "program") -> MemReport:
    """Build the static memory certificate for one lowered program.
    Accepts raw HLO text (either print dialect) or a parsed Module."""
    module = stats._as_module(hlo)
    report = MemReport(name=name)
    entry = module.entry_computation
    if entry is None:
        report.notes.append("module has no computations")
        return report
    analyzer = _Analyzer(module)
    peak, _, _, _, _ = analyzer._sweep(
        entry, entry_mode=True, charge_root=True, stack=(entry.name,),
        donated=_donated_indices(module), report=report)
    report.peak_bytes = peak
    report.transient_peak_bytes = max(0, peak - report.param_bytes)
    return report


# ---------------------------------------------------------------------------
# Donation proven as an aliased-bytes equality
# ---------------------------------------------------------------------------

def _leaf_bytes(type_str: str) -> List[int]:
    """Byte sizes of every array LEAF in a (possibly nested tuple) type."""
    s = hlo_ir._TYPE_COMMENT_RE.sub("", type_str or "").strip()
    if not s:
        return []
    if s.startswith("("):
        inner = s[1:hlo_ir._scan_balanced(s, 0) - 1]
        out: List[int] = []
        for part in hlo_ir.split_top(inner):
            out.extend(_leaf_bytes(part))
        return out
    b = hlo_ir.type_bytes(s)
    return [b] if b else []


def donation_alias_findings(module: hlo_ir.Module,
                            program: str = "program") -> List[str]:
    """Prove each donated entry parameter can actually alias an output:
    every donated leaf's byte size must be matched by a DISTINCT root
    leaf of the same size (multiset containment).  A donated buffer with
    no same-size output leaf is a donation that cannot round-trip — XLA
    will quietly copy, and the in-place-update story is fiction."""
    donated = _donated_indices(module)
    entry = module.entry_computation
    if not donated or entry is None:
        return []
    by_index: Dict[int, str] = {}
    for ins in entry.instructions.values():
        if ins.opcode == "parameter" and ins.operand_raw:
            try:
                by_index[int(ins.operand_raw[0])] = ins.result_type
            except ValueError:
                pass
    root = entry.root
    pool: Dict[int, int] = {}
    for b in _leaf_bytes(root.result_type if root is not None else ""):
        pool[b] = pool.get(b, 0) + 1
    out: List[str] = []
    for idx in sorted(donated):
        for b in _leaf_bytes(by_index.get(idx, "")):
            if pool.get(b, 0) > 0:
                pool[b] -= 1
            else:
                out.append(
                    f"{program}: donated entry parameter {idx} "
                    f"({by_index.get(idx, '?')}, {b} bytes) has no "
                    f"same-size output leaf to alias — the donation "
                    f"cannot round-trip in place")
    return out


# ---------------------------------------------------------------------------
# Differential check against compiled.memory_analysis()
# ---------------------------------------------------------------------------

def check_against_compiled(report: MemReport, mem_stats, *,
                           band: float = COMPILED_BAND,
                           windowed: bool = False) -> List[str]:
    """Compare the static bound with JAX's ``CompiledMemoryStats``.
    The static peak must NEVER sit under the compiled temp+output bytes
    (an under-count would certify programs that OOM); on the windowed
    train paths it must also sit within ``band`` x the compiled total
    (argument+output+temp) — conservative is fine, unmoored is not."""
    temp = getattr(mem_stats, "temp_size_in_bytes", 0) or 0
    out_b = getattr(mem_stats, "output_size_in_bytes", 0) or 0
    args = getattr(mem_stats, "argument_size_in_bytes", 0) or 0
    findings: List[str] = []
    floor = temp + out_b
    if report.peak_bytes < floor:
        findings.append(
            f"{report.name}: static peak {report.peak_bytes} B UNDER the "
            f"compiled floor temp+output = {temp}+{out_b} = {floor} B — "
            f"the bound is unsound")
    total = args + out_b + temp
    if windowed and total and report.peak_bytes > band * total:
        findings.append(
            f"{report.name}: static peak {report.peak_bytes} B exceeds "
            f"{band:g}x the compiled total {total} B — the bound came "
            f"unmoored from the executable")
    return findings


# ---------------------------------------------------------------------------
# The real train window, lowered for the certifier (needs jax)
# ---------------------------------------------------------------------------

def lower_window(model: str = "vgg11", *, world: int = 8,
                 window: int = 4, global_batch: int = 256):
    """Lower THE ``ddp`` train window with its metric ring, as the Trainer
    builds it (the same recipe the audit zoo uses); returns
    ``(lowered, name)`` so callers can take the HLO text for the static
    certifier AND ``.compile()`` it for the differential check.  Requires
    jax; lowering is abstract (eval_shape), no parameters materialize."""
    import jax

    from . import audit
    from ..models import get_model
    from ..obs import ringbuf
    from ..ops import sgd
    from ..parallel import get_strategy, mesh as meshlib
    from ..train import step as steplib

    mesh = meshlib.make_mesh(world)
    w = mesh.devices.size
    b = max(w, (global_batch // w) * w)
    strat = get_strategy("ddp" if w > 1 else "single")
    init_fn, apply_fn = get_model(model)
    st_sds = jax.eval_shape(
        lambda k: steplib.init_train_state(init_fn, k, strat, w),
        jax.random.PRNGKey(0))
    sds = audit._train_sds(mesh, st_sds, b, window,
                           ring_capacity=ringbuf.DEFAULT_CAPACITY)
    fn = steplib.make_train_window(
        apply_fn, strat, mesh, sgd.SGDConfig(), augment=True,
        metrics_ring=True)
    lowered = fn.lower(sds["state"], sds["ring"], sds["key"],
                       sds["epoch_images"], sds["epoch_labels"],
                       sds["start"], sds["lengths"])
    return lowered, f"train/window/ddp@w{w}/{model}"


def window_mem_report(model: str = "vgg11", *, world: int = 8,
                      window: int = 4, global_batch: int = 256
                      ) -> MemReport:
    """Lower the train window and run the liveness certifier over it."""
    from . import audit

    lowered, name = lower_window(
        model, world=world, window=window, global_batch=global_batch)
    return mem_report(audit._hlo_text(lowered), name)


# ---------------------------------------------------------------------------
# jax-free repo self-checks (tools/lint_graft.py path-less run)
# ---------------------------------------------------------------------------

#: The v5e datasheet literals and their single source of truth.  This
#: checker file is the one other place allowed to SPELL them (as the
#: patterns it greps for).
_HW_LITERALS = ("197e12", "819e9", "200e9")
_HW_HOME = os.path.join("cs744_ddp_tpu", "analysis", "costmodel.py")
_HW_CHECKER = os.path.join("cs744_ddp_tpu", "analysis", "memlife.py")
_CAPACITY_ASSIGN_RE = re.compile(r"^\s*V5E_HBM_CAPACITY_BYTES\s*=",
                                 re.MULTILINE)
_SCAN_DIRS = ("cs744_ddp_tpu", "tools")

#: Committed fixture pair proving the donation delta in bytes: identical
#: windowed programs, one donating its carried state, one not.
FIXTURE_DONATED = os.path.join("tests", "assets", "hlo",
                               "memlife_window_donated.hlo")
FIXTURE_UNDONATED = os.path.join("tests", "assets", "hlo",
                                 "memlife_window_undonated.hlo")


def _py_files(repo_root: str):
    for d in _SCAN_DIRS:
        for dirpath, _, names in os.walk(os.path.join(repo_root, d)):
            for fn in names:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def check_constants_single_source(repo_root: str) -> List[LintFinding]:
    """The v5e roofline/capacity numbers live in analysis/costmodel.py
    and NOWHERE else — a second copy is a fork waiting to drift."""
    findings: List[LintFinding] = []
    home = os.path.join(repo_root, _HW_HOME)
    checker = os.path.join(repo_root, _HW_CHECKER)
    for path in _py_files(repo_root):
        if os.path.abspath(path) in (os.path.abspath(home),
                                     os.path.abspath(checker)):
            continue
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        for lit in _HW_LITERALS:
            for m in re.finditer(re.escape(lit) + r"\b", text):
                line = text.count("\n", 0, m.start()) + 1
                findings.append(LintFinding(
                    "memory-constants", path, line,
                    f"v5e literal {lit} duplicated outside "
                    f"{_HW_HOME}; import it from analysis.costmodel"))
        for m in _CAPACITY_ASSIGN_RE.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            findings.append(LintFinding(
                "memory-constants", path, line,
                f"V5E_HBM_CAPACITY_BYTES reassigned outside {_HW_HOME}"))
    try:
        with open(home, encoding="utf-8") as f:
            home_text = f.read()
    except OSError:
        home_text = ""
    for lit in _HW_LITERALS:
        if len(re.findall(re.escape(lit) + r"\b", home_text)) != 1:
            findings.append(LintFinding(
                "memory-constants", home, 0,
                f"v5e literal {lit} must appear exactly once in its "
                f"home file"))
    if len(_CAPACITY_ASSIGN_RE.findall(home_text)) != 1:
        findings.append(LintFinding(
            "memory-constants", home, 0,
            "V5E_HBM_CAPACITY_BYTES must be assigned exactly once in "
            "its home file"))
    return findings


def check_fixture_invariants(repo_root: str) -> List[LintFinding]:
    """Re-prove the donation byte bound on the committed fixture pair:
    the non-donating windowed program's static peak must exceed the
    donating twin's by its carried state bytes, and the donating twin's
    donation must round-trip as an aliased-bytes equality."""
    findings: List[LintFinding] = []
    paths = {}
    for tag, rel in (("donated", FIXTURE_DONATED),
                     ("undonated", FIXTURE_UNDONATED)):
        path = os.path.join(repo_root, rel)
        if not os.path.exists(path):
            findings.append(LintFinding(
                "memory-fixture", path, 0,
                f"committed memlife fixture missing ({tag})"))
            continue
        with open(path, encoding="utf-8") as f:
            paths[tag] = (path, f.read())
    if len(paths) != 2:
        return findings
    don_path, don_text = paths["donated"]
    und_path, und_text = paths["undonated"]
    don = mem_report(don_text, "fixture/donated")
    und = mem_report(und_text, "fixture/undonated")
    if not und.undonated_copy_bytes:
        findings.append(LintFinding(
            "memory-fixture", und_path, 0,
            "non-donating windowed fixture charges no carry copy — the "
            "donation delta is no longer being proven"))
    if und.peak_bytes - don.peak_bytes != und.undonated_copy_bytes:
        findings.append(LintFinding(
            "memory-fixture", und_path, 0,
            f"donation delta broke: undonated peak {und.peak_bytes} - "
            f"donated peak {don.peak_bytes} != copy bytes "
            f"{und.undonated_copy_bytes}"))
    for msg in donation_alias_findings(stats._as_module(don_text),
                                       "fixture/donated"):
        findings.append(LintFinding("memory-fixture", don_path, 0, msg))
    return findings


def check_memory(repo_root: str) -> List[LintFinding]:
    """Everything the path-less lint run certifies about memory, with no
    jax import: constants single-sourcing + the fixture invariants."""
    return (check_constants_single_source(repo_root)
            + check_fixture_invariants(repo_root))
