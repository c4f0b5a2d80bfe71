"""Analytic FLOPs/bytes cost model over the :mod:`analysis.hlo_ir` IR.

Walks every instruction of a lowered program and charges:

- **FLOPs** — dots at ``2 x result_elems x K`` (K = product of the lhs
  contracting dims, batch dims fall out of ``result_elems``), convolutions
  at ``2 x result_elems x kernel_elems / C_out`` (grouped convs charge the
  per-group fan-in automatically), elementwise/transcendental ops at one
  flop per result element, reductions at one flop per input element.
- **HBM bytes** — operand + result bytes per instruction (a deliberately
  pessimistic "nothing fuses" model; see the roofline caveat in README),
  minus the donated entry-parameter bytes (a donated buffer is written in
  place, not copied out).
- **Wire bytes** — collective result bytes via the same accounting as
  :func:`stats.collective_bytes` (async pairs once, on the ``-done``).

Loop multiplicity: ``while`` bodies (the windowed paths' ``lax.scan``)
are charged ``trips`` times, with the trip count inferred as the largest
integer constant in the loop's condition computation — exactly where the
scan's bound lands in both print dialects.  Inference failures fall back
to 1 with a note rather than guessing.

Shard-map programs lower with PER-DEVICE shapes inside the manual region,
so a :class:`CostReport` over such a program is per-device; multiply by
the mesh size for machine totals.

This module is the single source of truth for the repo's analytic
FLOP/MFU arithmetic: ``obs/attribution.py`` delegates here (ISSUE 8
consolidation).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import hlo_ir, stats

# v5e datasheet numbers shared by every MFU/roofline consumer in the repo.
# analysis/memlife (the peak-HBM certifier) reads the capacity from HERE —
# tools/lint_graft.py's path-less run fails if any of these literals grows
# a second copy.
V5E_BF16_PEAK_FLOPS = 197e12     # bf16 peak, per chip
V5E_HBM_BYTES_PER_S = 819e9     # HBM bandwidth, per chip
V5E_ICI_BYTES_PER_S = 200e9     # 1600 Gbit/s ICI, per chip per direction
V5E_HBM_CAPACITY_BYTES = 16 * 2**30   # HBM capacity, per chip

# MEASURED paths divide a rate only by the peak of the device that produced
# it: the table is keyed by jax's ``device_kind`` string, and a device that
# is not in it gets no utilization field (a CPU-mesh run once filed
# ``mfu_vs_bf16_peak: 0.0`` against the v5e peak).  The static certificates
# (memlife, the audit budget) name their v5e target outright and
# keep using the constants above.
PEAK_BF16_FLOPS_BY_DEVICE_KIND = {"TPU v5 lite": V5E_BF16_PEAK_FLOPS}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INT_DTYPES = ("pred", "s8", "u8", "s16", "u16", "s32", "u32", "s64", "u64")

# One flop per result element.  Pure data movement (reshape, broadcast,
# transpose, slice, dynamic-update-slice, copy, ...) charges 0 flops and
# shows up in the HBM column instead.
_ELEMENTWISE = frozenset((
    "add", "subtract", "multiply", "divide", "remainder", "power",
    "maximum", "minimum", "clamp", "select", "compare",
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "sqrt", "rsqrt", "cbrt", "erf",
    "negate", "abs", "sign", "floor", "ceil", "is-finite",
    "round-nearest-afz", "round-nearest-even",
    "cosine", "sine", "tan", "atan2",
    "and", "or", "xor", "not", "convert",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
))
_REDUCE_OPS = frozenset(("reduce", "reduce-window"))
# Bookkeeping opcodes that move no HBM of their own.
_FREE_OPS = frozenset(("parameter", "constant", "tuple",
                       "get-tuple-element", "bitcast", "after-all",
                       "opt-barrier", "optimization-barrier"))


def mfu_fields(ips_per_chip: float, flops_per_image: Optional[float],
               device_kind: str) -> Dict:
    """Achieved TFLOP/s + model-flops-utilization fields for a per-chip
    image rate measured on a ``device_kind`` device.  Returns ``{}`` when
    the analytic flop count is unavailable, and no ``mfu_vs_bf16_peak``
    for a device outside the peak table — absent keys, never null values."""
    if not flops_per_image:
        return {}
    tflops = ips_per_chip * flops_per_image / 1e12
    out = {"tflops_per_sec": round(tflops, 2)}
    peak_flops = PEAK_BF16_FLOPS_BY_DEVICE_KIND.get(device_kind)
    if peak_flops is not None:
        out["mfu_vs_bf16_peak"] = round(tflops * 1e12 / peak_flops, 4)
    return out


def _dims(type_str: Optional[str]) -> Optional[List[int]]:
    """Dims of the first array shape in an HLO type string, or None."""
    m = _SHAPE_RE.search(type_str or "")
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def _elems(type_str: Optional[str]) -> int:
    """Total elements across every array shape in a (possibly tuple)
    HLO type string."""
    total = 0
    for _, dims in _SHAPE_RE.findall(type_str or ""):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _attr_ints(raw: Optional[str]) -> List[int]:
    return [int(t) for t in re.findall(r"\d+", raw or "")]


def _operand_type(comp: hlo_ir.Computation, ins: hlo_ir.Instruction,
                  i: int) -> Optional[str]:
    """Type of operand ``i``: resolved through the defining instruction
    (the pre-optimization print leaves operands untyped), falling back to
    a type printed inline on the operand (optimized print)."""
    if i >= len(ins.operands):
        return None
    ref = comp.instructions.get(ins.operands[i])
    if ref is not None and ref.result_type:
        return ref.result_type
    if i < len(ins.operand_raw) and _SHAPE_RE.search(ins.operand_raw[i]):
        return ins.operand_raw[i]
    return None


def _called_comp(ins: hlo_ir.Instruction, key: str) -> Optional[str]:
    raw = ins.attr(key)
    if not raw:
        return None
    m = re.search(r"[%A-Za-z_][\w.\-]*", raw)
    return m.group(0).lstrip("%") if m else None


def _infer_trips(module: hlo_ir.Module, ins: hlo_ir.Instruction,
                 notes: List[str]) -> int:
    """Trip count of a ``while``: the largest integer constant in its
    condition computation (where ``lax.scan`` lowers its bound,
    ``lt(counter, constant(W))``, in both print dialects)."""
    cond = _called_comp(ins, "condition")
    comp = module.computations.get(cond) if cond else None
    best = 0
    if comp is not None:
        for c in comp.instructions.values():
            if c.opcode != "constant":
                continue
            if not c.result_type.startswith(_INT_DTYPES):
                continue
            for raw in c.operand_raw:
                try:
                    best = max(best, int(raw.strip().strip("{}")))
                except ValueError:
                    pass
    if best <= 0:
        notes.append(f"while {ins.name}: no integer bound in condition "
                     "computation; charging 1 trip")
        return 1
    return best


@dataclass
class CostReport:
    """Per-program analytic costs (per-device for shard_map programs)."""
    name: str
    flops: float = 0.0
    flops_by_op: Dict[str, float] = field(default_factory=dict)
    hbm_bytes: float = 0.0
    wire_bytes: float = 0.0                 # loop-multiplicity weighted
    wire_by_collective: Dict[str, int] = field(default_factory=dict)
    collective_sizes: List[int] = field(default_factory=list)  # static, per op
    donated_params: int = 0
    donated_bytes: int = 0
    trip_counts: Dict[str, int] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)

    @property
    def arithmetic_intensity(self) -> float:
        """flops / HBM byte — the roofline x-axis."""
        return self.flops / self.hbm_bytes if self.hbm_bytes else math.inf

    @property
    def comm_compute_flop_ratio(self) -> float:
        """Wire bytes per flop (0 when the program has no collectives)."""
        return self.wire_bytes / self.flops if self.flops else 0.0

    def to_dict(self) -> Dict:
        return {
            "name": self.name,
            "gflops": round(self.flops / 1e9, 4),
            "flops_by_op": {k: round(v / 1e9, 4)
                            for k, v in self.flops_by_op.items()},
            "hbm_mib": round(self.hbm_bytes / 2**20, 3),
            "wire_mib": round(self.wire_bytes / 2**20, 4),
            "wire_by_collective": dict(self.wire_by_collective),
            "donated_params": self.donated_params,
            "donated_mib": round(self.donated_bytes / 2**20, 3),
            "trip_counts": dict(self.trip_counts),
            "arithmetic_intensity": (
                round(self.arithmetic_intensity, 2)
                if self.hbm_bytes else None),
            "notes": list(self.notes),
        }


def _dot_flops(comp: hlo_ir.Computation, ins: hlo_ir.Instruction,
               notes: List[str]) -> float:
    out_elems = _elems(ins.result_type)
    lhs_dims = _dims(_operand_type(comp, ins, 0))
    contracting = _attr_ints(ins.attr("lhs_contracting_dims"))
    if lhs_dims is None or not contracting:
        notes.append(f"dot {ins.name}: lhs shape or contracting dims "
                     "unresolved; charging K=1")
        return 2.0 * out_elems
    k = 1
    for d in contracting:
        if d < len(lhs_dims):
            k *= lhs_dims[d]
    return 2.0 * out_elems * k


def _conv_flops(comp: hlo_ir.Computation, ins: hlo_ir.Instruction,
                notes: List[str]) -> float:
    out_elems = _elems(ins.result_type)
    kern_dims = _dims(_operand_type(comp, ins, 1))
    if kern_dims is None:
        notes.append(f"convolution {ins.name}: kernel shape unresolved; "
                     "charging 1 MAC per output element")
        return 2.0 * out_elems
    labels = ins.attr("dim_labels") or ""
    kern_labels = ""
    if "_" in labels:
        kern_labels = labels.split("_", 1)[1].split("->", 1)[0]
    o_idx = kern_labels.find("o") if "o" in kern_labels else len(kern_dims) - 1
    c_out = kern_dims[o_idx] if 0 <= o_idx < len(kern_dims) else 1
    kern_elems = 1
    for d in kern_dims:
        kern_elems *= d
    return 2.0 * out_elems * (kern_elems / max(c_out, 1))


def _donated_entry_bytes(module: hlo_ir.Module) -> Tuple[int, int]:
    """(donated param count, donated param bytes) from whichever donation
    header this toolchain prints (same forms as
    :meth:`hlo_ir.Module.donated_param_count`)."""
    idxs: set = set()
    for key in ("buffer_donor", "input_output_alias"):
        raw = module.attr(key)
        if raw:
            idxs |= {int(i) for i in re.findall(r"\(\s*(\d+)\s*,", raw)}
    entry = module.entry_computation
    by_index: Dict[int, str] = {}
    if entry is not None:
        for ins in entry.instructions.values():
            if ins.opcode == "parameter" and ins.operand_raw:
                try:
                    by_index[int(ins.operand_raw[0])] = ins.result_type
                except ValueError:
                    pass
    nbytes = sum(stats.bytes_of_type(by_index.get(i, "")) for i in idxs)
    return len(idxs), nbytes


def cost_report(hlo: stats.ModuleOrText, name: str = "program") -> CostReport:
    """Build a :class:`CostReport` for one lowered program.  Accepts raw
    HLO text (either print dialect) or a parsed Module."""
    module = stats._as_module(hlo)
    rep = CostReport(name=name)

    # Execution multiplicity per computation: entry runs once; while
    # bodies/conditions run `trips` times; every other callee (fusions,
    # reducers, branches) inherits the caller's multiplicity.
    mult: Dict[str, float] = {}

    def visit(cname: str, m: float, stack: Tuple[str, ...] = ()) -> None:
        if cname in stack or cname not in module.computations:
            return
        mult[cname] = mult.get(cname, 0.0) + m
        for ins in module.computations[cname].instructions.values():
            if ins.opcode == "while":
                trips = _infer_trips(module, ins, rep.notes)
                rep.trip_counts[ins.name] = trips
                for key, factor in (("body", trips), ("condition", trips)):
                    callee = _called_comp(ins, key)
                    if callee:
                        visit(callee, m * factor, stack + (cname,))
            else:
                for callee in ins.called:
                    visit(callee, m, stack + (cname,))

    entry = module.entry or next(iter(module.computations), None)
    if entry is not None:
        visit(entry, 1.0)

    for cname, comp in module.computations.items():
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        for ins in comp.instructions.values():
            # --- FLOPs ---
            fl, key = 0.0, None
            if ins.opcode == "dot":
                fl, key = _dot_flops(comp, ins, rep.notes), "dot"
            elif ins.opcode == "convolution":
                fl, key = _conv_flops(comp, ins, rep.notes), "convolution"
            elif ins.opcode in _ELEMENTWISE:
                fl, key = float(_elems(ins.result_type)), "elementwise"
            elif ins.opcode in _REDUCE_OPS:
                fl, key = float(_elems(_operand_type(comp, ins, 0))), "reduce"
            if fl:
                rep.flops += fl * m
                rep.flops_by_op[key] = rep.flops_by_op.get(key, 0.0) + fl * m
            # --- HBM bytes (operand + result, nothing-fuses model) ---
            if ins.opcode not in _FREE_OPS:
                b = stats.bytes_of_type(ins.result_type)
                for i in range(len(ins.operands)):
                    b += stats.bytes_of_type(
                        _operand_type(comp, ins, i) or "")
                rep.hbm_bytes += b * m
            # --- wire bytes (same async-pair convention as stats) ---
            base = stats.collective_base(ins.opcode)
            if base is not None and not ins.opcode.endswith("-start"):
                b = stats.bytes_of_type(ins.result_type)
                rep.wire_bytes += b * m
                rep.collective_sizes.append(b)

    # Static per-collective bytes: identical accounting to the audit's
    # byte contracts (stats.collective_bytes), unweighted by loop trips.
    rep.wire_by_collective = stats.collective_bytes(module)
    rep.donated_params, rep.donated_bytes = _donated_entry_bytes(module)
    rep.hbm_bytes = max(0.0, rep.hbm_bytes - rep.donated_bytes)
    return rep
