"""A decoder's layers (models/sdar.py, ops/attention.py, ops/moe.py): where
the train modules' device time goes by named scope, the two kernels'
roofline shares, and how far the router leans.

The program wraps its four pieces in `jax.named_scope`s (`attn_blockdiff`,
`moe_route`, `moe_experts`, `lm_head`), which the compiler keeps in every
instruction's `metadata={op_name="..."}`, forward and backward.  The trace
names an operation by its instruction, so the driver
(`drivers/train_tokens.py`) classes the instructions of the loaded modules'
text with `scope_instructions`, sums the traced operations' time with
`scope_seconds` and leaves the result in `run.counters["scope_seconds"]`
(run.py deletes the trace before a reader runs).  Every reader returns None,
never 0, when it finds nothing: an untraced run, or a program without the
scopes or the counters (the parent).
"""

from __future__ import annotations

import collections
import re

from benchmark import lm_flops, trace as tracelib

_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*[^=]*?\s([a-z][\w\-]*)\(")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
KERNEL_SCOPES = ("attn_blockdiff", "moe_experts")    # hold Pallas kernels
# The splash-attention kernels carry a name of their own and no op_name:
# they are known by their instructions' names.
KERNEL_PREFIXES = {"splash_": "attn_blockdiff"}


def _scope_of(inst: str, op_name: str, scopes) -> str | None:
    for s in scopes:
        if s in op_name:
            return s
    for prefix, s in KERNEL_PREFIXES.items():
        if inst.startswith(prefix) and s in scopes:
            return s
    return None


def scope_instructions(hlo_text: str, scopes) -> dict:
    """{instruction: scope} over a compiled module's text.  An instruction
    belongs to the first of `scopes` its op_name holds; a fusion whose own
    op_name holds none takes the commonest scope of its fused computation."""
    own, inside, calls = {}, collections.defaultdict(collections.Counter), {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INST.match(line)
        if not m:
            continue
        inst = m.group(1)
        name = _OP_NAME.search(line)
        scope = _scope_of(inst, name.group(1) if name else "", scopes)
        if scope:
            own[inst] = scope
            inside[comp][scope] += 1
        c = _CALLS.search(line)
        if c:
            calls[inst] = c.group(1)
    for inst, called in calls.items():
        if inst not in own and inside.get(called):
            own[inst] = inside[called].most_common(1)[0][0]
    return own


def matmul_instructions(hlo_text: str) -> set:
    """`trace.matmul_instructions` (convolutions, dots and the fusions that
    hold one) plus the Pallas kernels of the attention and expert scopes:
    a `custom-call` has no computation to look into, and these ARE the
    model's matrix products."""
    out = set(tracelib.matmul_instructions(hlo_text))
    for line in hlo_text.splitlines():
        m = _INST.match(line)
        if m and m.group(2) == "custom-call" and "tpu_custom_call" in line:
            name = _OP_NAME.search(line)
            if _scope_of(m.group(1), name.group(1) if name else "",
                         KERNEL_SCOPES):
                out.add(m.group(1))
    return out


def scope_seconds(trace: dict, scope_by_module: dict, train_modules) -> dict:
    """{scope: seconds} of device 0's leaf operations inside the train
    modules, by the scope of their instruction."""
    if not trace["devices"]:
        return {}
    dev = trace["devices"][min(trace["devices"])]
    modules = sorted(dev["modules"], key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = collections.Counter()
    for name, start, dur in tracelib.leaf_ops(dev):
        module = tracelib._module_of(modules, starts, start)
        if module not in train_modules:
            continue
        inst, _ = tracelib.op_of(name)
        scope = scope_by_module.get(module, {}).get(inst)
        if scope:
            out[scope] += dur / 1e9
    return dict(out)


# -- readers -------------------------------------------------------------------

def _share(run, *scopes):
    secs = run.counters.get("scope_seconds")
    busy = run.trace.get("train_module_busy_s")
    if not secs or not busy or not any(s in secs for s in scopes):
        return None
    return 100.0 * sum(secs.get(s, 0.0) for s in scopes) / busy


def attn_share(run):
    return _share(run, "attn_blockdiff")


def moe_share(run):
    return _share(run, "moe_route", "moe_experts")


def head_share(run):
    return _share(run, "lm_head")


def _sequences_per_chip(run) -> float:
    return run.window.total("images") / run.chips


def attn_roofline(run):
    """The allowed score and value products of the traced window's training
    steps, forward and backward, over the chip's peak FLOP/s times the
    device time under `attn_blockdiff` in the train modules.  Compute-bound
    (head size 128, thousands of keys a query).  Counted on the allowed
    quarter: a kernel that computes masked tiles reads low."""
    secs = (run.counters.get("scope_seconds") or {}).get("attn_blockdiff")
    if not secs:
        return None
    least = (lm_flops.attention_train_flops_per_sequence(run.config)
             * _sequences_per_chip(run) / run.peak["flops_per_s"])
    return 100.0 * least / secs


def expert_roofline(run):
    """The grouped products' FLOPs on the rows the program's counter says
    its experts computed, over the larger of the peak-FLOP time and the time
    to stream the held experts' weights, times the device time under
    `moe_experts` (the moves into and out of the dropless buffer are in that
    time and not in the FLOPs)."""
    secs = (run.counters.get("scope_seconds") or {}).get("moe_experts")
    rows = run.counters.get("moe_rows_local")
    if not secs or not rows:
        return None
    flop_s = (lm_flops.expert_train_flops_per_row(run.config) * rows
              / run.chips / run.peak["flops_per_s"])
    byte_s = (lm_flops.expert_weight_bytes_per_step(run.config)
              * run.window.total("steps") / run.peak["hbm_bytes_per_s"])
    return 100.0 * max(flop_s, byte_s) / secs


def local_rows_share(run):
    """Rows this chip's experts computed over what even routing would have
    sent here: 1.0 when the router does not lean."""
    rows, expected = (run.counters.get(k) for k in
                      ("moe_rows_local", "moe_rows_expected"))
    return rows / expected if rows and expected else None


def load_max_over_mean(run):
    """The fullest held expert's rows in any layer of any sequence of the
    window, over the mean rows of a held expert in a layer of a sequence."""
    fullest = [u["moe_rows_max_expert"] for u in run.window.units
               if "moe_rows_max_expert" in u]
    rows = run.counters.get("moe_rows_local")
    if not fullest or not rows:
        return None
    slots = (len(run.config["experts_held"])
             * run.config["num_hidden_layers"] * run.window.total("images"))
    return max(fullest) / (rows / slots)
