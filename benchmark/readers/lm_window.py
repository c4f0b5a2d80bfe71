"""A decoder with sliding-window and full softmax layers (models/mellum2.py,
ops/attention.py `window_attention` and `causal_attention`, ops/moe.py):
where the train modules' device time goes by named scope, and the window
kernels' roofline share.

The mechanism is `readers/lm.py`'s (the program's `jax.named_scope`s in
every instruction's `op_name`; the driver, `drivers/train_tokens_latent.py`,
classes the loaded modules' instructions with `scope_instructions` here,
sums the traced time and leaves it in `run.counters["scope_seconds"]`).

The splash-attention kernels carry no `op_name` and one name whatever their
mask (`splash_mqa_fwd...`, `..._dq...`, `..._dkv...`), so a kernel is told
by its mask table: the first operand whose shape the instruction's
`operand_layout_constraints` give as an integer [heads, blocks, blocks].
Under the causal rule a table row lists every block of its side of the
grid, so both block counts are equal at the square tiles the kernels take;
under the window a row lists the band's few: the table's two block counts
differ, and the kernel is the window's (`window_core`).  A table the text
does not show leaves the kernel unclassed.  Every reader returns None,
never 0, when it finds nothing: an untraced run, or a program without the
scopes (the parent).
"""

from __future__ import annotations

import re

from benchmark import swa_flops
from benchmark.readers import lm
from benchmark.readers.lm_hybrid import (
    _scope_seconds, _sequences_per_chip, _share)

WINDOW_SCOPES = ("attn_window", "window_core")
_TABLE = re.compile(r"operand_layout_constraints=\{[su](?:8|16|32)"
                    r"\[(\d+),(\d+),(\d+)\]")


def splash_rule(line: str) -> str | None:
    """`window_core` or `attn_causal` for a splash kernel's line of module
    text, by its mask table's shape; None if the line shows none."""
    m = _TABLE.search(line)
    if not m:
        return None
    _, rows, cols = (int(g) for g in m.groups())
    return "attn_causal" if rows == cols else "window_core"


def scope_instructions(hlo_text: str, scopes, kernel_scopes: dict) -> dict:
    """`lm.scope_instructions`, the splash kernels classed by their mask
    tables and the kernels of `kernel_scopes` ({prefix of an instruction's
    name: scope}) by name."""
    own = lm.scope_instructions(hlo_text, scopes)
    for line in hlo_text.splitlines():
        m = lm._INST.match(line)
        if not m:
            continue
        name = m.group(1)
        scope = splash_rule(line) if name.startswith("splash_") else None
        for prefix, named in kernel_scopes.items():
            if name.startswith(prefix):
                scope = named
        if scope in scopes:
            own[name] = scope
    return own


def window_attn_share(run):
    """The sliding layers' attention (projections, rotary and the window
    kernels) over the train modules' device time."""
    return _share(run, *WINDOW_SCOPES)


def window_attn_roofline(run):
    """The band's score and value products of the traced window's training
    steps, forward and backward (`swa_flops`), over the chip's peak FLOP/s
    times the device time under `window_core` (the three window kernels)
    in the train modules.  Compute-bound.  The forward pass a layer's
    recomputation runs again is in the time and not in the operations, so
    it reads low."""
    secs = _scope_seconds(run, "window_core")
    if not secs:
        return None
    least = (swa_flops.window_attention_train_flops_per_sequence(run.config)
             * _sequences_per_chip(run) / run.peak["flops_per_s"])
    return 100.0 * least / secs
