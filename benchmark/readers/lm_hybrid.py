"""A hybrid decoder's layers (models/qwen3next.py, ops/gdn.py,
ops/attention.py `causal_attention`, ops/moe.py): where the train modules'
device time goes by named scope, the recurrence's and the causal kernels'
roofline shares, how full a held expert's group is.

The mechanism is `readers/lm.py`'s (the program's `jax.named_scope`s in
every instruction's `op_name`; the driver, `drivers/train_tokens_causal.py`,
classes the loaded modules' instructions, sums the traced time and leaves
it in `run.counters["scope_seconds"]`).  Here the splash-attention kernels,
which carry a name of their own and no op_name, belong to `attn_causal`.
Every reader returns None, never 0, when it finds nothing: an untraced run,
or a program without the scopes or the counters.
"""

from __future__ import annotations

from benchmark import hybrid_flops
from benchmark.readers import lm

KERNEL_PREFIXES = {"splash_": "attn_causal"}
GDN_SCOPES = ("attn_gdn", "gdn_conv", "gdn_recurrence")
MOE_SCOPES = ("moe_route", "moe_experts", "moe_shared")


def scope_instructions(hlo_text: str, scopes) -> dict:
    """`lm.scope_instructions` with this model's kernels known by name."""
    own = lm.scope_instructions(hlo_text, scopes)
    for line in hlo_text.splitlines():
        m = lm._INST.match(line)
        if not m:
            continue
        for prefix, scope in KERNEL_PREFIXES.items():
            if m.group(1).startswith(prefix) and scope in scopes:
                own[m.group(1)] = scope
    return own


def _scope_seconds(run, *scopes):
    secs = run.counters.get("scope_seconds") or {}
    found = [secs[s] for s in scopes if s in secs]
    return sum(found) if found and sum(found) > 0 else None


def _share(run, *scopes):
    secs, busy = _scope_seconds(run, *scopes), \
        run.trace.get("train_module_busy_s")
    return 100.0 * secs / busy if secs and busy else None


def linear_attn_share(run):
    return _share(run, *GDN_SCOPES)


def full_attn_share(run):
    return _share(run, "attn_causal")


def sparse_moe_share(run):
    return _share(run, *MOE_SCOPES)


def _sequences_per_chip(run) -> float:
    return run.window.total("images") / run.chips


def gdn_roofline(run):
    """The convolution's and the recurrence's required work in the traced
    window's training steps (forward + backward; `hybrid_flops`), the
    larger of its time at the chip's peak FLOP/s and at its peak bytes/s,
    over the device time under the scopes `gdn_conv` and `gdn_recurrence`
    in the train modules.  Memory-bound as counted (0.4 ms of operations
    against 2.3 ms of bytes a layer of a sequence at the published
    widths)."""
    secs = _scope_seconds(run, "gdn_conv", "gdn_recurrence")
    if not secs:
        return None
    n = _sequences_per_chip(run)
    least = max(
        hybrid_flops.gdn_train_flops_per_sequence(run.config) * n
        / run.peak["flops_per_s"],
        hybrid_flops.gdn_train_bytes_per_sequence(run.config) * n
        / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / secs


def causal_attn_roofline(run):
    """The causal score and value products of the traced window's training
    steps, forward and backward, over the chip's peak FLOP/s times the
    device time under `attn_causal` in the train modules.  Compute-bound
    (head size 256, thousands of keys a query)."""
    secs = _scope_seconds(run, "attn_causal")
    if not secs:
        return None
    least = (hybrid_flops.causal_attention_train_flops_per_sequence(run.config)
             * _sequences_per_chip(run) / run.peak["flops_per_s"])
    return 100.0 * least / secs


def rows_per_held_expert(run):
    """Rows a held expert computed in a layer of a sequence, the mean over
    the traced window: how full a group of the grouped products is against
    their row tile."""
    rows = run.counters.get("moe_rows_local")
    if not rows:
        return None
    return rows / (len(run.config["experts_held"])
                   * run.config["num_hidden_layers"]
                   * run.window.total("images"))
