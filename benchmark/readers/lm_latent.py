"""A latent-attention decoder with a hyper-connected residual
(models/xing4.py, ops/mla.py, ops/hyper.py, ops/moe.py): where the train
modules' device time goes by named scope, the attention kernels' and the
stream mixers' roofline shares, how far the Sinkhorn iterations stopped
from the manifold.

The mechanism is `readers/lm.py`'s (the program's `jax.named_scope`s in
every instruction's `op_name`; the driver, `drivers/train_tokens_latent.py`,
classes the loaded modules' instructions with `scope_instructions` here,
sums the traced time and leaves it in `run.counters["scope_seconds"]`).
The scopes and the kernels known by name are the traffic file's, not
constants of this module.  Every reader returns None, never 0, when it
finds nothing: an untraced run, or a program without the scopes or the
counters.
"""

from __future__ import annotations

from benchmark import latent_flops
from benchmark.readers import lm
from benchmark.readers.lm_hybrid import (
    _scope_seconds, _sequences_per_chip, _share)

MLA_SCOPES = ("attn_mla", "mla_core")
MHC_SCOPES = ("mhc_mix", "mhc_sinkhorn")
FFN_SCOPES = ("mlp_dense", "moe_route", "moe_experts", "moe_shared")


def scope_instructions(hlo_text: str, scopes, kernel_scopes: dict) -> dict:
    """`lm.scope_instructions` with the kernels of `kernel_scopes` ({prefix
    of an instruction's name: scope}) known by name."""
    own = lm.scope_instructions(hlo_text, scopes)
    for line in hlo_text.splitlines():
        m = lm._INST.match(line)
        if not m:
            continue
        for prefix, scope in kernel_scopes.items():
            if m.group(1).startswith(prefix) and scope in scopes:
                own[m.group(1)] = scope
    return own


def mla_share(run):
    return _share(run, *MLA_SCOPES)


def mhc_share(run):
    return _share(run, *MHC_SCOPES)


def ffn_share(run):
    return _share(run, *FFN_SCOPES)


def mla_attn_roofline(run):
    """The causal score and value products of the traced window's training
    steps, forward and backward, at keys of 192 beside values of 128, over
    the chip's peak FLOP/s times the device time under `mla_core` (the
    three attention kernels) in the train modules.  Compute-bound.  The
    forward pass a layer's recomputation runs again is in the time and not
    in the operations, so it reads low."""
    secs = _scope_seconds(run, "mla_core")
    if not secs:
        return None
    least = (latent_flops.mla_attention_train_flops_per_sequence(run.config)
             * _sequences_per_chip(run) / run.peak["flops_per_s"])
    return 100.0 * least / secs


def mhc_roofline(run):
    """The hyper-connections' required work in the traced window's
    training steps (`latent_flops`), the larger of its time at the chip's
    peak bytes/s and at its peak FLOP/s (memory-bound as counted: 1.4 ms a
    sublayer of a sequence against 0.05), over the device time under
    `mhc_mix` and, inside it, `mhc_sinkhorn`."""
    secs = _scope_seconds(run, *MHC_SCOPES)
    if not secs:
        return None
    n = _sequences_per_chip(run)
    least = max(
        latent_flops.mhc_train_bytes_per_sequence(run.config) * n
        / run.peak["hbm_bytes_per_s"],
        latent_flops.mhc_train_flops_per_sequence(run.config) * n
        / run.peak["flops_per_s"])
    return 100.0 * least / secs


def res_gap(run):
    """The largest |row sum - 1| or |column sum - 1| of any position's
    H_res in the window (the objective's column `mhc_res_gap`, a maximum,
    from the units' records)."""
    gaps = [u["mhc_res_gap"] for u in run.window.units if "mhc_res_gap" in u]
    return max(gaps) if gaps else None
