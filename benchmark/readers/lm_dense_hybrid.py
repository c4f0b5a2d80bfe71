"""A dense hybrid decoder whose linear layers have negative eigenvalues
(models/olmo_hybrid.py, ops/gdn.py, ops/attention.py `causal_attention`):
the linear mixers' share of the train modules' device time, the delta
rule's and the front's kernels against their rooflines, and the share of
the linear layers' betas above 1.

The mechanism is `readers/lm.py`'s (the program's `jax.named_scope`s in
every instruction's `op_name`; `drivers/train_tokens_latent.py` classes the
loaded modules' instructions with `scope_instructions`, sums the traced
time and leaves it in `run.counters["scope_seconds"]`).  The
classing is `readers/lm_window.py`'s: the splash kernels by their mask
tables (here all causal), and the kernels the traffic file names by prefix
(`gdn_chunks_*` under `gdn_chunks`, `gdn_front_*` under `gdn_front`, apart
from the XLA work around them).  Every reader returns None, never 0, when it
finds nothing: an untraced run, or a program without the scopes or the
counters (the parent).
"""

from __future__ import annotations

from benchmark import dense_hybrid_flops
from benchmark.readers.lm_hybrid import (
    _scope_seconds, _sequences_per_chip, _share)
from benchmark.readers.lm_window import scope_instructions  # noqa: F401

LINEAR_SCOPES = ("attn_gdn", "gdn_conv", "gdn_recurrence", "gdn_chunks",
                 "gdn_front")


def negdelta_share(run):
    """The linear mixers (projections, front, recurrence, gated norm, the
    kernels and the work around them) over the train modules' device
    time."""
    return _share(run, *LINEAR_SCOPES)


def negdelta_roofline(run):
    """The recurrence's required work in the traced window's training steps
    at the published head sizes (`dense_hybrid_flops`), the larger of its
    time at the chip's peak FLOP/s and at its peak bytes/s, over the device
    time of the delta rule's kernels (`gdn_chunks`) in the train modules.
    The forward kernel a layer's recomputation runs again is in the time
    and not in the work, so it reads low."""
    secs = _scope_seconds(run, "gdn_chunks")
    if not secs:
        return None
    n = _sequences_per_chip(run)
    least = max(
        dense_hybrid_flops.recurrence_train_flops_per_sequence(run.config)
        * n / run.peak["flops_per_s"],
        dense_hybrid_flops.recurrence_train_bytes_per_sequence(run.config)
        * n / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / secs


def negdelta_front_roofline(run):
    """The front's required bytes in the traced window's training steps
    (`dense_hybrid_flops`) at the chip's peak bytes/s, over the device time
    of the front's kernels (`gdn_front`) in the train modules."""
    secs = _scope_seconds(run, "gdn_front")
    if not secs:
        return None
    least = (dense_hybrid_flops.front_train_bytes_per_sequence(run.config)
             * _sequences_per_chip(run) / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / secs


def neg_eig_share(run):
    """The linear layers' betas above 1 over all of them in the traced
    window (every held linear layer, position and head): the share of
    transitions I - beta k k^T with a negative eigenvalue."""
    over = run.counters.get("gdn_beta_over_one")
    entries = run.counters.get("gdn_beta_entries")
    if over is None or not entries:
        return None
    return over / entries
