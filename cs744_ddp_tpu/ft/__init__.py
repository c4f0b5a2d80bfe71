"""Fault-tolerance layer: chaos injection, staging supervision, non-finite
step guard, and preemption-safe mid-epoch resume.

Everything is opt-in through one ``FTConfig`` handed to ``Trainer``; the
default (``ft=None``) leaves every hot path byte-identical to the
unsupervised build — the chaos plan is the stateless ``NULL_CHAOS``
singleton and the guard is never compiled into the step programs.
"""

from __future__ import annotations

from typing import Any, NamedTuple

from .chaos import (NULL_CHAOS, PUBLISH_SITES, RANK_SITES, REPLICA_SITES,
                    ChaosError, ChaosPlan, NullChaos, RankDeathError, SITES)
from .guard import POLICIES, NonFiniteError
from .preempt import PreemptedError, PreemptionGuard
from .supervisor import (StagingStalled, Watchdog, batch_checksums,
                         call_with_retry, verify_checksums)


class FTConfig(NamedTuple):
    """Fault-tolerance knobs (defaults are production-shaped; tests shrink
    the timeouts).

    nonfinite         : "off" | "halt" | "skip" | "restore" step-guard policy.
    chaos             : ChaosPlan (or NULL_CHAOS) of deterministic injections.
    put_timeout_s     : watchdog deadline for one chunk device_put (+ arena
                        fence wait); overruns are counted, not interrupted.
    put_retries       : total attempts for a failing chunk put.
    backoff_base_s    : exponential backoff base between put retries.
    stall_timeout_s   : consumer-side deadline with no staged item arriving
                        while the producer looks alive -> treated as a
                        producer failure (restart once, then degrade).
    producer_restarts : producer restart attempts before degrading to the
                        synchronous per-batch staging path.
    verify_chunks     : crc32-verify staged rows right before each put
                        (auto-enabled when the chaos plan corrupts slots).
    degrade_staging   : start in the degraded synchronous staging mode
                        (testing knob — exercises the fallback).
    slow_rank_stall_s : stall injected per ``slow_rank`` chaos entry and
                        attributed to the target rank's step-time gauge
                        (elastic/straggler.py must flag it).
    """

    nonfinite: str = "off"
    chaos: Any = NULL_CHAOS
    put_timeout_s: float = 30.0
    put_retries: int = 3
    backoff_base_s: float = 0.05
    stall_timeout_s: float = 120.0
    producer_restarts: int = 1
    verify_chunks: bool = False
    degrade_staging: bool = False
    slow_rank_stall_s: float = 0.25


__all__ = [
    "FTConfig", "ChaosPlan", "ChaosError", "NullChaos", "NULL_CHAOS", "SITES",
    "PUBLISH_SITES", "RANK_SITES", "REPLICA_SITES", "RankDeathError",
    "POLICIES", "NonFiniteError", "PreemptedError", "PreemptionGuard",
    "StagingStalled", "Watchdog", "call_with_retry", "batch_checksums",
    "verify_checksums",
]
