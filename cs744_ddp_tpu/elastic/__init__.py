"""Elastic training: checkpoint-based world-resize resume (round 6).

A run interrupted at world=N resumes at world=M with re-sharded data order
and pinned math.  Layers:

* ``protocol``     — resume planning (weak/strong scaling), shrink
                     planning, per-rank data-order keys, and the
                     backward-compat ``world_of`` default;
* ``step_elastic`` — the strong-scaling microshard window whose update is
                     bitwise world-invariant (CI-pinned at world 1→2→4);
* ``coordinator``  — membership + the retry → shrink → single-rank
                     degradation ladder over rank-level chaos
                     (``ft/chaos.py``: rank_death, slow_rank,
                     coordinator_loss);
* ``straggler``    — EWMA-vs-peers step-time outlier detection over the
                     per-rank gauges the trainer emits.

The re-exports below are eager and cheap: nothing here loads orbax.  The
coordinator reads checkpoint metadata through the JSON readers of
``train/checkpoint.py``; ``orbax.checkpoint`` is imported only where a
``CheckpointManager`` is built, i.e. only when ``--checkpoint-dir`` is given
(which ``--elastic`` requires).
"""

from .coordinator import ElasticCoordinator                     # noqa: F401
from .protocol import (ElasticConfig, PROTOCOLS, ResumePlan,    # noqa: F401
                       flat_meta, plan_resume, plan_shrink,
                       rank_data_keys, validate_rank_keys, world_of)
from .step_elastic import (make_elastic_train_window,           # noqa: F401
                           tree_combine_mean)
from .straggler import StragglerDetector                        # noqa: F401

__all__ = [
    "ElasticConfig", "ElasticCoordinator", "PROTOCOLS", "ResumePlan",
    "StragglerDetector", "flat_meta", "make_elastic_train_window",
    "plan_resume", "plan_shrink", "rank_data_keys", "tree_combine_mean",
    "validate_rank_keys", "world_of",
]
