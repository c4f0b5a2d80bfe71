"""Single-chip serving fast path (the ROADMAP north star's other half).

The training side of this repo is evidence-closed; this package is the
first measured serving surface: an AOT-compiled executable ladder over a
fixed set of batch buckets (``engine``), a bounded-queue micro-batcher
that coalesces concurrent requests into the largest ready bucket
(``batcher``), double-buffered uint8 host staging reusing the training
arena (``ingest``), a warm-start executable cache so a restarted server
skips XLA compile (``cache``), and a seeded open-loop demo/measurement
driver (``demo``).

Round 9 grows this into a serving TIER: a continuous-batching SLO
scheduler with priority-tiered admission and deterministic load shedding
(``scheduler``), device-pinned engine replicas with chaos hooks
(``replica``) behind a least-loaded router with death failover
(``router``), and a socket front-end speaking a length-prefixed binary
protocol (``frontend``).

Round 14 makes the dispatch a PIPELINE: the engine splits issue from
completion (``infer_counts_async``/``complete``) and the scheduler keeps
``PIPELINE_SLOTS`` (= 2, the staging arena depth) dispatches in flight
per replica, so batch N+1's host work overlaps batch N's device compute
and the device never idles between buckets.
"""

from .batcher import MicroBatcher, QueueFull, coalesce, plan_batches
from .cache import ExecutableCache
from .engine import BUCKETS, DispatchHandle, InferenceEngine
from .frontend import FrontendClient, LoopbackClient, ServingFrontend
from .ingest import StagedIngest
from .replica import EngineReplica
from .router import ReplicaRouter
from .scheduler import (PIPELINE_SLOTS, Reply, SchedRequest, ServiceModel,
                        SLOScheduler, admit, cost_model_weights,
                        make_request, plan_continuous, plan_drain,
                        virtual_requests)

__all__ = [
    "BUCKETS", "DispatchHandle", "EngineReplica", "ExecutableCache",
    "FrontendClient", "InferenceEngine", "LoopbackClient", "MicroBatcher",
    "PIPELINE_SLOTS", "QueueFull", "Reply", "ReplicaRouter", "SLOScheduler",
    "SchedRequest", "ServiceModel", "ServingFrontend", "StagedIngest",
    "admit", "coalesce", "cost_model_weights", "make_request", "plan_batches",
    "plan_continuous", "plan_drain", "virtual_requests",
]
