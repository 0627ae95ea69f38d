"""Serving subsystem: the unified fabric from request queue to kernels.

One stack, four layers (see core.py for the architecture notes):

* **core** — workload-agnostic :class:`ExecutionCore` + :class:`Workload`
  protocol (compile cache, pad-to-bucket, async in-flight window,
  watchdog, wall-union metrics, fault seams);
* **workloads** — :class:`ServingEngine` (sharded trigger paths) and
  :class:`LMEngine` (prefill + slot-recycling decode) instantiate the
  core;
* **resilience** — :class:`ResilientEngine` wraps a workload engine in
  the degradation ladder / shedding / health state machine; the
  :class:`Sentinel` (opt-in) adds the silent-corruption defense:
  golden canaries, terminal-rung shadow re-execution, canary-gated
  quarantine;
* **front-end** — :class:`ServingLoop` drains a live request queue
  through the :class:`DeadlineBatcher` into any of the above, with
  bounded-inflight backpressure and per-request :class:`RequestFuture`
  completion.
"""

from repro.serving.batcher import BatchPlan, DeadlineBatcher
from repro.serving.core import (
    ExecutionCore,
    PendingPlan,
    PendingResult,
    WatchdogTimeout,
    Workload,
)
from repro.serving.engine import ServingEngine, TriggerWorkload
from repro.serving.faults import (
    LOUD_SEAMS,
    SEAMS,
    SILENT_SEAMS,
    Fault,
    FaultInjector,
    InjectedFault,
    StaleCacheFn,
)
from repro.serving.lm import LMEngine, LMRequest, LMWorkload
from repro.serving.loop import RequestFuture, ServingLoop
from repro.serving.metrics import ServingMetrics, kgps, percentile
from repro.serving.resilient import (
    NonFiniteOutput,
    ResilientEngine,
    ResilientPending,
    ResilientPlan,
)
from repro.serving.sentinel import Sentinel, SentinelConfig
__all__ = [
    "LOUD_SEAMS",
    "SEAMS",
    "SILENT_SEAMS",
    "BatchPlan",
    "DeadlineBatcher",
    "ExecutionCore",
    "Fault",
    "FaultInjector",
    "InjectedFault",
    "LMEngine",
    "LMRequest",
    "LMWorkload",
    "NonFiniteOutput",
    "PendingPlan",
    "PendingResult",
    "RequestFuture",
    "ResilientEngine",
    "ResilientPending",
    "ResilientPlan",
    "Sentinel",
    "SentinelConfig",
    "ServingEngine",
    "ServingLoop",
    "ServingMetrics",
    "StaleCacheFn",
    "TriggerWorkload",
    "WatchdogTimeout",
    "Workload",
    "kgps",
    "percentile",
]
