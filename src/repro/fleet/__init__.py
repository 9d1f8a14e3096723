"""Fleet orchestration & observability (DESIGN.md §13).

The fleet subsystem makes failures *discovered* instead of scripted and
every run analyzable after the fact:

  * ``lease`` — heartbeat/lease failure detection on the worker's link
    model (missed lease ⇒ synthesized ``WorkerLeft(discovered=True)``,
    rejoin ⇒ ``WorkerJoined(discovered=True)`` with partial-shard-pull
    state catch-up), with batch expiry checks so 10k-worker fleets
    simulate in seconds;
  * ``scheduler`` — capability-aware batch/data-share assignment from
    heartbeat-reported speeds, applied via ``SetBatchFraction``;
  * ``metrics`` — the typed, append-only metrics stream (commit latency,
    push/pull bytes, shard staleness, search/drift/lease/churn events)
    shared by the simulator, the mesh backend, and the engine;
  * ``monitor`` — the PS-side ``FleetMonitor`` composing the three.
"""

from .lease import LeaseConfig, LeaseTracker, heartbeat_delay
from .metrics import (
    AssignRecord,
    CapabilityRecord,
    ChurnRecord,
    CommitRecord,
    DriftRecord,
    EvalRecord,
    JsonlSink,
    LeaseRecord,
    MetricRecord,
    MetricsLog,
    MetricsSink,
    PullRecord,
    SearchRecord,
    ServeRecord,
    SpanRecord,
    from_dict,
    load_jsonl,
    record_kinds,
    to_dict,
)
from .monitor import FleetConfig, FleetMonitor
from .scheduler import (
    DeviceScheduler,
    FleetAssignment,
    ProportionalScheduler,
    SqrtScheduler,
    UniformScheduler,
    get_scheduler,
    register_scheduler,
    scheduler_names,
)

__all__ = [
    # lease
    "LeaseConfig", "LeaseTracker", "heartbeat_delay",
    # monitor
    "FleetConfig", "FleetMonitor",
    # scheduler
    "DeviceScheduler", "FleetAssignment", "UniformScheduler",
    "ProportionalScheduler", "SqrtScheduler",
    "register_scheduler", "get_scheduler", "scheduler_names",
    # metrics
    "MetricRecord", "CommitRecord", "EvalRecord", "SearchRecord",
    "DriftRecord", "LeaseRecord", "ChurnRecord", "CapabilityRecord",
    "AssignRecord", "ServeRecord", "PullRecord", "SpanRecord",
    "MetricsSink", "MetricsLog", "JsonlSink",
    "record_kinds", "to_dict", "from_dict", "load_jsonl",
]
