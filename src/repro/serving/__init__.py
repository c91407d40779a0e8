"""Discrete-event replicated serving: arrivals -> queueing master -> engine."""

from repro.serving.arrivals import (
    ArrivalProcess,
    DeterministicArrivals,
    MMPPArrivals,
    MultiTenantArrivals,
    PoissonArrivals,
    TraceArrivals,
    make_arrivals,
)
from repro.serving.engine import (
    ReplicatedServingEngine,
    RequestStats,
    ServeEngineConfig,
)
from repro.serving.queueing import (
    AdmissionQueue,
    BatchJob,
    ClonePolicy,
    EventDrivenMaster,
    HedgedDispatchPolicy,
    NoOpPolicy,
    QueuePolicy,
    RelaunchPolicy,
    Request,
    StragglerPolicy,
    partition_requests,
)

__all__ = [
    "AdmissionQueue",
    "ArrivalProcess",
    "BatchJob",
    "ClonePolicy",
    "DeterministicArrivals",
    "EventDrivenMaster",
    "HedgedDispatchPolicy",
    "MMPPArrivals",
    "MultiTenantArrivals",
    "NoOpPolicy",
    "PoissonArrivals",
    "QueuePolicy",
    "RelaunchPolicy",
    "ReplicatedServingEngine",
    "Request",
    "RequestStats",
    "ServeEngineConfig",
    "StragglerPolicy",
    "TraceArrivals",
    "make_arrivals",
    "partition_requests",
]
