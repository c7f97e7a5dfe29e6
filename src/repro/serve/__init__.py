"""Production serving tier — the closed loop in front of the engine.

The engine (``core/engine.py``) answers queries as fast as the hardware
allows; this package decides *which* queries reach it and *when*, the
difference between a benchmark loop and a service.  The pipeline is

    arrivals -> admission -> bounded queue -> deadline batcher -> engine

with each stage a module: :mod:`clock` (virtual/wall time so every policy
is testable without sleeps), :mod:`workload` (open/closed-loop
Poisson+Zipf request generators, timed edge writes), :mod:`admission`
(token-bucket throttling + cache-aware bypass), :mod:`queue` (bounded FIFO
load-leveling with typed ``Overload`` rejections), :mod:`batcher`
(deadline-aware batch formation against the planner's cost estimates),
:mod:`degrade` (hysteretic graceful degradation under sustained queue
growth) and :mod:`service` (the loop tying them together, with a write
lane that applies edge deltas between micro-batches).
``launch/ppr_serve.py`` is the CLI over this package; docs/SERVING.md
has the architecture and the overload state machine.
"""

from .admission import AdmissionController, AdmissionPolicy, TokenBucket
from .batcher import CostModel, DeadlineBatcher
from .clock import Clock, VirtualClock, WallClock
from .degrade import DegradeLevel, DegradePolicy
from .metrics import latency_summary
from .queue import BoundedQueue, Overload
from .service import PPRService, Served, ServiceConfig, ServiceReport, WriteAck
from .workload import (
    ClosedLoopWorkload,
    OpenLoopWorkload,
    Request,
    Write,
    zipf_seeds,
)

__all__ = [
    "AdmissionController",
    "AdmissionPolicy",
    "BoundedQueue",
    "Clock",
    "ClosedLoopWorkload",
    "CostModel",
    "DeadlineBatcher",
    "DegradeLevel",
    "DegradePolicy",
    "OpenLoopWorkload",
    "Overload",
    "PPRService",
    "Request",
    "Served",
    "ServiceConfig",
    "ServiceReport",
    "TokenBucket",
    "VirtualClock",
    "WallClock",
    "Write",
    "WriteAck",
    "latency_summary",
    "zipf_seeds",
]
