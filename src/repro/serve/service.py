"""The serving loop: admission → bounded queue → deadline batcher → engine.

:class:`PPRService` is a single-threaded discrete-event loop over an
injected :class:`~repro.serve.clock.Clock`; with a
:class:`~repro.serve.clock.VirtualClock` and a fixed
:class:`~repro.serve.batcher.CostModel` the whole service — throttling,
shedding, batching, degradation — is a deterministic simulation (no
wall-clock sleeps anywhere), and with a
:class:`~repro.serve.clock.WallClock` the identical loop paces and
measures a real service.  Batches drain through
``engine.run(TopKQuery(...))`` — the engine's own planned path, so
answers served through the tier are **bit-identical** to direct
``engine.run`` whenever no degradation is active (the tier decides when
and what to run, never how; tests/test_serving.py pins it).

Writes share the loop (the write lane): ``serve(reads, writes=...)``
takes timed edge deltas (:class:`~repro.serve.workload.Write`), and
before each dispatch the loop applies every write that is due, in arrival
order, as ``engine.run(DeltaQuery(..., rank=False))``.  A write is
acknowledged once its layout is on the device
(:class:`WriteAck` in ``ServiceReport.writes``).  The guarantee is
read-your-writes per micro-batch: a micro-batch dispatched after a write
was acknowledged answers on that version or a newer one, and all answers
of one micro-batch come from one version, which ``Served.graph_version``
states.

Latency is accounted **per request** (arrival to completion, queue wait
included), not per batch: ``Served.t_dispatch`` splits it into the wait
in the queue and the service of the request's micro-batch.  Under a
running ``jax.profiler`` trace the loop's stages show as host spans:
``serve.ingest``, ``serve.batch`` (one per micro-batch, with its index)
and, inside it, ``serve.assemble``; and ``serve.write``, one per write,
with the engine's ``engine.delta.apply`` inside.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from .admission import AdmissionController, AdmissionPolicy
from .batcher import CostModel, DeadlineBatcher
from .clock import Clock, WallClock
from .degrade import DegradePolicy
from .metrics import latency_summary
from .queue import BoundedQueue, Overload
from .workload import Request

__all__ = [
    "ServiceConfig",
    "PPRService",
    "Served",
    "ServiceReport",
    "WriteAck",
    "EngineExecutor",
    "NullExecutor",
]


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Static description of one serving tier instance.

    ``time_source`` selects how batch service time is charged to the
    clock: ``"wall"`` (measured; the real-service mode) or ``"model"``
    (predicted from plan cost × :class:`CostModel`; the deterministic
    simulation mode — required with a virtual clock when determinism
    matters).  ``seconds_per_unit`` seeds the cost model; ``None`` defers
    to :meth:`PPRService.calibrate` (one measured warmup batch).
    """

    batch_size: int = 16
    k: int = 5
    queue_cap: int = 64
    admission: AdmissionPolicy = dataclasses.field(default_factory=AdmissionPolicy)
    degrade: Optional[DegradePolicy] = None
    cfg: Any = None  # BatchConfig; None = engine defaults
    safety_s: float = 0.0
    time_source: str = "wall"
    seconds_per_unit: Optional[float] = None
    base_s: float = 0.0

    def __post_init__(self):
        if self.time_source not in ("wall", "model"):
            raise ValueError(f"time_source must be 'wall' or 'model', got {self.time_source!r}")
        if int(self.batch_size) < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")


@dataclasses.dataclass
class Served:
    """One completed request: timing, fidelity and (optionally) values.

    ``t_dispatch`` is the service clock's time when the request's
    micro-batch went to the executor: ``t_dispatch - req.t_arrival`` is
    its wait in the queue and ``latency_s`` that wait plus
    ``t_done - t_dispatch``.  ``graph_version`` is the engine's edge-set
    version its micro-batch ran on.
    """

    req: Request
    t_done: float
    latency_s: float
    deadline_met: bool
    t_dispatch: float = None
    level: int = 0
    degraded: bool = False
    cache_hit: bool = False
    indices: Any = None
    scores: Any = None
    graph_version: Optional[int] = None


@dataclasses.dataclass
class WriteAck:
    """One write the lane applied: when it arrived, when the lane began
    applying it and when its layout was on the device (all on the service
    clock), the version it made, and whether it laid the graph out again
    (None off the live layout)."""

    t_arrival: float
    t_start: float
    t_ack: float
    graph_version: int
    relayouts: Optional[int] = None


@dataclasses.dataclass
class ServiceReport:
    """Everything one :meth:`PPRService.serve` run produced."""

    served: List[Served]
    shed: List[Overload]
    batches: List[tuple]  # (service_s, n_real, level)
    t_start: float
    t_end: float
    queue_stats: dict
    admission_stats: dict
    batcher_stats: dict
    degrade_stats: Optional[dict]
    writes: List[WriteAck] = dataclasses.field(default_factory=list)

    def summary(self) -> dict:
        """Aggregate view (serving logs, the benchmark record)."""
        n_served, n_shed = len(self.served), len(self.shed)
        lat_ms = np.asarray([s.latency_s for s in self.served]) * 1e3
        dur = max(self.t_end - self.t_start, 1e-12)
        n_deg = sum(1 for s in self.served if s.degraded)
        n_miss = sum(1 for s in self.served if not s.deadline_met)
        n_hit = sum(1 for s in self.served if s.cache_hit)
        out = dict(
            offered=n_served + n_shed,
            served=n_served,
            shed=n_shed,
            shed_frac=n_shed / max(n_served + n_shed, 1),
            qps=n_served / dur,
            duration_s=dur,
            degraded_frac=n_deg / max(n_served, 1),
            deadline_miss_frac=n_miss / max(n_served, 1),
            cache_bypass_frac=n_hit / max(n_served, 1),
            batches=len(self.batches),
            latency=latency_summary(lat_ms),
            queue=self.queue_stats,
            admission=self.admission_stats,
            batcher=self.batcher_stats,
            degrade=self.degrade_stats,
        )
        return out


class EngineExecutor:
    """Default executor: one ``engine.run(TopKQuery)`` per micro-batch."""

    def __call__(self, engine, sources, k: int, cfg):
        import jax

        from ..core import TopKQuery

        env = engine.run(TopKQuery(sources=sources, k=int(k), cfg=cfg))
        jax.block_until_ready(env.result.scores)
        return env


class NullExecutor:
    """No-op executor for pure queueing simulation (load sweeps where
    only the timing dynamics matter, not the answers)."""

    def __call__(self, engine, sources, k: int, cfg):
        return None


class PPRService:
    """Closed-loop serving tier over one prepared :class:`PageRankEngine`.

    The loop is event-driven: ingest arrivals due now, dispatch when the
    batcher says so (full batch, deadline trigger, or final flush), else
    sleep exactly until the next event.  All state (bucket, queue,
    batcher, degrade ladder) advances on the injected clock only.
    """

    def __init__(
        self,
        engine,
        config: Optional[ServiceConfig] = None,
        *,
        clock: Optional[Clock] = None,
        executor=None,
    ):
        from ..core import BatchConfig

        self.engine = engine
        self.config = config or ServiceConfig()
        self.clock = clock or WallClock()
        self.executor = executor or EngineExecutor()
        cfg = self.config.cfg
        if cfg is None:
            cfg = BatchConfig(dtype=engine.engine_plan.dtype, c=engine.engine_plan.c)
        self.cfg = cfg
        self.admission = AdmissionController(self.config.admission, engine)
        self.queue = BoundedQueue(self.config.queue_cap)
        self.degrade = self.config.degrade
        if engine.live:
            self._check_rungs_take_writes()
        # per-level serving state: (engine, cfg, plan-cost units); level 0
        # is the prepared engine at full fidelity.
        self._levels: dict = {}
        units0 = self._level_state(0)[2]
        spu = self.config.seconds_per_unit
        calibrated = spu is not None
        self.cost_model = CostModel(
            seconds_per_unit=spu if calibrated else 1e-9,
            base_s=self.config.base_s,
            # wall serving self-calibrates; model mode keeps the fixed
            # calibration that makes the simulation deterministic.
            ewma=0.3 if self.config.time_source == "wall" else 0.0,
        )
        self._calibrated = calibrated
        self.batcher = DeadlineBatcher(
            self.config.batch_size,
            self.cost_model,
            batch_cost_units=units0,
            safety_s=self.config.safety_s,
        )

    def _check_rungs_take_writes(self):
        """Refuse a rung that serves a fallback engine of its own: the
        writes reach only ``self.engine``, so it would answer on a graph
        they never change."""
        rungs = self.degrade.levels if self.degrade is not None else ()
        other = sorted({r.step_impl for r in rungs if r.step_impl
                        and r.step_impl != self.engine.step_impl})
        if other:
            raise ValueError(
                f"degrade rungs with step_impl {other} serve a fallback "
                f"engine that edge writes never reach; an engine that takes "
                f"writes can only degrade by xi_scale")

    # ------------------------------------------------------------------ #
    # per-level engines/configs (the degrade ladder's serving state)
    # ------------------------------------------------------------------ #
    def _level_state(self, level: int):
        state = self._levels.get(level)
        if state is not None:
            return state
        from ..core import TopKQuery

        if level == 0 or self.degrade is None:
            eng, cfg = self.engine, self.cfg
        else:
            rung = self.degrade.levels[level]
            cfg = dataclasses.replace(
                self.cfg, xi=self.cfg.xi * rung.xi_scale, tol=self.cfg.tol * rung.xi_scale
            )
            eng = self.engine
            if rung.step_impl and rung.step_impl != self.engine.step_impl:
                # a cheaper backend: prepare a fallback engine once, on
                # the SAME graph object (shared layout caches), through
                # the same capability registry the planner uses.
                from ..core import EnginePlan, PageRankEngine

                plan = self.engine.engine_plan
                eng = PageRankEngine(
                    self.engine.graph,
                    EnginePlan(step_impl=rung.step_impl, c=plan.c, dtype=plan.dtype),
                )
        probe = np.zeros(self.config.batch_size, dtype=np.int64)
        units = eng.plan(TopKQuery(sources=probe, k=self.config.k, cfg=cfg)).cost
        state = (eng, cfg, float(units))
        self._levels[level] = state
        return state

    # ------------------------------------------------------------------ #
    # calibration — one measured warmup batch outside the served window
    # ------------------------------------------------------------------ #
    def calibrate(self, seeds=None) -> dict:
        """Run one warmup micro-batch (compile + measure) and seed the
        cost model with the observed seconds-per-unit.  Returns the
        measurement; the CLI prints it as the compile/warmup line.  A wall
        clock restarts afterwards, so the workload's schedule (which starts
        at 0) and its latencies leave the warmup out."""
        B = self.config.batch_size
        if seeds is None:
            seeds = np.zeros(B, dtype=np.int64)
        seeds = np.asarray(seeds)[:B]
        if len(seeds) < B:
            fill = seeds[-1] if len(seeds) else 0
            seeds = np.concatenate([seeds, np.full(B - len(seeds), fill)])
        eng, cfg, units = self._level_state(0)
        self.executor(eng, seeds, self.config.k, cfg)  # compile pass
        t0 = time.perf_counter()
        self.executor(eng, seeds, self.config.k, cfg)
        wall = time.perf_counter() - t0
        if wall > 0 and units > 0:
            self.cost_model.seconds_per_unit = wall / units
            self._calibrated = True
        spu = self.cost_model.seconds_per_unit
        if isinstance(self.clock, WallClock):
            self.clock.restart()
        return dict(warm_batch_s=wall, cost_units=units, seconds_per_unit=spu)

    # ------------------------------------------------------------------ #
    # the event loop
    # ------------------------------------------------------------------ #
    def serve(self, workload, writes=()) -> ServiceReport:
        """Serve ``workload``'s reads until it drains.  ``writes`` is a
        sequence of :class:`~repro.serve.workload.Write`, applied through
        the write lane in arrival order; writes still to come when the
        reads drain stay unapplied."""
        pending = collections.deque(sorted(writes, key=lambda w: w.t_arrival))
        if pending:
            self._check_rungs_take_writes()
        if not self._calibrated:
            self.calibrate()
        served: List[Served] = []
        shed: List[Overload] = []
        batches: List[tuple] = []
        acks: List[WriteAck] = []
        t_start = self.clock.now()
        while True:
            if pending:
                self._apply_writes(pending, acks)
            now = self.clock.now()
            for req in workload.take_due(now):
                self._ingest(req, now, workload, served, shed)
            flush = workload.next_time() == float("inf")
            reason = self.batcher.should_dispatch(self.queue, now, flush=flush)
            if reason is not None:
                self._dispatch(workload, served, batches)
                continue
            t_next = min(workload.next_time(), self.batcher.trigger_time(self.queue))
            if t_next == float("inf"):
                break  # drained: no arrivals, nothing queued
            self.clock.sleep_until(t_next)
        queue_stats = dict(
            enqueued=self.queue.enqueued,
            rejected=self.queue.rejected,
            max_depth=self.queue.max_depth,
            capacity=self.queue.capacity,
        )
        degrade_stats = self.degrade.stats() if self.degrade is not None else None
        return ServiceReport(
            served=served,
            shed=shed,
            batches=batches,
            t_start=t_start,
            t_end=self.clock.now(),
            queue_stats=queue_stats,
            admission_stats=self.admission.stats(),
            batcher_stats=self.batcher.stats(),
            degrade_stats=degrade_stats,
            writes=acks,
        )

    # ------------------------------------------------------------------ #
    # stages
    # ------------------------------------------------------------------ #
    def _apply_writes(self, pending: collections.deque,
                      acks: List[WriteAck]):
        """The write lane: every write due now, in arrival order, each
        acknowledged once the engine has its layout on the device (the
        engine blocks on it before ``run`` returns)."""
        from ..core import DeltaQuery

        while pending and pending[0].t_arrival <= self.clock.now():
            w = pending.popleft()
            t_start = self.clock.now()
            with TraceAnnotation("serve.write"):
                env = self.engine.run(DeltaQuery(add=w.add, remove=w.remove,
                                                 rank=False))
            acks.append(WriteAck(
                t_arrival=w.t_arrival, t_start=t_start, t_ack=self.clock.now(),
                graph_version=self.engine.graph_version,
                relayouts=env.result.relayouts))

    def _ingest(self, req: Request, now: float, workload, served, shed):
        with TraceAnnotation("serve.ingest"):
            decision = self.admission.admit(req, now, self.cfg)
            if isinstance(decision, Overload):
                shed.append(decision)
                workload.on_reject(req, now)
                return
            if decision == "bypass":
                self._serve_bypass(req, workload, served)
                return
            ov = self.queue.offer(req, now, retry_after_s=self.batcher.predicted_batch_s())
            if ov is not None:
                shed.append(ov)
                workload.on_reject(req, now)

    def _serve_bypass(self, req: Request, workload, served):
        """Fresh cache entry: answer now, skipping queue and batcher.

        A full-hit micro-batch performs no device pass (core/cache.py),
        so the only cost is assembly — charged as zero model time (wall
        time passes on its own under a WallClock)."""
        eng, cfg, _ = self._level_state(0)
        t_dispatch = self.clock.now()
        version = eng.graph_version
        env = self.executor(eng, np.asarray([req.seed]), self.config.k, cfg)
        t_done = self.clock.now()
        if env is not None:
            indices = np.asarray(env.result.indices[0])
            scores = np.asarray(env.result.scores[0])
        else:
            indices = scores = None
        s = Served(
            req=req,
            t_done=t_done,
            latency_s=t_done - req.t_arrival,
            deadline_met=t_done <= req.deadline,
            t_dispatch=t_dispatch,
            level=0,
            degraded=False,
            cache_hit=True,
            indices=indices,
            scores=scores,
            graph_version=version,
        )
        served.append(s)
        workload.on_complete(req, t_done)

    def _dispatch(self, workload, served, batches):
        with TraceAnnotation("serve.batch", batch=len(batches)):
            self._run_batch(workload, served, batches)

    def _run_batch(self, workload, served, batches):
        reqs = self.queue.pop_batch(self.config.batch_size)
        # the degrade signal is the backlog LEFT BEHIND by this batch: a
        # healthy service pops its batch and leaves ~nothing (so depth
        # before the pop — always >= B on a full dispatch — would sit in
        # the dead band forever and never recover)
        level = self.degrade.observe(self.queue.depth) if self.degrade is not None else 0
        eng, cfg, units = self._level_state(level)
        n_real = len(reqs)
        sources = np.asarray([r.seed for r in reqs], dtype=np.int64)
        if n_real < self.config.batch_size:
            # pad the tail to the compiled [B, n] shape
            pad = np.full(self.config.batch_size - n_real, sources[-1], dtype=np.int64)
            sources = np.concatenate([sources, pad])
        t_dispatch = self.clock.now()
        version = eng.graph_version
        t0 = time.perf_counter()
        env = self.executor(eng, sources, self.config.k, cfg)
        wall = time.perf_counter() - t0
        if self.config.time_source == "wall":
            service_s = wall
            self.cost_model.observe(units, wall)
        else:
            service_s = self.cost_model.predict(units)
        self.clock.advance(service_s)
        t_done = self.clock.now()
        degraded = level > 0
        if env is not None:
            env.degraded = degraded  # every degraded answer says so
        batches.append((service_s, n_real, level))
        with TraceAnnotation("serve.assemble"):
            for i, req in enumerate(reqs):
                if env is not None:
                    indices = np.asarray(env.result.indices[i])
                    scores = np.asarray(env.result.scores[i])
                else:
                    indices = scores = None
                s = Served(
                    req=req,
                    t_done=t_done,
                    latency_s=t_done - req.t_arrival,
                    deadline_met=t_done <= req.deadline,
                    t_dispatch=t_dispatch,
                    level=level,
                    degraded=degraded,
                    cache_hit=False,
                    indices=indices,
                    scores=scores,
                    graph_version=version,
                )
                served.append(s)
                workload.on_complete(req, t_done)
