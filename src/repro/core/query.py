"""The query plane — typed queries, execution plans, result envelopes.

The engine's query surface used to be four ad-hoc methods
(``solve``/``solve_batch``/``topk``/``update``) whose backend × mesh ×
batch compatibility rules lived in hand-written ``if`` chains inside
``PageRankEngine``.  This module replaces that surface with three typed
layers:

  * **Queries** — frozen dataclasses describing *what* is asked:
    :class:`RankQuery` (one global ranking), :class:`PPRQuery` (a [B, n]
    personalization batch), :class:`TopKQuery` (served per-seed top-k),
    :class:`DeltaQuery` (an edge delta + incremental re-rank) and
    :class:`BatchQuery` (a sequential composition of any of them).
  * **The planner** — :func:`plan_query` maps (prepared-engine snapshot,
    query) onto an :class:`ExecutionPlan`: which backend, which mesh
    layout, which execution path, at what estimated cost, and *why*.
    Compatibility is decided from the backend's declared
    :class:`~repro.core.backends.BackendCapabilities`, not from its name —
    a newly registered layout participates by declaration alone.
  * **Envelopes** — :class:`ResultEnvelope` wraps every answer with its
    residual/iteration counters, the plan that produced it (provenance)
    and wall-clock timing.

``PageRankEngine.plan(query)`` and ``PageRankEngine.run(query)`` are the
engine-side entry points; the legacy methods are thin wrappers over
``run`` and stay bit-identical (tests/test_query_plan.py).  See
docs/API.md for the capability matrix and the planner rules.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

from .solver_config import BatchConfig, SolverConfig, make_config

__all__ = [
    "Query", "RankQuery", "PPRQuery", "TopKQuery", "DeltaQuery",
    "BatchQuery", "ExecutionPlan", "ResultEnvelope", "PlannerState",
    "plan_query",
]


# ---------------------------------------------------------------------------
# Query types
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Query:
    """Base marker for everything the engine can be asked."""

    kind = "?"


@dataclasses.dataclass(frozen=True)
class RankQuery(Query):
    """One PR(P, c, p) solve against the prepared graph.

    ``cfg`` is any single-solve config (``ItaConfig``, ``PowerConfig``,
    ``ForwardPushConfig``, ``MonteCarloConfig``); ``None`` means the
    engine plan's ``default_method`` at its default settings.  ``method``
    overrides the registry entry for configs shared between variants
    (e.g. ``ItaConfig`` with ``method="ita_traced"``).
    """

    cfg: Optional[SolverConfig] = None
    method: Optional[str] = None

    kind = "rank"


@dataclasses.dataclass(frozen=True)
class PPRQuery(Query):
    """A [B, n] personalization batch solved in one pass.

    ``p_batch`` is the float[B, n] operand (one preference row per
    query); ``cfg`` a :class:`~repro.core.solver_config.BatchConfig`
    (``None`` ⇒ engine defaults).  ``no_cache=True`` bypasses the
    engine's result cache (when one is attached) for this query only —
    rows solve on device even if cached; the cache is neither read nor
    written.
    """

    p_batch: Any = None
    cfg: Optional[BatchConfig] = None
    no_cache: bool = False

    kind = "ppr"


@dataclasses.dataclass(frozen=True)
class TopKQuery(Query):
    """Served PPR: per-seed top-``k`` vertices and scores.

    ``sources`` is an int[B] sequence of seed vertices (classic one-hot
    personalizations).  ``no_cache=True`` bypasses the engine's result
    cache for this query only (see :class:`PPRQuery`).
    """

    sources: Any = None
    k: int = 10
    cfg: Optional[BatchConfig] = None
    no_cache: bool = False

    kind = "topk"


@dataclasses.dataclass(frozen=True)
class DeltaQuery(Query):
    """An edge delta plus the incremental re-rank it triggers.

    ``add``/``remove`` are iterables of ``(src, dst)`` pairs, the
    :func:`repro.graph.apply_edge_delta` contract.  ``rank=False`` applies
    the delta and acknowledges it without re-ranking: the result carries
    the new ``graph_version`` and no pi, and the engine drops the global
    ranking's residual state, which no longer matches the graph (a later
    ranked delta solves it again from scratch).  That is the write of a
    deployment that serves PPR and keeps no global ranking.
    """

    add: tuple = ()
    remove: tuple = ()
    rank: bool = True

    kind = "delta"


@dataclasses.dataclass(frozen=True)
class BatchQuery(Query):
    """Sequential composition: run each sub-query in order, one envelope
    each.  A :class:`DeltaQuery` inside the sequence mutates the engine
    for the queries after it — exactly the serving-loop semantics."""

    queries: Tuple[Query, ...] = ()

    kind = "composite"

    def __post_init__(self):
        object.__setattr__(self, "queries", tuple(self.queries))
        for q in self.queries:
            if not isinstance(q, Query) or isinstance(q, BatchQuery):
                raise TypeError(
                    f"BatchQuery composes non-composite Query instances; "
                    f"got {type(q).__name__}")


# ---------------------------------------------------------------------------
# Execution plans
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The planner's decision record for one query.

    ``path`` names the execution strategy the engine will drive:

      * ``"while-loop"``        device-resident jitted solve loop;
      * ``"host-loop"``         python-driven loop (host-driven backend);
      * ``"direct"``            solver that consumes no push backend
                                (forward_push, monte_carlo);
      * ``"batched-while-loop"`` / ``"batched-host-loop"``  the [B, n]
                                forms of the above;
      * ``"donated-batch"``     compiled batched loop with the [B, n]
                                buffer donated (accelerators);
      * ``"distributed-batch"`` mesh-sharded batched pass
                                (``core/distributed.py``);
      * ``"incremental"``       signed correction cascade
                                (``core/dynamic.py``);
      * ``"layout"``            an edge delta applied with no re-rank
                                (``DeltaQuery(rank=False)``);
      * ``"composite"``         a :class:`BatchQuery` of sub-plans.

    ``cfg`` is the *resolved* config the execution will use (defaults
    filled in); ``reasons`` the why-chain ``explain()`` renders.
    ``cost`` always stays in declared edge-traversal units (the serving
    tier's pricing unit); ``cost_source``/``cost_detail`` record whether
    a measured roofline sample (``repro.roofline.planner_costs``) or the
    declared backend constants produced the estimate, with the measured
    bytes/FLOPs/seconds provenance ``explain()`` quotes.
    """

    query: str                      # Query.kind
    backend: str                    # step_impl name ("-" when unused)
    path: str
    method: str                     # registry / batch-family name
    mesh: Optional[tuple] = None    # normalized (R, C), None off-mesh
    micro_batch: Optional[int] = None
    cost: float = float("nan")      # est. edge-traversal units
    cfg: Any = None
    reasons: Tuple[str, ...] = ()
    sub_plans: Tuple["ExecutionPlan", ...] = ()
    cost_source: str = "declared"   # "measured" | "declared"
    cost_detail: Optional[dict] = None  # PlanCost.as_dict() provenance

    def explain(self) -> str:
        """Human-readable decision record: backend, mesh layout, why."""
        mesh = (f"({self.mesh[0]}, {self.mesh[1]})"
                f"[data×{self.mesh[0]}, model×{self.mesh[1]}]"
                if self.mesh else "none (single device)")
        head = (f"plan[{self.query}]: backend={self.backend} "
                f"path={self.path} method={self.method} mesh={mesh}")
        if self.micro_batch is not None:
            head += f" micro_batch={self.micro_batch}"
        lines = [head]
        if self.cost == self.cost:  # not NaN
            lines.append(f"  est. cost: {self.cost:.3g} edge-traversal units")
            src = f"  cost source: {self.cost_source}"
            reason = (self.cost_detail or {}).get("reason")
            if reason:
                src += f" — {reason}"
            lines.append(src)
        if self.reasons:
            lines.append("  why:")
            lines.extend(f"  - {r}" for r in self.reasons)
        for sp in self.sub_plans:
            lines.extend("    " + ln for ln in sp.explain().splitlines())
        return "\n".join(lines)


@dataclasses.dataclass
class ResultEnvelope:
    """Every ``engine.run`` answer: values + counters + provenance + time.

    ``result`` is the underlying typed result (``SolverResult``,
    ``BatchSolverResult``, ``TopKResult``, or a tuple of sub-envelopes
    for a composite query); ``values`` the primary payload (``pi`` for
    solves, ``(indices, scores)`` for top-k).  ``plan`` records how the
    answer was produced; ``wall_time_s`` the envelope-level timing
    (compile included on first use — steady-state numbers come from the
    underlying result's own ``wall_time_s``).
    """

    result: Any
    plan: ExecutionPlan
    values: Any = None
    iterations: Optional[int] = None
    residual: Optional[float] = None
    converged: Optional[bool] = None
    wall_time_s: Optional[float] = None
    # Set only when the answer came through the result cache
    # (core/cache.py): per-call row counts (hits/misses/revalidated),
    # the graph_version served, and cumulative totals.  ``None`` means
    # the query ran on device exactly as an uncached engine would.
    cache_stats: Optional[dict] = None
    # Set by the serving tier (serve/service.py) when the answer was
    # produced at a degraded fidelity level (looser ξ or a cheaper
    # backend under overload).  False everywhere else: a direct
    # ``engine.run`` answer is always full fidelity.
    degraded: bool = False


# ---------------------------------------------------------------------------
# The planner
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlannerState:
    """Snapshot of a prepared engine — everything planning may depend on.

    Built by ``PageRankEngine._planner_state()`` per ``plan()`` call;
    keeping it a value type means the planner owns the compatibility
    matrix while the engine owns only the prepared buffers.
    """

    step_impl: str
    capabilities: Any               # BackendCapabilities of the prepared backend
    backend_reason: str             # why prepare picked this backend
    mesh_shape: Optional[tuple]     # normalized (R, C) or None
    donate: bool                    # accelerator buffer-donation available
    n: int
    m: int
    default_method: str
    dtype: Any
    has_residual_state: bool
    graph_version: int = 0          # monotone edge-set version (deltas bump)
    cache: Any = None               # CachePolicy when a result cache is on
    undirected: bool = False        # Graph.is_undirected (symmetric edges)
    core_edges: Optional[int] = None  # the push's core edge list, if any
    level_depth: int = -1           # deepest weak-unreferenced level K


def _core_reason(state: PlannerState) -> list:
    """The why-line for a push that keeps a core edge list, if it does."""
    if state.core_edges is None:
        return []
    return [f"referenced core (paper §III): {state.core_edges} of "
            f"{state.m} edges, deepest weak-unreferenced level "
            f"K={state.level_depth}; a round whose input is zero off the "
            f"core walks only those, bit for bit the full push"]


def _price(backend_name: str, stats: dict, cfg, batch: int = 1) -> dict:
    """Price one planned solve through the roofline measured-cost layer.

    Returns ``PlanCost.as_dict()`` — ``cost`` in declared edge-traversal
    units × batch, ``source`` "measured"/"declared", and the provenance
    ``reason`` ``ExecutionPlan.explain()`` quotes.  With no table, or no
    sample for this backend, ``plan_cost`` prices by the declared
    constants; any other failure there is a bug and raises.
    """
    from ..roofline.planner_costs import plan_cost
    return plan_cost(backend_name, stats, cfg, batch=batch).as_dict()


def _check_step_compat(state: PlannerState, cfg) -> None:
    want = getattr(cfg, "step_impl", None)
    if want not in (None, "auto", state.step_impl):
        raise ValueError(
            f"config requests step_impl={want!r} but this engine "
            f"prepared {state.step_impl!r}; construct the engine with "
            f"EnginePlan(step_impl={want!r}) instead")
    want_mesh = getattr(cfg, "mesh_shape", None)
    if want_mesh is not None:
        shape = want_mesh if len(want_mesh) == 2 else (want_mesh[0], 1)
        if shape != state.mesh_shape:
            raise ValueError(
                f"config requests mesh_shape={shape} but this engine "
                f"prepared mesh={state.mesh_shape}; construct the engine "
                f"with EnginePlan(mesh={shape}) instead")


def _check_dtype(state: PlannerState, cfg) -> None:
    caps = state.capabilities
    name = np.dtype(getattr(cfg, "dtype", state.dtype)).name
    if name not in caps.dtypes:
        raise ValueError(
            f"backend {state.step_impl!r} declares dtypes {caps.dtypes}, "
            f"config requests {name!r}")


def _plan_rank(state: PlannerState, q: RankQuery) -> ExecutionPlan:
    from .api import SOLVERS  # local import: api builds engines (shim)
    from .solver_config import accepted_params

    cfg = q.cfg
    if cfg is None:
        cfg = make_config(state.default_method, dtype=state.dtype)
    if isinstance(cfg, BatchConfig):
        raise TypeError("BatchConfig describes a [B, n] solve; "
                        "use solve_batch / topk (PPRQuery / TopKQuery)")
    method = q.method or type(cfg).method
    if method not in SOLVERS:
        raise KeyError(f"unknown solver {method!r}; "
                       f"available: {sorted(SOLVERS)}")
    if not isinstance(cfg, SOLVERS[method].config_cls):
        # same contract Solver.__call__ enforces, surfaced at plan time
        raise TypeError(
            f"solver {method!r} takes "
            f"{SOLVERS[method].config_cls.__name__}, "
            f"got {type(cfg).__name__}")
    _check_step_compat(state, cfg)
    _check_dtype(state, cfg)
    caps = state.capabilities
    reasons = [f"engine prepared step_impl={state.step_impl!r} "
               f"({state.backend_reason})",
               f"capabilities: {caps.summary()}"]
    if state.undirected:
        reasons.append(
            "graph is undirected (Graph.is_undirected): the "
            "undirected-schedule rule discounts priority diffusion "
            "(frontier_priority) in host-eligible backend pools")
    stats = dict(n=state.n, m=state.m, undirected=state.undirected,
                 dtype=np.dtype(getattr(cfg, "dtype", state.dtype)).name)
    if "step_impl" not in accepted_params(SOLVERS[method].fn):
        # solver consumes no push backend — runs as-is
        return ExecutionPlan(
            query=q.kind, backend="-", path="direct", method=method,
            mesh=None, cfg=cfg, cost=float("nan"),
            reasons=(f"solver {method!r} consumes no push backend "
                     f"(its own schedule)",))
    reasons += _core_reason(state)
    if caps.jittable:
        path = "while-loop"
        reasons.append("jittable push -> device-resident jitted solve loop")
    else:
        path = "host-loop"
        reasons.append("host-driven push -> python loop, identical step "
                       "semantics")
    price = _price(state.step_impl, stats, cfg)
    return ExecutionPlan(query=q.kind, backend=state.step_impl, path=path,
                         method=method, mesh=None, cfg=cfg,
                         cost=price["cost"], cost_source=price["source"],
                         cost_detail=price, reasons=tuple(reasons))


def _plan_batch_common(state: PlannerState, cfg, B: int, kind: str
                       ) -> ExecutionPlan:
    """Shared PPR/TopK planning — the batch × mesh × backend matrix."""
    _check_step_compat(state, cfg)
    _check_dtype(state, cfg)
    if cfg.batch_method not in ("ita", "power"):
        raise KeyError(f"unknown batch_method {cfg.batch_method!r}; "
                       f"available: ['ita', 'power']")
    caps = state.capabilities
    reasons = [f"engine prepared step_impl={state.step_impl!r} "
               f"({state.backend_reason})",
               f"capabilities: {caps.summary()}", *_core_reason(state)]
    stats = dict(n=state.n, m=state.m, undirected=state.undirected,
                 dtype=np.dtype(getattr(cfg, "dtype", state.dtype)).name)
    price = _price(state.step_impl, stats, cfg, batch=B)
    mesh = None
    if (state.mesh_shape is not None and cfg.shard_batch
            and cfg.batch_method == "ita" and caps.batch_parallel_mesh):
        mesh = state.mesh_shape
        path = "distributed-batch"
        R, C = mesh
        if C > 1:
            schedule = ("sharded-ELL column blocks: Graph.ell_partitioned"
                        f"({C}) tiles through the batched Pallas kernel"
                        if state.step_impl == "ell" else
                        "dense segment-sum over partition_cols blocks")
            reasons.append(
                f"mesh {mesh} from EnginePlan and shard_batch=True: "
                f"batch axis {R}-way on 'data', vertex axis {C}-way on "
                f"'model' ({schedule}; declared vertex_sharded_mesh)")
            # sharded cost model: each device streams its m/C edge block
            # per round; mesh-aware backend costs (EllBackend) see the
            # grid via the "mesh" stats entry.
            price = _price(
                state.step_impl,
                dict(stats, m=max(1, state.m // C), mesh=mesh), cfg, batch=B)
            reasons.append(
                f"sharded cost model: per-device edge block "
                f"m/C ≈ {state.m // max(C, 1)} drives the estimate")
        else:
            reasons.append(
                f"mesh {mesh} from EnginePlan and shard_batch=True: "
                f"batch axis {R}-way on 'data' (vertex axis whole; "
                f"per-device push_batch, bit-identical)")
    elif state.mesh_shape is not None and cfg.batch_method != "ita":
        reasons.append("engine holds a mesh but only ITA batches run "
                       "sharded; power batch falls back to single device")
        path = None
    elif state.mesh_shape is not None and not cfg.shard_batch:
        reasons.append("query opted out of the engine mesh "
                       "(shard_batch=False)")
        path = None
    else:
        path = None
    if path is None:
        if state.donate and cfg.batch_method == "ita" and caps.donation:
            path = "donated-batch"
            reasons.append("accelerator platform + donation capability: "
                           "[B, n] buffer donated across micro-batches")
        elif caps.jittable:
            path = "batched-while-loop"
            reasons.append("jittable push_batch -> one device-resident "
                           "batched loop")
        else:
            path = "batched-host-loop"
            reasons.append("host-driven push -> per-row python loop, "
                           "identical numerics")
    if state.cache is not None and cfg.batch_method == "ita":
        refresh = ("stale entries revalidate via ita_incremental from "
                   "their stored (π̄, h) pair" if state.cache.revalidate
                   else "stale entries drop and re-solve")
        reasons.append(
            f"result cache attached (capacity={state.cache.capacity}): "
            f"one-hot rows keyed (graph_version={state.graph_version}, "
            f"seed, cfg); staleness bound ξ={cfg.xi:g} — {refresh}")
    elif state.cache is not None:
        reasons.append("result cache attached but power batches carry no "
                       "(π̄, h) state to revalidate — cache bypassed")
    return ExecutionPlan(query=kind, backend=state.step_impl, path=path,
                         method=f"{cfg.batch_method}_batch", mesh=mesh,
                         micro_batch=B, cfg=cfg, cost=price["cost"],
                         cost_source=price["source"], cost_detail=price,
                         reasons=tuple(reasons))


def _plan_ppr(state: PlannerState, q: PPRQuery) -> ExecutionPlan:
    cfg = q.cfg or BatchConfig(dtype=state.dtype)
    if not isinstance(cfg, BatchConfig):
        raise TypeError(f"solve_batch takes a BatchConfig, "
                        f"got {type(cfg).__name__}")
    shape = np.shape(q.p_batch)
    if len(shape) != 2 or shape[1] != state.n:
        raise ValueError(f"p_batch must be [B, n={state.n}], got {shape}")
    return _plan_batch_common(state, cfg, int(shape[0]), q.kind)


def _plan_topk(state: PlannerState, q: TopKQuery) -> ExecutionPlan:
    cfg = q.cfg or BatchConfig(dtype=state.dtype)
    if not isinstance(cfg, BatchConfig):
        raise TypeError(f"topk takes a BatchConfig, "
                        f"got {type(cfg).__name__}")
    shape = np.shape(q.sources)
    if len(shape) != 1:
        raise ValueError(f"sources must be int[B], got shape {shape}")
    if int(q.k) < 1:
        raise ValueError(f"k must be >= 1, got {q.k}")
    plan = _plan_batch_common(state, cfg, int(shape[0]), q.kind)
    return dataclasses.replace(
        plan, reasons=plan.reasons + (
            f"one-hot personalizations + lax.top_k(k={int(q.k)}) "
            f"on the batched result",))


def _plan_delta(state: PlannerState, q: DeltaQuery) -> ExecutionPlan:
    caps = state.capabilities
    if not caps.dynamic_update:
        raise ValueError(
            f"backend {state.step_impl!r} does not declare dynamic_update; "
            f"prepare the engine with a backend that does")
    reasons = [f"engine prepared step_impl={state.step_impl!r} "
               f"({state.backend_reason})"]
    if q.rank:
        path, method = "incremental", "ita_incremental"
        reasons += ["signed incremental cascade (core/dynamic.py) on the "
                    "changed support",
                    "warm (π̄, h) residual state reused" if
                    state.has_residual_state else
                    "cold start: one residual solve establishes (π̄, h), "
                    "later deltas are incremental"]
    else:
        path, method = "layout", "-"
        reasons += ["rank=False: the edge set and the push's layout change, "
                    "no re-rank; any (π̄, h) residual state is dropped"]
    n_delta = len(tuple(q.add)) + len(tuple(q.remove))
    return ExecutionPlan(query=q.kind, backend=state.step_impl,
                         path=path, method=method,
                         mesh=None, micro_batch=None, cost=float("nan"),
                         cfg=None,
                         reasons=tuple(reasons) + (
                             f"delta size: {n_delta} edge(s)",))


def plan_query(state: PlannerState, query: Query) -> ExecutionPlan:
    """Map a typed query onto an :class:`ExecutionPlan`.

    This function owns the backend × mesh × batch compatibility matrix:
    every rule reads the prepared backend's declared capabilities, so new
    layouts/scenarios land as new capability declarations, not new
    branches here.  Raises the same ``TypeError``/``ValueError``/
    ``KeyError`` contracts the legacy methods held.
    """
    if isinstance(query, BatchQuery):
        subs = tuple(plan_query(state, q) for q in query.queries)
        return ExecutionPlan(
            query=query.kind, backend=state.step_impl, path="composite",
            method="-", mesh=state.mesh_shape,
            micro_batch=len(subs), cfg=None,
            reasons=(f"sequential composition of {len(subs)} sub-quer"
                     f"{'y' if len(subs) == 1 else 'ies'}; a DeltaQuery "
                     f"re-plans everything after it",),
            sub_plans=subs)
    if isinstance(query, RankQuery):
        return _plan_rank(state, query)
    if isinstance(query, PPRQuery):
        return _plan_ppr(state, query)
    if isinstance(query, TopKQuery):
        return _plan_topk(state, query)
    if isinstance(query, DeltaQuery):
        return _plan_delta(state, query)
    raise TypeError(f"not a Query: {type(query).__name__}")
