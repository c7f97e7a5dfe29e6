"""PageRankEngine — a prepared-graph session behind one query plane.

The paper's central observation (§III) is that dangling and (weakly)
unreferenced vertices are *structure*: classify them once and every solve
afterwards exploits the classification for free.  Prepare peels the graph
by weak-unreferenced level (``Graph.reference_levels``); the dense push
keeps the referenced core's out-edges as a second edge list and walks it
in every round whose input is zero outside the core, from round K + 2 of
a rank solve with deepest level K and from round 1 of a PPR row seeded in
the core.  It checks that on the input each round, so the classification
only ever saves work, and the sums are the full list's bit for bit.  The
one-shot entry point
``solve_pagerank(g, method, **kwargs)`` re-derived all of that per call —
vertex masks, the ELL bucketing, the frontier CSR plan, the backend choice.
This module turns the derivation into an explicit **prepare** phase and the
solves into cheap queries against it, the prepare-once/query-many shape the
D-Iteration and forward-push serving papers assume:

    engine = PageRankEngine(graph, plan=EnginePlan(step_impl="ell"))
    env = engine.run(RankQuery(ItaConfig(xi=1e-12)))    # the query plane
    ep  = engine.plan(TopKQuery(sources=[3, 17], k=10)) # decide, don't run
    print(ep.explain())                                 # backend/mesh/why

    r  = engine.solve(ItaConfig(xi=1e-12))          # legacy wrappers —
    rb = engine.solve_batch(P)                      # thin shims over run(),
    tk = engine.topk(sources=[3, 17], k=10)         # bit-identical
    ru = engine.update(add=[(5, 9)])                # (tests/test_query_plan)

Prepare phase (one-time, at construction; again per ``DeltaQuery`` only
where the live layout below does not apply):
  * vertex classification per §III — dangling / unreferenced masks and
    counts, materialized on device, and the weak-unreferenced levels,
    whose deepest finite level is ``level_depth``;
  * backend selection: ``EnginePlan.step_impl="auto"`` resolves by the
    declared :meth:`~repro.core.backends.SolverBackend.cost` estimates
    (``choose_backend``), an explicit name is validated; the per-graph
    context follows (``Graph.ell()`` bucketing for the Pallas kernel, the
    CSR-by-src plan for frontier compression);
  * mesh resolution (``EnginePlan.mesh``): the graph operands and backend
    ctx are replicated onto the device grid once with ``NamedSharding``;
    mesh eligibility comes from the backend's declared capabilities
    (``batch_parallel_mesh`` / ``vertex_sharded_mesh``), not its name.

**The query plane** (``core/query.py``): :meth:`PageRankEngine.plan` maps
a typed query (``RankQuery`` / ``PPRQuery`` / ``TopKQuery`` /
``DeltaQuery`` / ``BatchQuery``) onto an ``ExecutionPlan`` — backend, mesh
layout, execution path, estimated cost, and an ``explain()`` why-chain —
and :meth:`PageRankEngine.run` executes that plan, returning a
``ResultEnvelope`` (values + counters + plan provenance + timing).  The
planner, not this class, owns the backend × mesh × batch compatibility
matrix; the engine only drives the path the plan names.  Queries reuse the
prepared context verbatim — ``run`` calls the very same solver functions
as the legacy API with ``ctx=`` threaded through, so results are
bit-for-bit identical to the per-call path (asserted by
tests/test_engine.py and tests/test_query_plan.py).

``DeltaQuery`` wraps ``core/dynamic.py``: the engine holds the
unnormalized residual pair (π̄, h) across updates, so successive edge
deltas each cost one *incremental* signed-ITA cascade instead of a
from-scratch solve, and the state chains — update after update — without
ever resolving globally.  On the dense backend without a mesh the first
delta lays the edge lists out with slack (``core/live.py``); every later
delta edits that layout in place on the device, compiling nothing and
costing host work in proportion to the edges added since, and one that
does not fit lays the graph out again (``relayouts``).  ``PPRQuery`` and
``TopKQuery`` on that layout read the graph's ``Degrees``, not a
``Graph``, so they neither rebuild the graph after a delta nor compile
again when m changes.  ``DeltaQuery(rank=False)`` applies a delta with no
re-rank: the write of a deployment that serves PPR.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from ..graph.structure import Graph, apply_edge_delta
from .backends import choose_backend, get_step_impl, resolve_step_impl
from .cache import CachePolicy, ResultCache
from .batch import (
    BatchSolverResult,
    _counters,
    _ita_batch_loop_donated,
    ita_batch,
    normalize_rows,
    one_hot_personalizations,
    power_method_batch,
)
from .distributed import ita_batch_distributed, resolve_mesh
from .dynamic import ita_incremental, ita_residual_state
from .live import LiveLayout
from .metrics import SolverResult
from .query import (
    BatchQuery,
    DeltaQuery,
    ExecutionPlan,
    PlannerState,
    PPRQuery,
    Query,
    RankQuery,
    ResultEnvelope,
    TopKQuery,
    plan_query,
)
from .solver_config import BatchConfig, SolverConfig

__all__ = ["EnginePlan", "PageRankEngine", "TopKResult"]


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """Static description of how an engine prepares and serves a graph.

    The plan is the engine-level analogue of a solver config: everything
    here is resolved once at prepare time and becomes part of the compiled
    state's identity.  ``step_impl="auto"`` picks the lowest-cost jittable
    backend by the registry's declared estimates (bucketed-ELL on TPU
    where the Mosaic kernel pays, dense elsewhere).

    ``mesh`` asks the engine to serve batched queries sharded over a
    device grid: ``None`` (single device), ``"host"`` (all ``jax.devices()``
    as an (n_dev, 1) batch-parallel grid — the CI fallback that works on
    simulated host devices), ``(R,)`` / ``(R, C)`` shapes, or a prebuilt
    ``jax.sharding.Mesh`` with a "data" (and optionally "model") axis.
    Constraints, enforced at prepare time from the backend's declared
    capabilities: serving under ``shard_map`` needs
    ``batch_parallel_mesh`` (the host-driven "frontier" declares it
    false), and C-way vertex sharding (C > 1) needs
    ``vertex_sharded_mesh`` — declared by "dense" (partition_cols
    segment-sum) and "ell" (per-block bucketed tiles through the batched
    Pallas kernel).  With ``step_impl="auto"`` the choice is mesh-aware:
    on a C > 1 grid the pool narrows to vertex-sharded backends and the
    ELL kernel's declared sharded cost wins (see ``EllBackend.cost``).
    """

    step_impl: Optional[str] = "auto"
    ell_widths: tuple = (8, 32, 128)
    row_align: int = 8
    dtype: Any = jnp.float64
    default_method: str = "ita"
    c: float = 0.85          # damping used by the update/residual machinery
    update_xi: float = 1e-12  # accuracy the maintained residual state holds
    mesh: Any = None          # None | "host" | (R,) | (R, C) | Mesh
    # Result cache over PPRQuery/TopKQuery (core/cache.py): None disables,
    # True attaches the default CachePolicy(), or pass a CachePolicy.
    # Entries key on (graph_version, seed, frozen cfg); DeltaQuery bumps
    # the version and stale entries revalidate via ita_incremental.
    cache: Any = None


class TopKResult(NamedTuple):
    """Served PPR answer: per-query top-``k`` vertices and scores."""

    indices: jnp.ndarray   # int32 [B, k]
    scores: jnp.ndarray    # [B, k]
    result: BatchSolverResult


def _acknowledged(graph_version: int, relayouts: Optional[int] = None,
                  core_edges: Optional[int] = None) -> SolverResult:
    """A ``DeltaQuery(rank=False)``'s result: the delta applied, no pi."""
    return SolverResult(pi=None, iterations=0, residual=0.0, ops=0.0,
                        converged=True, method="delta_apply",
                        relayouts=relayouts, core_edges=core_edges,
                        graph_version=graph_version)


class PageRankEngine:
    """Prepare a graph once; plan and run typed queries against it."""

    def __init__(self, graph: Graph, plan: Optional[EnginePlan] = None):
        self.engine_plan = plan or EnginePlan()
        # monotone counter, observable by tests: one tick per prepare phase
        # (construction + each update), never per query.
        self.prepare_count = 0
        self._state = None        # (pi_bar, h) residual pair for DeltaQuery
        self.relayouts = 0        # deltas that overflowed the live layout
        self._donate = jax.default_backend() != "cpu"
        policy = self.engine_plan.cache
        if policy is True:
            policy = CachePolicy()
        elif policy is not None and not isinstance(policy, CachePolicy):
            raise TypeError(
                f"EnginePlan.cache must be None, True, or a CachePolicy; "
                f"got {type(policy).__name__}")
        self.cache_policy = policy
        # the cache survives _prepare: entries are version-stamped, so a
        # DeltaQuery leaves them in place to be revalidated lazily.
        self.result_cache = ResultCache(policy) if policy is not None else None
        self._prepare(graph)

    # ------------------------------------------------------------------ #
    # prepare phase
    # ------------------------------------------------------------------ #
    def _prepare(self, g: Graph) -> None:
        """One-time per-graph work: classify, bucket, build backend ctx,
        and (when the plan carries a mesh) lay the prepared state out on
        the device grid once so every query reuses the placement."""
        self._graph = g
        self.n = g.n
        # the dense push's layout for edge deltas, taken at the first
        # DeltaQuery (core/live.py); None keeps the plain layout
        self._live = None
        # the edge-set version cache entries are stamped with; each
        # DeltaQuery advances it.
        self.graph_version = g.graph_version
        plan = self.engine_plan
        # mesh geometry first: the backend choice is mesh-aware (an (R, C)
        # grid with C > 1 restricts "auto" to vertex-sharded backends and
        # flips the ELL kernel's declared cost in their favour).
        self.mesh = resolve_mesh(plan.mesh)
        self._mesh_shape = None
        if self.mesh is not None:
            C = (self.mesh.shape["model"]
                 if "model" in self.mesh.axis_names else 1)
            # normalized (R, C) grid — a user-supplied single-axis Mesh
            # has a 1-length devices.shape, so derive from the axes.
            self._mesh_shape = (self.mesh.shape["data"], C)
        if plan.step_impl in (None, "auto"):
            require = ()
            if self._mesh_shape is not None:
                require = (("batch_parallel_mesh", "vertex_sharded_mesh")
                           if self._mesh_shape[1] > 1
                           else ("batch_parallel_mesh",))
            self.step_impl, self._backend_reason = choose_backend(
                dict(n=g.n, m=g.m, mesh=self._mesh_shape,
                     undirected=g.is_undirected,
                     dtype=np.dtype(plan.dtype).name), require=require)
        else:
            self.step_impl = resolve_step_impl(plan.step_impl)
            self._backend_reason = "explicit EnginePlan(step_impl=...) request"
        self.backend = get_step_impl(self.step_impl)
        self.caps = self.backend.capabilities()
        # §III vertex classification, materialized once on device.
        self.dangling_mask = g.dangling_mask
        self.unreferenced_mask = g.unreferenced_mask
        self.n_dangling = int(jax.device_get(jnp.sum(self.dangling_mask)))
        self.n_unreferenced = int(
            jax.device_get(jnp.sum(self.unreferenced_mask)))
        # deepest finite weak-unreferenced level K, -1 when every vertex
        # lies in the referenced core
        self.level_depth = int(g.reference_levels.max(initial=-1))
        if self.step_impl == "ell":
            # honor the plan's bucketing; Graph.ell caches per (widths,
            # align) so the EllBackend default prepare() would otherwise
            # convert under its own key.
            self._ctx = g.ell(widths=plan.ell_widths,
                              row_align=plan.row_align)
        else:
            self._ctx = self.backend.prepare(g)
        self.core_edges = self.backend.core_edges(self._ctx)
        self.scan_edge_passes = self.backend.scan_edge_passes(self._ctx)
        if self.mesh is not None:
            if not self.caps.batch_parallel_mesh:
                raise ValueError(
                    f"EnginePlan(mesh=...) needs a jittable backend; "
                    f"{self.step_impl!r} is host-driven and cannot run "
                    f"under shard_map (declared batch_parallel_mesh=False)")
            C = self._mesh_shape[1]
            if C > 1 and not self.caps.vertex_sharded_mesh:
                from .distributed import _vertex_sharded_impls
                raise ValueError(
                    f"vertex sharding (mesh model axis = {C}) needs a "
                    f"backend declaring vertex_sharded_mesh (registered: "
                    f"{_vertex_sharded_impls()}); {self.step_impl!r} does "
                    f"not — prepare the engine with one of those")
            if C > 1 and self.step_impl == "ell":
                # prepare-once: the column-block bucketing the sharded
                # serving path consumes is host-side O(m) work — pay it
                # here, not on the first query.
                g.ell_partitioned(C, widths=plan.ell_widths,
                                  row_align=plan.row_align)
            # replicate the prepared context and graph operands onto the
            # grid once; shard_map then never reshards them per query.
            rep = NamedSharding(self.mesh, PartitionSpec())
            self._ctx = jax.device_put(self._ctx, rep)
            self._graph = jax.device_put(g, rep)
            # device_put builds a NEW Graph pytree, which would silently
            # drop the host-side layout caches (same edge set, so the
            # cached conversions stay valid) — transplant them so the
            # prepare-time warming above actually serves the queries.
            for attr in ("_ell_cache", "_ell_part_cache",
                         "_part_cols_cache", "_undirected_cache",
                         "_levels_cache", "_graph_version"):
                cache = getattr(g, attr, None)
                if cache is not None:
                    object.__setattr__(self._graph, attr, cache)
        self.prepare_count += 1

    @property
    def graph(self) -> Graph:
        """The graph the engine serves.  After a ``DeltaQuery`` on the
        live layout it is built from the host edge set on first use
        (O(m)); the delta path and the PPR and top-k paths never need
        it."""
        if self._graph is None:
            self._graph = self._live.edges.graph()
        return self._graph

    @property
    def m(self) -> int:
        return self._live.edges.m if self._live is not None else self.graph.m

    @property
    def live(self) -> bool:
        """Whether the engine serves from the layout for edge deltas
        (``core/live.py``), taken at its first delta."""
        return self._live is not None

    def _loop_graph(self):
        """What a batched loop reads of the graph: the :class:`Graph`, or
        on the live layout its :class:`~repro.graph.structure.Degrees`,
        whose shapes do not change with m and which needs no rebuild."""
        return self._live.degrees if self._live is not None else self.graph

    def _edge_devices(self) -> list:
        edges = self._graph.src if self._graph is not None else self._ctx.src
        return sorted(d.id for d in edges.devices())

    def describe(self, include_plan: bool = True) -> dict:
        """Prepared-state summary (serving logs, benchmarks).

        ``plan`` carries the default-query ``ExecutionPlan.explain()``
        text — the backend/mesh/why record a serving log wants.  Pass
        ``include_plan=False`` to skip building it (callers that print
        a query-specific plan themselves, or only read a field).
        """
        d = dict(
            n=self.n, m=self.m,
            n_dangling=self.n_dangling,
            n_unreferenced=self.n_unreferenced,
            level_depth=self.level_depth,
            core_edges=self.core_edges,
            # the push's scan passes per edge slot, per edge list
            scan_edge_passes=self.scan_edge_passes,
            step_impl=self.step_impl,
            jittable=self.caps.jittable,
            capabilities=self.caps.summary(),
            mesh=self._mesh_shape,
            # ids of the devices holding the graph's edge arrays
            devices=self._edge_devices(),
            prepare_count=self.prepare_count,
            # edge-list slots for added edges, from the first delta on
            delta_capacity=(self._live.slack if self._live is not None
                            else None),
            relayouts=self.relayouts,
            has_residual_state=self._state is not None,
            graph_version=self.graph_version,
            cache=(self.result_cache.stats()
                   if self.result_cache is not None else None),
        )
        if include_plan:
            d["plan"] = self.plan(RankQuery()).explain()
        return d

    # ------------------------------------------------------------------ #
    # the query plane: plan / run
    # ------------------------------------------------------------------ #
    def _planner_state(self, query: Optional[Query] = None) -> PlannerState:
        # a delta, a PPR and a top-k query run without the graph, so they
        # are planned without building it (on the live layout it is None
        # after a delta until something asks for it, and is then not known
        # to be undirected); every other query builds it to run anyway
        lazy = isinstance(query, (DeltaQuery, PPRQuery, TopKQuery))
        g = self._graph if lazy else self.graph
        return PlannerState(
            step_impl=self.step_impl,
            capabilities=self.caps,
            backend_reason=self._backend_reason,
            mesh_shape=self._mesh_shape,
            donate=self._donate,
            n=self.n,
            m=self.m,
            default_method=self.engine_plan.default_method,
            dtype=self.engine_plan.dtype,
            has_residual_state=self._state is not None,
            graph_version=self.graph_version,
            cache=self.cache_policy,
            undirected=g is not None and g.is_undirected,
            core_edges=self.core_edges,
            level_depth=self.level_depth,
        )

    def plan(self, query: Query) -> ExecutionPlan:
        """Decide how ``query`` would execute — without executing it.

        Pure planning: backend, mesh layout, path, estimated cost, and the
        why-chain ``ExecutionPlan.explain()`` renders.  All compatibility
        errors (``TypeError``/``ValueError``/``KeyError``) are raised
        here, before any device work.
        """
        return plan_query(self._planner_state(query), query)

    def run(self, query: Query) -> ResultEnvelope:
        """Execute ``query`` along its plan; the one entry point.

        Returns a :class:`~repro.core.query.ResultEnvelope` whose
        ``result`` is the legacy typed result (``SolverResult`` /
        ``BatchSolverResult`` / ``TopKResult`` / tuple of envelopes),
        bit-identical to the legacy method for the same arguments.
        """
        if isinstance(query, BatchQuery):
            # sub-queries plan themselves as they run (a DeltaQuery in the
            # sequence re-prepares the engine, so pre-computed sub-plans
            # could go stale); the composite envelope's plan records the
            # plans that actually executed.
            t0 = time.perf_counter()
            envs = tuple(self.run(q) for q in query.queries)
            ep = ExecutionPlan(
                query=query.kind, backend=self.step_impl, path="composite",
                method="-", mesh=self._mesh_shape, micro_batch=len(envs),
                reasons=("sequential composition; each sub-plan below is "
                         "the one its sub-query executed",),
                sub_plans=tuple(e.plan for e in envs))
            return ResultEnvelope(
                result=envs, plan=ep,
                values=tuple(e.values for e in envs),
                wall_time_s=time.perf_counter() - t0)
        if (self.result_cache is not None
                and isinstance(query, (PPRQuery, TopKQuery))
                and not query.no_cache):
            env = self.result_cache.serve(self, query)
            if env is not None:
                return env
            # None: not cacheable (dense rows, power family, ...) — run
            # exactly as an uncached engine would.
        with TraceAnnotation("engine.plan"):
            ep = self.plan(query)
        t0 = time.perf_counter()
        with TraceAnnotation("engine.exec"):
            if isinstance(query, RankQuery):
                res = self._exec_rank(ep)
                values = res.pi
            elif isinstance(query, PPRQuery):
                res = self._exec_ppr(query.p_batch, ep)
                values = res.pi
            elif isinstance(query, TopKQuery):
                res = self._exec_topk(query, ep)
                values = (res.indices, res.scores)
            elif isinstance(query, DeltaQuery):
                res = self._exec_delta(query)
                values = res.pi
            else:  # plan_query would have raised already; defensive
                raise TypeError(
                    f"not a runnable Query: {type(query).__name__}")
        counters = res.result if isinstance(res, TopKResult) else res
        return ResultEnvelope(
            result=res, plan=ep, values=values,
            iterations=int(counters.iterations),
            residual=float(counters.residual),
            converged=bool(counters.converged),
            wall_time_s=time.perf_counter() - t0)

    # ------------------------------------------------------------------ #
    # plan execution (each drives exactly the legacy code path)
    # ------------------------------------------------------------------ #
    def _exec_rank(self, ep: ExecutionPlan) -> SolverResult:
        from .api import SOLVERS  # local import: api builds engines (shim)

        # step_impl/ctx are signature-filtered by Solver.__call__, so the
        # "direct" path (forward_push, monte_carlo) ignores them — one
        # call shape, same bits as the legacy method.
        return SOLVERS[ep.method](self.graph, ep.cfg,
                                  step_impl=self.step_impl, ctx=self._ctx)

    def _exec_ppr(self, p_batch, ep: ExecutionPlan,
                  return_state: bool = False) -> BatchSolverResult:
        # return_state=True additionally returns the unnormalized (PiBar,
        # H) rows at quiescence — the result cache's fill path consumes
        # them; ITA paths only (power has no residual state).
        cfg = ep.cfg
        p_batch = jnp.asarray(p_batch)
        if ep.path == "distributed-batch":
            return ita_batch_distributed(
                self.graph, p_batch, self.mesh, c=cfg.c, xi=cfg.xi,
                max_iter=cfg.max_iter, dtype=cfg.dtype,
                step_impl=self.step_impl, ctx=self._ctx,
                ell_widths=self.engine_plan.ell_widths,
                row_align=self.engine_plan.row_align,
                return_state=return_state)
        if ep.path == "donated-batch":
            return self._solve_batch_donated(p_batch, cfg,
                                             return_state=return_state)
        fn = ita_batch if cfg.batch_method == "ita" else power_method_batch
        kw = cfg.kwargs_for(fn)
        kw["step_impl"] = self.step_impl
        kw["ctx"] = self._ctx
        if return_state:
            if fn is not ita_batch:
                raise ValueError(
                    "return_state=True needs the ITA batch family; "
                    f"cfg.batch_method={cfg.batch_method!r}")
            kw["return_state"] = True
        return fn(self._loop_graph(), p_batch, **kw)

    def _exec_topk(self, q: TopKQuery, ep: ExecutionPlan) -> TopKResult:
        P = one_hot_personalizations(self._loop_graph(), q.sources,
                                     dtype=self.engine_plan.dtype)
        rb = self._exec_ppr(P, ep)
        scores, indices = jax.lax.top_k(rb.pi, int(q.k))
        return TopKResult(indices=indices, scores=scores, result=rb)

    def _exec_delta(self, q: DeltaQuery) -> SolverResult:
        plan = self.engine_plan
        if self.step_impl != "dense" or self.mesh is not None:
            return self._exec_delta_reprepared(q)
        if self._live is None:
            # take the slack: the layout every later delta edits in place
            self._live = LiveLayout(self.graph)
            self._ctx = self._live.ctx
        live = self._live
        if not q.rank:
            self._state = None  # the ranking it held is of the old graph
        elif self._state is None:
            pi_bar, h, _, _ = ita_residual_state(
                live.degrees, c=plan.c, xi=plan.update_xi, dtype=plan.dtype,
                step_impl="dense", ctx=live.ctx)
            self._state = (pi_bar, h)
        with TraceAnnotation("engine.delta.apply"):
            relaid = live.apply(add=q.add, remove=q.remove)
            self._ctx, _ = jax.block_until_ready((live.ctx, live.degrees))
        self._graph = None
        self.graph_version = live.edges.version
        self.dangling_mask = live.degrees.dangling_mask
        self.unreferenced_mask = live.degrees.unreferenced_mask
        self.n_dangling = live.edges.n_dangling
        self.n_unreferenced = live.edges.n_unreferenced
        self.core_edges = live.core_edges
        self.scan_edge_passes = self.backend.scan_edge_passes(live.ctx)
        if relaid:
            self.relayouts += 1
            self.level_depth = int(live.levels.max(initial=-1))
        self.prepare_count += 1
        if not q.rank:
            return _acknowledged(self.graph_version, int(relaid),
                                 live.core_edges)
        pi_bar, h = self._state
        result, self._state = ita_incremental(
            live.degrees, live.degrees, pi_bar, h, c=plan.c,
            xi=plan.update_xi, step_impl="dense", ctx=live.ctx,
            return_state=True)
        return dataclasses.replace(result, relayouts=int(relaid),
                                   core_edges=live.core_edges,
                                   graph_version=self.graph_version)

    def _exec_delta_reprepared(self, q: DeltaQuery) -> SolverResult:
        """A delta on a layout that cannot take it in place: the new
        graph built on the host and prepared whole."""
        plan = self.engine_plan
        if not q.rank:
            self._state = None
        elif self._state is None:
            pi_bar, h, _, _ = ita_residual_state(
                self.graph, c=plan.c, xi=plan.update_xi,
                dtype=plan.dtype, step_impl=self.step_impl,
                ctx=self._ctx)
            self._state = (pi_bar, h)
        g_old = self.graph
        g_new = apply_edge_delta(g_old, add=q.add, remove=q.remove)
        self._prepare(g_new)  # ctx must belong to the NEW graph
        if not q.rank:
            jax.block_until_ready(self._ctx)
            return _acknowledged(graph_version=self.graph_version)
        pi_bar, h = self._state
        result, self._state = ita_incremental(
            g_old, g_new, pi_bar, h, c=plan.c, xi=plan.update_xi,
            step_impl=self.step_impl, ctx=self._ctx, return_state=True)
        return dataclasses.replace(result, graph_version=self.graph_version)

    def _solve_batch_donated(self, p_batch, cfg: BatchConfig,
                             return_state: bool = False):
        """Accelerator path: the batched-ITA loop with the [B, n]
        information buffer donated — the serving loop then updates in
        place instead of allocating per micro-batch.  It runs the body of
        ``ita_batch``'s loop with the same arguments, so results match
        ``ita_batch`` bit for bit.
        """
        t0 = time.perf_counter()
        H0 = (p_batch.astype(cfg.dtype) * self.n).astype(cfg.dtype)
        H, PiBar, n_active, it, ops, core_rounds = _ita_batch_loop_donated(
            self._loop_graph(), self._ctx, H0, float(cfg.c), float(cfg.xi),
            int(cfg.max_iter), self.backend)
        Pi = normalize_rows(PiBar + H)
        with TraceAnnotation("solve.wait"):
            Pi = jax.block_until_ready(Pi)
            result = BatchSolverResult(
                pi=Pi, iterations=int(it), residual=float(cfg.xi),
                converged=bool(int(n_active) == 0),
                method=f"ita_batch[{self.step_impl}]",
                batch=int(p_batch.shape[0]),
                wall_time_s=time.perf_counter() - t0,
                **_counters(self.backend, self._ctx, ops, core_rounds))
        if return_state:
            return result, (PiBar, H)
        return result

    # ------------------------------------------------------------------ #
    # legacy query methods — thin wrappers over run(), bit-identical
    # ------------------------------------------------------------------ #
    def solve(self, cfg: Optional[SolverConfig] = None, *,
              method: Optional[str] = None) -> SolverResult:
        """One PR(P, c, p) solve; wrapper over ``run(RankQuery(...))``.

        ``cfg`` defaults to the plan's ``default_method`` config; ``method``
        overrides the registry entry for configs shared between variants
        (e.g. ``ItaConfig`` with ``method="ita_traced"``).
        """
        return self.run(RankQuery(cfg=cfg, method=method)).result

    def solve_batch(self, p_batch: jnp.ndarray,
                    cfg: Optional[BatchConfig] = None) -> BatchSolverResult:
        """Solve a whole [B, n] personalization batch in one device pass;
        wrapper over ``run(PPRQuery(...))``.

        ``p_batch`` is float[B, n] (any float dtype; promoted to
        ``cfg.dtype``, default float64), one preference row per query;
        returns a :class:`~repro.core.batch.BatchSolverResult` whose
        ``pi`` is [B, n] with each row summing to 1.  The planner decides
        the path — mesh-sharded / donated / plain batched loop — from the
        engine mesh and the backend's declared capabilities; see
        ``engine.plan(PPRQuery(...)).explain()``.
        """
        return self.run(PPRQuery(p_batch=p_batch, cfg=cfg)).result

    def topk(self, sources, k: int = 10,
             cfg: Optional[BatchConfig] = None) -> TopKResult:
        """Serve PPR queries; wrapper over ``run(TopKQuery(...))``.

        ``sources`` is an int[B] vector of seed vertices (classic one-hot
        PPR); returns a :class:`TopKResult` with ``indices`` int32 [B, k]
        and ``scores`` ``plan.dtype`` [B, k], rows sorted by descending
        score.
        """
        return self.run(TopKQuery(sources=sources, k=int(k), cfg=cfg)).result

    def update(self, add=(), remove=()) -> SolverResult:
        """Apply an edge delta and incrementally re-rank; wrapper over
        ``run(DeltaQuery(...))``.

        Maintains the unnormalized residual pair (π̄, h) across calls: the
        first update pays one from-scratch residual solve, every later one
        runs only the signed correction cascade of ``ita_incremental`` on
        the changed support.  The backend ctx follows the new structure
        first: edited in place on the dense live layout, re-prepared
        whole elsewhere.
        """
        return self.run(DeltaQuery(add=add, remove=remove)).result
