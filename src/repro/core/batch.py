"""Batched personalization — one device pass serves many PPR queries.

The serving shape the ROADMAP's "millions of users" target needs: a
personalized-PageRank query is PR(P, c, p_u) for a per-user preference
vector p_u, and the graph operand (the edge stream — by far the larger
side of the SpMV) is IDENTICAL across users.  Solving a [B, n] batch in
one pass therefore reads the edge structure once per iteration for all B
queries: arithmetic intensity grows ~linearly in B until vertex state
fills VMEM, which is exactly where the batched ELL kernel
(``spmv_ell_bucket_batch``) wants to operate.

Semantics: each batch row follows bit-for-bit the trajectory it would in a
sequential solve —

  * ITA rows that reach quiescence stop changing on their own (a quiet row
    pushes nothing), so running the batch until ALL rows are quiet leaves
    every row exactly where its own solve would;
  * power-method rows are frozen the iteration their residual crosses
    ``tol`` (a per-row ``done`` mask), matching the sequential stopping
    rule instead of silently over-iterating converged rows.

Backends come from core/backends.py via their ``push_batch`` op;
``step_impl="frontier"`` falls back to a host-driven loop like the
single-query solvers.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..graph.structure import Graph
from .backends import count_core, get_step_impl

__all__ = ["BatchSolverResult", "ita_batch", "power_method_batch",
           "solve_pagerank_batch", "one_hot_personalizations"]


@dataclasses.dataclass
class BatchSolverResult:
    """Uniform return type for the batched solvers.

    ``pi`` is float[B, n] (the solve's ``dtype``, default float64), one
    normalized ranking row per personalization row; ``iterations`` is the
    shared synchronous-round count (all rows step together), ``residual``
    the stopping threshold the solve ran to (``xi`` for ITA, max row
    residual for power), ``converged`` whether every row met it within
    ``max_iter``, ``method`` a tag like ``"ita_batch[dense]"`` naming
    solver family and ``step_impl``, and ``ops`` the Formula-15 edge
    operations summed over rows and rounds (``None`` where a path does
    not count them: the power family, the mesh and the result cache), and
    ``core_rounds`` the rounds whose push walked the referenced core's
    edge list (``None`` there too, and where the backend keeps no list).
    """

    pi: jnp.ndarray
    iterations: int
    residual: float
    converged: bool
    method: str
    batch: int
    wall_time_s: Optional[float] = None
    ops: Optional[float] = None
    core_rounds: Optional[int] = None

    def stats(self) -> dict:
        return dict(method=self.method, batch=self.batch,
                    iterations=int(self.iterations),
                    residual=float(self.residual),
                    converged=bool(self.converged),
                    wall_time_s=self.wall_time_s, ops=self.ops,
                    core_rounds=self.core_rounds)


def one_hot_personalizations(g: Graph, seeds, dtype=jnp.float64) -> jnp.ndarray:
    """[B, n] matrix of single-seed preference vectors (classic PPR).

    ``seeds`` is any int sequence/array of vertex ids (B entries; an empty
    list yields a valid [0, n] batch).  Duplicates are allowed — identical
    rows solve to identical rankings — and a dangling seed is legal: its
    row's mass never transmits, so the solve returns the seed's own
    one-hot as the ranking (the paper's V_D semantics).  Returns
    ``dtype``[B, n], each row exactly one 1.0.
    """
    seeds = jnp.asarray(seeds, jnp.int32)
    return jax.nn.one_hot(seeds, g.n, dtype=dtype)


@jax.jit
def normalize_rows(U: jnp.ndarray) -> jnp.ndarray:
    """``U`` with each row scaled to sum to 1.

    Each row is summed as its own 1-D array, so a row's answer does not
    depend on how many rows share its batch: a TPU tiles a 2-D reduction
    by the row count, and the (R, 1) mesh must match one device bit for
    bit with a quarter of the rows per chip.  Compiled once per shape: an
    eager ``lax.map`` traces and compiles its loop again on every call.
    """
    return U / jax.lax.map(jnp.sum, U)[:, None]


def _batch_ita_step(backend, g, ctx, H, PiBar, c, xi, inv_deg, non_dangling):
    """One batched ITA round; returns ``(H', PiBar', n_active, ops, core)``,
    ``ops`` being Formula 15 summed over the rows and ``core`` as
    ``SolverBackend.push_batch_counted`` gives it."""
    with jax.named_scope("ita_round"):
        active = jnp.logical_and(H > xi, non_dangling[None, :])
        H_act = jnp.where(active, H, 0)
        PiBar = PiBar + H_act
        with jax.named_scope("push"):
            pushed, core = backend.push_batch_counted(
                g, ctx, H_act * inv_deg[None, :] * c)
        H = jnp.where(active, 0, H) + pushed
        n_active = jnp.sum(active, dtype=jnp.int32)
        ops = jnp.sum(jnp.where(active, g.out_deg[None, :], 0)
                      .astype(jnp.float32), dtype=jnp.float32)
        return H, PiBar, n_active, ops, core


def _ita_batch_loop_impl(g: Graph, ctx, H0, c, xi, max_iter: int, backend):
    inv_deg = g.inv_out_deg(H0.dtype)
    non_dangling = jnp.logical_not(g.dangling_mask)

    def cond(state):
        _, _, n_active, it, _, _ = state
        return jnp.logical_and(n_active > 0, it < max_iter)

    def body(state):
        H, PiBar, _, it, ops_total, core_total = state
        H, PiBar, n_active, ops, core = _batch_ita_step(
            backend, g, ctx, H, PiBar, c, xi, inv_deg, non_dangling)
        return (H, PiBar, n_active, it + 1, ops_total + ops,
                count_core(core_total, core))

    init = (H0, jnp.zeros_like(H0), jnp.asarray(1, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0.0, jnp.float32),
            jnp.asarray(0, jnp.int32))
    return jax.lax.while_loop(cond, body, init)


def _counters(backend, ctx, ops, core_rounds) -> dict:
    """A batched loop's counters as :class:`BatchSolverResult` fields."""
    if backend.core_edges(ctx) is None:
        core_rounds = None
    return dict(ops=None if ops is None else float(ops),
                core_rounds=None if core_rounds is None else int(core_rounds))


# static key is the backend instance, so re-registration invalidates traces
_ita_batch_loop = jax.jit(_ita_batch_loop_impl,
                          static_argnames=("max_iter", "backend"))
# The engine's accelerator path: the same loop with the [B, n] information
# buffer donated.  The graph and ctx are arguments, never closure constants,
# so the compiled program holds no edge arrays.
_ita_batch_loop_donated = jax.jit(_ita_batch_loop_impl,
                                  static_argnames=("max_iter", "backend"),
                                  donate_argnames=("H0",))


def ita_batch(
    g: Graph,
    p_batch: jnp.ndarray,
    *,
    c: float = 0.85,
    xi: float = 1e-10,
    max_iter: int = 10_000,
    dtype=jnp.float64,
    step_impl: str = "dense",
    ctx=None,
    return_state: bool = False,
) -> BatchSolverResult:
    """Multi-source ITA: ``p_batch`` is [B, n], one preference row per query.

    ``p_batch`` may be any float dtype (promoted to ``dtype``, default
    float64); initial information is ``p · n`` per the paper's uniform
    h0 = 1 convention.  ``step_impl`` accepts every registered backend —
    "dense", "ell" (jittable: the solve runs as one device-resident
    ``while_loop``) or "frontier" (host-driven loop, same numerics).
    ``ctx`` injects a prepared backend context (an engine session);
    ``None`` prepares one in place.  Returns a :class:`BatchSolverResult`
    with ``pi`` ``dtype``[B, n]; for the mesh-sharded form of this solve
    see ``core/distributed.ita_batch_distributed``.

    ``return_state=True`` returns ``(result, (PiBar, H))`` — the
    UNNORMALIZED per-row residual pairs at quiescence, the batched
    analogue of :func:`repro.core.dynamic.ita_residual_state`.  ``pi``
    is unchanged (the fold ``PiBar + H`` then row-normalize happens
    either way); the pair is what the result cache stores so a cached
    row can later be *revalidated* by ``ita_incremental`` instead of
    re-solved after an edge delta.
    """
    backend = get_step_impl(step_impl)
    if ctx is None:
        ctx = backend.prepare(g)
    H0 = (jnp.asarray(p_batch, dtype) * g.n).astype(dtype)
    t0 = time.perf_counter()
    if backend.capabilities().jittable:
        # the counters are the loop's last outputs; a stand-in loop with
        # the four outputs of old (bench/tests/test_control.py swaps one
        # in) counts none
        H, PiBar, n_active, it, *counters = _ita_batch_loop(
            g, ctx, H0, float(c), float(xi), int(max_iter), backend)
        ops, core_rounds = (*counters, None, None)[:2]
    else:
        inv_deg = g.inv_out_deg(dtype)
        non_dangling = jnp.logical_not(g.dangling_mask)
        H, PiBar = H0, jnp.zeros_like(H0)
        it, ops, core_rounds = 0, 0.0, 0
        n_active = jnp.asarray(1, jnp.int32)
        while it < max_iter:
            H, PiBar, n_active, ops_round, core = _batch_ita_step(
                backend, g, ctx, H, PiBar, c, xi, inv_deg, non_dangling)
            ops += float(ops_round)
            core_rounds = count_core(core_rounds, core)
            it += 1
            if int(n_active) == 0:
                break
    Pi = normalize_rows(PiBar + H)
    with TraceAnnotation("solve.wait"):
        Pi = jax.block_until_ready(Pi)
        result = BatchSolverResult(
            pi=Pi, iterations=int(it), residual=float(xi),
            converged=bool(int(n_active) == 0),
            method=f"ita_batch[{step_impl}]", batch=int(p_batch.shape[0]),
            wall_time_s=time.perf_counter() - t0,
            **_counters(backend, ctx, ops, core_rounds))
    if return_state:
        return result, (PiBar, H)
    return result


@partial(jax.jit, static_argnames=("max_iter", "backend"))
def _power_batch_loop(g: Graph, ctx, P, c, tol, max_iter: int, backend):
    inv_deg = g.inv_out_deg(P.dtype)
    dmask = g.dangling_mask

    def cond(state):
        _, Res, it = state
        return jnp.logical_and(jnp.any(Res > tol), it < max_iter)

    def body(state):
        Pi, Res, it = state
        Y = c * backend.push_batch(g, ctx, Pi * inv_deg[None, :])
        dm = jnp.sum(jnp.where(dmask[None, :], Pi, 0), axis=1, keepdims=True)
        Pi_new = Y + (c * dm + (1.0 - c)) * P
        res_new = jnp.linalg.norm(Pi_new - Pi, axis=1)
        # freeze rows that already met tol — the sequential stopping rule
        done = Res <= tol
        Pi_next = jnp.where(done[:, None], Pi, Pi_new)
        Res_next = jnp.where(done, Res, res_new)
        return Pi_next, Res_next, it + 1

    B = P.shape[0]
    init = (P, jnp.full((B,), jnp.inf, P.dtype), jnp.asarray(0, jnp.int32))
    return jax.lax.while_loop(cond, body, init)


def power_method_batch(
    g: Graph,
    p_batch: jnp.ndarray,
    *,
    c: float = 0.85,
    tol: float = 1e-10,
    max_iter: int = 1000,
    dtype=jnp.float64,
    step_impl: str = "dense",
    ctx=None,
) -> BatchSolverResult:
    """Batched power iteration with per-row freezing.

    ``p_batch`` float[B, n] → :class:`BatchSolverResult` with ``pi``
    ``dtype``[B, n].  Rows freeze the iteration their own L2 residual
    crosses ``tol`` (the sequential stopping rule).  ``step_impl``:
    jittable backends only ("dense", "ell"); "frontier" re-routes to
    "dense" because every vertex stays active under the power iteration,
    so frontier compression buys nothing.
    """
    backend = get_step_impl(step_impl)
    if not backend.capabilities().jittable:
        # every vertex stays active under the power iteration — frontier
        # compression buys nothing, so route through the dense batch path
        # (the non-jittable backend's ctx is meaningless there, drop it).
        return power_method_batch(g, p_batch, c=c, tol=tol, max_iter=max_iter,
                                  dtype=dtype, step_impl="dense")
    if ctx is None:
        ctx = backend.prepare(g)
    P = jnp.asarray(p_batch, dtype)
    t0 = time.perf_counter()
    Pi, Res, it = _power_batch_loop(g, ctx, P, float(c), float(tol),
                                    int(max_iter), backend)
    Pi = jax.block_until_ready(Pi)
    return BatchSolverResult(
        pi=Pi, iterations=int(it), residual=float(jnp.max(Res)),
        converged=bool(jnp.all(Res <= tol)),
        method=f"power_batch[{step_impl}]", batch=int(P.shape[0]),
        wall_time_s=time.perf_counter() - t0)


_BATCH_SOLVERS = {"ita": ita_batch, "power": power_method_batch}

# "leave this option at the solver's own default" marker: ita and power
# defaults differ (max_iter 10_000 vs 1000, xi vs tol), so None cannot
# stand in for "unset" (ctx=None is itself a meaningful value).
_UNSET = object()


def solve_pagerank_batch(g: Graph, p_batch: jnp.ndarray, method: str = "ita",
                         *, c=_UNSET, xi=_UNSET, tol=_UNSET, max_iter=_UNSET,
                         dtype=_UNSET, step_impl=_UNSET, ctx=_UNSET,
                         return_state=_UNSET) -> BatchSolverResult:
    """Solve PR(P, c, p_u) for every row p_u of ``p_batch`` in one pass.

    ``p_batch`` must be float[B, n]; ``method`` is "ita" or "power".  The
    solver options mirror :func:`ita_batch` / :func:`power_method_batch`
    (``xi``/``return_state`` are ITA's, ``tol`` is power's); anything left
    unset keeps that solver's own default.  Spelling the options out (vs.
    the old ``**kwargs`` funnel) makes a misspelled option a ``TypeError``
    here, at the API boundary.  The session form is
    ``PageRankEngine.solve_batch`` with a
    :class:`~repro.core.solver_config.BatchConfig`, which adds mesh
    sharding (``EnginePlan.mesh`` / ``BatchConfig.shard_batch``).
    """
    if method not in _BATCH_SOLVERS:
        raise KeyError(f"unknown batch solver {method!r}; "
                       f"available: {sorted(_BATCH_SOLVERS)}")
    p_batch = jnp.asarray(p_batch)
    if p_batch.ndim != 2 or p_batch.shape[1] != g.n:
        raise ValueError(f"p_batch must be [B, n={g.n}], got {p_batch.shape}")
    opts = {k: v for k, v in dict(
        c=c, xi=xi, tol=tol, max_iter=max_iter, dtype=dtype,
        step_impl=step_impl, ctx=ctx, return_state=return_state).items()
        if v is not _UNSET}
    return _BATCH_SOLVERS[method](g, p_batch, **opts)
