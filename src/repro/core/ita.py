"""ITA — the Information Transmitting Algorithm (paper Algorithm 3).

Semantics (faithful to §IV):
  every vertex holds ⟨pi_bar_i, h_i⟩;  while some *non-dangling* vertex has
  h_i > xi:  pi_bar_i += h_i,  push c·h_i/deg_i along every out-edge,
  h_i = 0.  Dangling vertices never push — their received information parks
  in h.  On termination  pi_i = pi_bar_i / Σ_j pi_bar_j, with the in-flight
  residual h folded into pi_bar (this is what makes pi_bar ∝ Σ_r (cP)^r p,
  Eq. 7, exact).

TPU schedule: the paper proves {pi_ij(r)} is commutative/associative
("the processing order ... has no effect on the final results", §IV), so any
grouping of pushes is exact.  We use the *synchronous bulk* grouping — all
currently-active vertices push at once — which turns the inner loop into a
masked SpMV (one gather + one sorted segment_sum), the shape that roofs on
TPU.  The asynchronous CPU schedule of the paper is a different traversal of
the same commutative sum; equivalence is asserted in tests to ~1e-12
against the power method.

Operation accounting reproduces Formula (15):
    m(t) = Σ_{v active at t} out_deg(v),   M(T) = Σ_t m(t)
and the active-vertex counter is the Management-thread CNT of Algorithm 3.

Beyond-paper fast paths (selected by ``step_impl``; see core/backends.py):
  * "dense"    — masked SpMV over the edge list (paper-faithful baseline);
                 once h is zero outside the referenced core (§III: a
                 vertex of weak-unreferenced level k receives nothing
                 after round k), each round walks only the core's
                 out-edges.  The round checks that on h itself, and the
                 core list's sums equal the full list's bit for bit.
  * "frontier" — frontier compression: gathers the active sub-frontier into
                 fixed-size buckets so the per-iteration edge working set
                 shrinks with the active set (attacks the memory term).
  * "ell"      — bucketed-ELL layout via the Pallas kernel
                 ``repro.kernels.spmv_ell`` (interpret-mode on CPU).
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..graph.structure import Graph
from .backends import (_ita_round, count_core, get_step_impl,
                       ita_step_impl, run_ita_loop)
from .metrics import SolverResult, err_max_rel, res_l2

__all__ = ["ita", "ita_traced", "ita_step", "ita_fixed_point"]


def ita_step(
    g: Graph,
    h: jnp.ndarray,
    pi_bar: jnp.ndarray,
    c: float,
    xi: float,
    inv_deg: jnp.ndarray,
    non_dangling: jnp.ndarray,
):
    """One synchronous ITA round.  Returns (h', pi_bar', n_active, ops).

    Pure function of its inputs — reused verbatim by the jitted loop, the
    traced loop, the distributed shard_map solver and the Pallas kernel's
    oracle tests.  This is the ``"dense"`` backend's step; other layouts
    live in ``core/backends.py``.
    """
    return ita_step_impl(get_step_impl("dense"), g, None, h, pi_bar, c, xi,
                         inv_deg, non_dangling)


def _default_h0(g: Graph, p, dtype) -> jnp.ndarray:
    # Paper initialisation: h_i = 1 (== n * (e/n)).  For a general
    # personalisation p the information scale is n*p so xi keeps the same
    # per-vertex meaning as in the paper.
    if p is None:
        return jnp.ones((g.n,), dtype=dtype)
    return (p * g.n).astype(dtype)


def ita(
    g: Graph,
    *,
    c: float = 0.85,
    xi: float = 1e-10,
    p: Optional[jnp.ndarray] = None,
    max_iter: int = 10_000,
    dtype=jnp.float64,
    step_impl: str = "dense",
    ctx=None,
) -> SolverResult:
    """Fast path: device-resident ``while_loop`` for jittable backends,
    host-driven frontier loop otherwise (``step_impl`` selects, see
    core/backends.py).  ``ctx`` accepts a prepared backend context (from
    ``get_step_impl(step_impl).prepare(g)``) so a session holding one —
    :class:`repro.core.engine.PageRankEngine` — skips re-preparation."""
    h0 = _default_h0(g, p, dtype)
    t0 = time.perf_counter()
    h, pi_bar, n_active, ops, it, *core = run_ita_loop(
        g, h0, jnp.zeros_like(h0), c=c, xi=xi, max_iter=max_iter,
        impl=step_impl, ctx=ctx)
    core = core[0] if core else None
    # Fold the in-flight residual — including everything parked on dangling
    # vertices — then normalize (Algorithm 3 final step).
    pi_bar = pi_bar + h
    pi = pi_bar / jnp.sum(pi_bar)
    with TraceAnnotation("solve.wait"):
        pi = jax.block_until_ready(pi)
        wall = time.perf_counter() - t0
        return SolverResult(
            pi=pi,
            iterations=int(it),
            residual=float(xi),
            ops=float(ops),
            converged=bool(int(n_active) == 0),
            method="ita" if step_impl == "dense" else f"ita[{step_impl}]",
            wall_time_s=wall,
            core_rounds=None if core is None else int(core),
        )


def ita_traced(
    g: Graph,
    *,
    c: float = 0.85,
    xi: float = 1e-10,
    p: Optional[jnp.ndarray] = None,
    max_iter: int = 10_000,
    dtype=jnp.float64,
    pi_true: Optional[jnp.ndarray] = None,
    step_impl: str = "dense",
    ctx=None,
) -> SolverResult:
    """Instrumented loop: per-iteration RES (between successive normalized
    estimates), active-set size (Management thread's CNT), per-round ops
    m(t), and ERR when a reference is provided.  Used by the Fig. 1/2/3/5
    reproductions and the active-set-decay analysis."""
    backend = get_step_impl(step_impl)
    if ctx is None:
        ctx = backend.prepare(g)
    h = _default_h0(g, p, dtype)
    pi_bar = jnp.zeros_like(h)
    inv_deg = g.inv_out_deg(dtype)
    non_dangling = jnp.logical_not(g.dangling_mask)

    def _step(h, pb):
        return _ita_round(backend, g, ctx, h, pb, c, xi, inv_deg,
                          non_dangling, signed=False)

    step = jax.jit(_step) if backend.capabilities().jittable else _step

    res_hist, active_hist, ops_hist, err_hist = [], [], [], []
    est_prev = None
    ops_total = 0.0
    it = core_rounds = 0
    t0 = time.perf_counter()
    while it < max_iter:
        h, pi_bar, n_active, ops, core = step(h, pi_bar)
        n_active = int(n_active)
        if n_active == 0 and it > 0:
            break
        folded = pi_bar + h
        est = folded / jnp.sum(folded)
        if est_prev is not None:
            res_hist.append(float(res_l2(est, est_prev)))
        if pi_true is not None:
            err_hist.append(float(err_max_rel(est, pi_true)))
        est_prev = est
        active_hist.append(n_active)
        ops_hist.append(float(ops))
        ops_total += float(ops)
        core_rounds = int(count_core(core_rounds, core))
        it += 1
        if n_active == 0:
            break
    pi_bar = pi_bar + h
    pi = pi_bar / jnp.sum(pi_bar)
    pi = jax.block_until_ready(pi)
    wall = time.perf_counter() - t0
    out = SolverResult(
        pi=pi,
        iterations=it,
        residual=res_hist[-1] if res_hist else float("nan"),
        ops=ops_total,
        converged=True,
        method="ita" if step_impl == "dense" else f"ita[{step_impl}]",
        res_history=res_hist,
        active_history=active_hist,
        ops_history=ops_hist,
        wall_time_s=wall,
        core_rounds=(core_rounds if backend.core_edges(ctx) is not None
                     else None),
    )
    if pi_true is not None:
        out.err_history = err_hist  # type: ignore[attr-defined]
    return out


def ita_fixed_point(g: Graph, *, c: float = 0.85, dtype=jnp.float64,
                    n_terms: int = 200, p: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Direct Neumann-series oracle  pi ∝ Σ_{r<n_terms} (cP)^r p  (Eq. 7).

    O(n_terms · m) — test/benchmark reference only, never the fast path.
    """
    from .propagate import spmv_p

    if p is None:
        p = jnp.full((g.n,), 1.0 / g.n, dtype=dtype)
    p = p.astype(dtype)
    inv_deg = g.inv_out_deg(dtype)

    def body(_, carry):
        term, acc = carry
        term = c * spmv_p(g, term, inv_deg=inv_deg)
        return term, acc + term

    _, acc = jax.lax.fori_loop(0, n_terms, body, (p, p))
    return acc / jnp.sum(acc)
