"""Beyond-paper: ITA on dynamic graphs + prioritized push.

The paper's §VII closes with "Having obtained the most fine-grained
decomposition of PageRank, we can continue discussing PageRank on dynamic
graph."  The constructive definition makes that step small, and we take it:

**Incremental ITA** (``ita_incremental``).  At convergence the unnormalized
information vector satisfies  ū = p + cP ū  (up to ξ).  After the graph
changes P → P', the *residual of the old solution under the new graph*

    r' = p + cP'ū − ū = c (P' − P) ū   (+ the old sub-ξ leftovers)

is supported only on destinations of edges whose SOURCE changed out-degree
or gained/lost edges — a tiny set for incremental updates.  By linearity
of the Neumann series,  ū' = ū + (I − cP')⁻¹ r',  so we simply run ITA
with h initialized from the run invariant (h₀ = p + cP'π̄_old − π̄_old —
exact across dangling-status changes; the naive cancelled form c(P'−P)ū
is first-order wrong when a dangling vertex gains an edge) and π̄
initialized to ū.  Deletions make h negative — the signed push is still
exact (the series is linear), with the active threshold on |h|.  The
saving is the global warm-up phase: on small-world graphs the correction
cascade still reaches most vertices, so expect ~1.5x fewer ops at ~0.25%
edge churn and more as edits shrink (measured in tests).

**Prioritized (Gauss-Southwell) ITA** (``ita_prioritized``).  The paper
proves pushes commute, so ANY order converges to the same π — their
threads use arrival order; Forward-Push literature uses max-residual
(Gauss-Southwell) order.  We push only the top-K |h| vertices per round:
fewer total operations on skewed graphs at the cost of more rounds — the
knob trades bandwidth against latency on a real mesh.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..graph.structure import Graph
from .backends import get_step_impl, run_ita_loop
from .metrics import SolverResult

__all__ = ["ita_residual_state", "ita_incremental", "ita_prioritized"]


def ita_residual_state(g: Graph, *, c: float = 0.85, xi: float = 1e-12,
                       dtype=jnp.float64, step_impl: str = "dense",
                       ctx=None):
    """Solve from scratch, returning (pi_bar_unnormalized, h_leftover).

    This is the warm-start state ``ita_incremental`` consumes.
    """
    h0 = jnp.ones((g.n,), dtype)
    pi0 = jnp.zeros((g.n,), dtype)
    h, pi_bar, n_active, ops, it, _ = run_ita_loop(
        g, h0, pi0, c=c, xi=xi, max_iter=100_000, impl=step_impl, signed=True,
        ctx=ctx)
    return pi_bar, h, float(ops), int(it)


def _warm_start(g, ctx, pi_bar, p_vec, c, backend):
    """The in-flight vector a warm start needs on the NEW graph.

    Exact from the run invariant  π̄ + h = p + cP π̄  (which the converged
    old state satisfies to ξ): under the new graph the required in-flight
    vector is  h₀ = p + cP'π̄_old − π̄_old.  This form is exact across
    dangling-status changes — the cancelled form c(P'−P)(π̄+h)+h is NOT: a
    previously-dangling vertex gaining an edge carries O(1) parked mass in
    h, and (P'−P) hits it at first order (caught by tests).
    """
    with jax.named_scope("delta_warm"):
        w = pi_bar * g.inv_out_deg(pi_bar.dtype) * c
        return p_vec + backend.push(g, ctx, w) - pi_bar


_warm_start_jit = jax.jit(_warm_start, static_argnames=("backend",))


def ita_incremental(
    g_old: Graph,
    g_new: Graph,
    pi_bar_old: jnp.ndarray,
    h_old: jnp.ndarray,
    *,
    c: float = 0.85,
    xi: float = 1e-12,
    max_iter: int = 100_000,
    step_impl: str = "dense",
    ctx=None,
    return_state: bool = False,
    p=None,
) -> SolverResult:
    """Update PageRank after edge insertions/deletions.

    r' = c·(P' − P)·ū + h_old, supported on dst(changed edges); runs the
    signed ITA from (π̄=ū_old, h=r') on the NEW graph.  Only ``g_new`` is
    read, and a :class:`~repro.graph.structure.Degrees` does for it where
    ``ctx`` holds the new graph's edges (the engine's live layout).

    ``return_state=True`` returns ``(result, (pi_bar, h))`` — the same
    warm-start pair :func:`ita_residual_state` produces, so a session
    (:class:`repro.core.engine.PageRankEngine`) can chain incremental
    updates without ever re-solving from scratch.

    ``p`` is the personalization the warm-start invariant is evaluated
    against, in the paper's h₀ scale (sum = n; ``None`` means the global
    ranking's uniform ones-vector).  Personalized entries — e.g. the
    one-hot PPR rows the result cache (``repro.core.cache``) revalidates —
    pass ``n · e_seed`` so the refreshed entry solves the same PR(P', c,
    p) its cached value did.
    """
    dtype = pi_bar_old.dtype
    backend = get_step_impl(step_impl)
    if ctx is None:
        ctx = backend.prepare(g_new)  # ctx belongs to the NEW graph
    t0 = time.perf_counter()
    if p is None:
        p_vec = jnp.ones((g_new.n,), dtype)  # paper scale: h₀ = n·(e/n) = 1
    else:
        p_vec = jnp.asarray(p, dtype)
    warm = (_warm_start_jit if backend.capabilities().jittable
            else _warm_start)
    r = warm(g_new, ctx, pi_bar_old, p_vec, float(c), backend)

    h, pi_bar, n_active, ops, it, core = run_ita_loop(
        g_new, r, pi_bar_old, c=c, xi=xi, max_iter=max_iter, impl=step_impl,
        signed=True, ctx=ctx)
    folded = pi_bar + h
    pi = folded / jnp.sum(folded)
    pi = jax.block_until_ready(pi)
    result = SolverResult(
        pi=pi, iterations=int(it), residual=float(xi), ops=float(ops),
        converged=bool(int(n_active) == 0), method="ita_incremental",
        wall_time_s=time.perf_counter() - t0,
        core_rounds=None if core is None else int(core),
    )
    if return_state:
        return result, (pi_bar, h)
    return result


@partial(jax.jit, static_argnames=("max_iter", "k", "backend"))
def _prioritized_loop(g: Graph, ctx, h0, c, xi, k: int, max_iter: int,
                      backend):
    inv_deg = g.inv_out_deg(h0.dtype)
    non_dangling = jnp.logical_not(g.dangling_mask)

    def cond(state):
        _, _, n_active, _, it = state
        return jnp.logical_and(n_active > 0, it < max_iter)

    def body(state):
        h, pi_bar, _, ops_total, it = state
        eligible = jnp.logical_and(h > xi, non_dangling)
        # Gauss-Southwell: push only the top-k residuals this round
        hv = jnp.where(eligible, h, -jnp.inf)
        kth = jax.lax.top_k(hv, k)[0][-1]
        active = jnp.logical_and(eligible, h >= jnp.maximum(kth, xi))
        h_act = jnp.where(active, h, 0)
        pi_bar = pi_bar + h_act
        pushed = backend.push(g, ctx, h_act * inv_deg * c)
        h = jnp.where(active, 0, h) + pushed
        # Eligibility is counted AFTER the push: the pre-push count is
        # nonzero by construction on every round that pushed anything, so
        # returning it made the loop run one extra zero-mass round (a full
        # wasted B·m push) after convergence before cond() saw 0
        # (tests/test_dynamic.py::TestPrioritized::test_no_extra_round).
        n_elig = jnp.sum(jnp.logical_and(h > xi, non_dangling),
                         dtype=jnp.int32)
        ops = jnp.sum(jnp.where(active, g.out_deg, 0).astype(jnp.float32),
                      dtype=jnp.float32)
        return h, pi_bar, n_elig, ops_total + ops, it + 1

    init = (h0, jnp.zeros_like(h0), jnp.asarray(1, jnp.int32),
            jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32))
    return jax.lax.while_loop(cond, body, init)


def ita_prioritized(g: Graph, *, c: float = 0.85, xi: float = 1e-10,
                    k: Optional[int] = None, max_iter: int = 1_000_000,
                    dtype=jnp.float64,
                    step_impl: str = "dense") -> SolverResult:
    """Top-K max-residual push (order freedom the paper's §IV proves)."""
    from .backends import available_step_impls

    backend = get_step_impl(step_impl)
    if not backend.capabilities().jittable:
        raise ValueError(
            f"ita_prioritized needs a jittable backend (top_k inside "
            f"while_loop); got step_impl={step_impl!r}; "
            f"jittable: {available_step_impls(jittable_only=True)}")
    ctx = backend.prepare(g)
    k = k or max(g.n // 16, 1)
    t0 = time.perf_counter()
    h0 = jnp.ones((g.n,), dtype)
    h, pi_bar, n_active, ops, it = _prioritized_loop(
        g, ctx, h0, float(c), float(xi), int(k), int(max_iter), backend)
    pi_bar = pi_bar + h
    pi = pi_bar / jnp.sum(pi_bar)
    pi = jax.block_until_ready(pi)
    return SolverResult(
        pi=pi, iterations=int(it), residual=float(xi), ops=float(ops),
        converged=bool(int(n_active) == 0), method="ita_prioritized",
        wall_time_s=time.perf_counter() - t0,
    )
