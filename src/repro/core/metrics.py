"""Convergence metrics used across solvers, tests and benchmarks.

The paper's §VI metrics:
  RES = ||pi(k) - pi(k-1)||_2      (successive-iterate residual)
  ERR = max_i |pi_i - pi*_i| / pi*_i   (max relative error vs. a reference)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

__all__ = ["res_l2", "err_max_rel", "l1_diff", "SolverResult"]


def res_l2(pi_new: jnp.ndarray, pi_old: jnp.ndarray) -> jnp.ndarray:
    return jnp.linalg.norm(pi_new - pi_old, ord=2)


def l1_diff(pi_new: jnp.ndarray, pi_old: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(jnp.abs(pi_new - pi_old))


def err_max_rel(pi: jnp.ndarray, pi_true: jnp.ndarray, eps: float = 0.0) -> jnp.ndarray:
    """Paper's ERR.  ``eps`` guards division when a true value is ~0.

    Entries where ``max(|pi_true|, eps)`` is exactly 0 — unreferenced
    vertices can carry a genuinely zero reference score — contribute their
    *absolute* error instead of dividing by zero (which returned inf/nan
    for any mismatch at such an entry and poisoned the max).
    """
    denom = jnp.maximum(jnp.abs(pi_true), eps)
    safe = jnp.where(denom > 0, denom, 1.0)
    return jnp.max(jnp.abs(pi - pi_true) / safe)


@dataclasses.dataclass
class SolverResult:
    """Uniform return type for every PageRank solver in ``repro.core``.

    ``ops`` is the paper's operation count M(T): for the power method
    (2m+n) per iteration; for ITA the sum over iterations of the out-degree
    of the *active* frontier (Formula 15) — the quantity behind the paper's
    "special vertices decrease ITA's calculations" claim.  ``core_rounds``
    counts the rounds whose push walked the referenced core's edge list in
    place of all m edges (``None`` where the backend keeps no such list).
    """

    pi: jnp.ndarray
    iterations: int
    residual: float
    ops: float
    converged: bool
    method: str
    # Optional per-iteration traces (instrumented python-loop mode only).
    res_history: Optional[list] = None
    active_history: Optional[list] = None
    ops_history: Optional[list] = None
    wall_time_s: Optional[float] = None
    core_rounds: Optional[int] = None
    # an edge delta's refresh (DeltaQuery on the live layout): whether the
    # delta overflowed the layout's slack and re-laid it out (0 or 1), and
    # the core list's live edges after it
    relayouts: Optional[int] = None
    core_edges: Optional[int] = None
    # an edge delta's: the edge-set version it made
    graph_version: Optional[int] = None

    def stats(self) -> dict:
        return dict(
            method=self.method,
            iterations=int(self.iterations),
            residual=float(self.residual),
            ops=float(self.ops),
            converged=bool(self.converged),
            wall_time_s=self.wall_time_s,
            core_rounds=self.core_rounds,
            relayouts=self.relayouts,
            core_edges=self.core_edges,
            graph_version=self.graph_version,
        )
