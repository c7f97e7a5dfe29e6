"""The plain reference: float64 power iteration on the host.

Copied from ``chip_smoke.py`` (``host_pagerank``) and vectorized over
personalization rows: ``pi <- c P pi + c (d . pi) p + (1 - c) p`` from
``pi = p``, for each row ``p`` of ``P``, until every row's l2 step is at
most ``tol`` (``d`` marks the dangling vertices).  These are the
semantics of ``repro.core.reference_pagerank``; the push is one sparse
product over the benchmark's own edge list.  Nothing here imports the
program or JAX.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse

__all__ = ["pagerank_rows", "l1_bound", "topk_err", "finite"]

REF_TOL = 1e-14  # l2 step at which the reference stops


def pagerank_rows(src, dst, n: int, P, *, c: float, tol: float = REF_TOL,
                  max_iter: int = 1000):
    """Reference PageRank for each row of ``P`` (float[S, n]).

    Returns ``(Pi, iterations)`` with ``Pi`` float64[S, n], each row the
    ranking for its personalization row.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    P = np.atleast_2d(np.asarray(P, np.float64))
    out_deg = np.bincount(src, minlength=n)
    inv_deg = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1), 0.0)
    dangling = out_deg == 0
    A = scipy.sparse.csr_matrix((inv_deg[src], (dst, src)), shape=(n, n))
    Pt = np.ascontiguousarray(P.T)
    X = Pt.copy()
    for it in range(1, max_iter + 1):
        new = c * (A @ X)
        new += (c * X[dangling].sum(axis=0) + (1.0 - c)) * Pt
        step = np.sqrt(((new - X) ** 2).sum(axis=0))
        X = new
        if np.all(step <= tol):
            return np.ascontiguousarray(X.T), it
    raise RuntimeError(f"reference did not reach an l2 step of {tol:g} in "
                       f"{max_iter} iterations")


def l1_bound(n: int, *, c: float, xi: float, tol: float = REF_TOL) -> float:
    """Largest L1 distance an exact ITA answer may have from the reference.

    ITA stops with at most ``xi`` left on each non-dangling vertex, out of
    a total mass of at least ``n``; pushing that rest on would move at
    most ``c / (1 - c)`` times it, and normalizing at most doubles the
    distance: ``2 c xi / (1 - c)``.  The reference stops at an l2 step
    ``tol``, so it is within ``c / (1 - c) sqrt(n) tol`` of the fixed point.
    This is the analytic scale of the check; the limits themselves are set
    from readings (PERF.md).
    """
    return 2 * c * xi / (1 - c) + c / (1 - c) * np.sqrt(n) * tol


def topk_err(idx, scores, ref, k: int) -> float:
    """Widest gap between a served top-k and the reference's own top-k.

    For each position j: the served score against the reference's j-th
    largest value, and the reference's value at the served vertex against
    that same j-th largest value.  Ties in the reference read 0, whichever
    tied vertex was served; a wrong vertex or a wrong score reads its gap.
    An answer that is not ``k`` vertices of the graph reads infinity.
    """
    idx = np.asarray(idx, np.int64)
    scores = np.asarray(scores, np.float64)
    if idx.shape != (k,) or scores.shape != (k,) or idx.min() < 0 \
            or idx.max() >= ref.size:
        return float("inf")
    best = -np.sort(-ref)[:k]
    return float(max(np.max(np.abs(scores - best)),
                     np.max(np.abs(ref[idx] - best))))


def finite(x) -> float:
    """A compared number; anything not finite reads as infinitely far."""
    x = float(x)
    return x if np.isfinite(x) else float("inf")
