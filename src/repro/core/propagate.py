"""The single edge-propagation primitive shared by every solver: one
application of the raw transition matrix ``P``.

    (P @ x)_i  =  sum_{j : (j->i) in E}  x_j / out_deg(j)

On TPU this is the paper's "push" re-expressed as a *pull over dst-sorted
edges*: gather ``x[src] * inv_deg[src]`` then ``segment_sum`` by ``dst``.
Sorted segments compile to a contention-free scan — the TPU replacement for
the paper's atomic `h_u += c*h_i/deg_i` (DESIGN.md §2).

``spmv_p`` is the reference implementation; ``repro.kernels.spmv_ell``
provides the Pallas-blocked version used on the perf path, with this
function as its oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.structure import Graph

__all__ = ["spmv_p", "push_weighted", "dangling_mass"]


def spmv_p(g: Graph, x: jnp.ndarray, *, inv_deg: jnp.ndarray | None = None) -> jnp.ndarray:
    """y = P @ x with the raw (dangling-preserving) transition matrix.

    Columns of P at dangling vertices are zero — mass sent *from* a dangling
    vertex is simply never gathered, which is exactly the paper's
    "transmitting terminates at dangling vertices".
    """
    if inv_deg is None:
        inv_deg = g.inv_out_deg(x.dtype)
    contrib = (x * inv_deg)[g.src]
    return jax.ops.segment_sum(contrib, g.dst, num_segments=g.n)


def push_weighted(g: Graph, per_src: jnp.ndarray) -> jnp.ndarray:
    """Scatter an arbitrary per-source scalar along edges (no 1/deg scale).

    For values already scaled per source, e.g. a residual already divided
    by degree.
    """
    return jax.ops.segment_sum(per_src[g.src], g.dst, num_segments=g.n)


def dangling_mass(g: Graph, x: jnp.ndarray) -> jnp.ndarray:
    """sum of x over dangling vertices — the power method's rank-1 term."""
    return jnp.sum(jnp.where(g.dangling_mask, x, 0))
