"""Edge partitioners for distributed ITA.

1-D: vertices split into R contiguous dst-blocks; device r owns all edges
whose dst lands in block r.  h is replicated; the per-step collective is
one all-gather of the new h blocks.  Right when n fits per-device HBM.

2-D (R rows × C cols — the production layout): device (i, j) owns the edge
block {(u→v) : v ∈ row-block i, u ∈ col-block j}.  h lives *column-sharded*
and row-replicated; each step is

    local segment-sum → psum_scatter over cols → all-gather over rows

with NO all-to-all and no replicated h.  The column layout is the
block-cyclic permutation q(i·nr + j·sub + s) = j·nc + i·sub + s (sub =
n/(R·C)) chosen precisely so that psum_scatter chunks reassemble into
contiguous column blocks — see core/distributed.py.

Batched PPR (``partition_cols``): the [B, n] serving pass shards the batch
over "data" and (optionally) the vertex axis over "model", so it needs the
2-D edge blocks with a single row group — ``partition_2d(g, 1, C)``.  With
R = 1 the block-cyclic permutation degenerates to the identity (i = 0, so
q = j·sub + s = id), which is what lets the batched solver keep natural
vertex order: psum_scatter chunks of the [n_pad] dst range ARE the
contiguous column blocks.  ``partition_cols`` wraps that special case.

Both partitioners are host-side numpy (rank-0 data-pipeline work) and
produce static, padded per-device arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .structure import Graph

__all__ = ["Partition1D", "Partition2D", "partition_1d", "partition_2d",
           "partition_cols"]


def _round_up(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


@dataclasses.dataclass
class Partition1D:
    """R dst-blocks; edge arrays [R, e_pad] with global src, local dst."""
    src: np.ndarray          # int32 [R, e_pad] (global ids; pad = n)
    dst_local: np.ndarray    # int32 [R, e_pad] (dst - r*nr; pad = nr)
    n: int
    n_pad: int
    nr: int                  # rows per block
    e_pad: int
    R: int


def partition_1d(g: Graph, R: int, *, pad_factor: float = 1.05) -> Partition1D:
    n_pad = _round_up(g.n, R)
    nr = n_pad // R
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    owner = dst // nr
    counts = np.bincount(owner, minlength=R)
    e_pad = _round_up(int(counts.max() * pad_factor) + 8, 8)
    src_out = np.full((R, e_pad), g.n, np.int32)       # sentinel src = n
    dst_out = np.full((R, e_pad), nr, np.int32)        # sentinel dst = nr
    for r in range(R):
        sel = owner == r
        k = int(counts[r])
        src_out[r, :k] = src[sel]
        dst_out[r, :k] = dst[sel] - r * nr
    return Partition1D(src=src_out, dst_local=dst_out, n=g.n, n_pad=n_pad,
                       nr=nr, e_pad=e_pad, R=R)


@dataclasses.dataclass
class Partition2D:
    """R×C edge blocks in the block-cyclic column layout."""
    src_local: np.ndarray    # int32 [R, C, e_pad] (index into column block; pad = nc)
    dst_local: np.ndarray    # int32 [R, C, e_pad] (index into row block;    pad = nr)
    perm: np.ndarray         # int64 [n_pad] natural-id -> column-layout position
    inv_perm: np.ndarray     # column-layout position -> natural id (or n for pad)
    n: int
    n_pad: int
    nr: int
    nc: int
    sub: int
    e_pad: int
    R: int
    C: int

    def to_col_layout(self, x: np.ndarray, fill=0.0) -> np.ndarray:
        """Scatter a natural-order [n] vector into the padded column layout."""
        out = np.full(self.n_pad, fill, dtype=x.dtype)
        out[self.perm[: self.n]] = x
        return out

    def from_col_layout(self, x: np.ndarray) -> np.ndarray:
        return x[self.perm[: self.n]]


def partition_2d(g: Graph, R: int, C: int, *, pad_factor: float = 1.05) -> Partition2D:
    n_pad = _round_up(g.n, R * C)
    nr, nc, sub = n_pad // R, n_pad // C, n_pad // (R * C)
    src = np.asarray(g.src).astype(np.int64)
    dst = np.asarray(g.dst).astype(np.int64)

    # column-layout permutation: natural id g = i*nr + j*sub + s
    #   -> position q = j*nc + i*sub + s
    ids = np.arange(n_pad, dtype=np.int64)
    i = ids // nr
    rem = ids % nr
    j = rem // sub
    s = rem % sub
    perm = j * nc + i * sub + s
    inv_perm = np.empty(n_pad, np.int64)
    inv_perm[perm] = ids

    row = dst // nr
    col = (src % nr) // sub
    owner = row * C + col
    counts = np.bincount(owner, minlength=R * C)
    e_pad = _round_up(int(counts.max() * pad_factor) + 8, 8)

    src_out = np.full((R, C, e_pad), nc, np.int32)     # sentinel -> zero slot
    dst_out = np.full((R, C, e_pad), nr, np.int32)
    # local src index within column block j: perm[src] - j*nc
    src_col_local = (perm[src] % nc).astype(np.int32)
    dst_row_local = (dst % nr).astype(np.int32)
    order = np.argsort(owner, kind="stable")
    so, do, oo = src_col_local[order], dst_row_local[order], owner[order]
    starts = np.searchsorted(oo, np.arange(R * C))
    ends = np.searchsorted(oo, np.arange(R * C) + 1)
    for r in range(R):
        for c_ in range(C):
            k = r * C + c_
            lo, hi = starts[k], ends[k]
            src_out[r, c_, : hi - lo] = so[lo:hi]
            dst_out[r, c_, : hi - lo] = do[lo:hi]
    return Partition2D(src_local=src_out, dst_local=dst_out, perm=perm,
                       inv_perm=inv_perm, n=g.n, n_pad=n_pad, nr=nr, nc=nc,
                       sub=sub, e_pad=e_pad, R=R, C=C)


def partition_cols(g: Graph, C: int, *, pad_factor: float = 1.05) -> Partition2D:
    """Column-only edge partition for the batched-PPR pass.

    ``partition_2d(g, 1, C)``: device column j owns every edge whose src
    falls in vertex block [j·nc, (j+1)·nc); dst indices stay global
    (nr == n_pad) and the layout permutation is the identity, so [B, n]
    state needs no reordering on entry or exit.  See core/distributed.py
    ``ita_batch_distributed`` for the consuming schedule; this COO form
    feeds its dense realisation, while the same column geometry re-bucketed
    per block (``Graph.ell_partitioned(C)`` / ``sparse.ell.ELLCols``)
    feeds the sharded-ELL kernel realisation.
    """
    part = partition_2d(g, 1, C, pad_factor=pad_factor)
    assert np.array_equal(part.perm, np.arange(part.n_pad)), \
        "R=1 column layout must be the identity permutation"
    return part
