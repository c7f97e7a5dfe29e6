"""Bucketed ELLPACK (padded-CSR) layout — the TPU-native edge layout.

GPU push kernels scatter with atomics; TPUs want dense, statically-shaped
tiles.  We therefore re-block the dst-sorted edge list into ELL buckets:

  * rows (= destination vertices) are grouped by in-degree into buckets
    with padded widths k ∈ {8, 16, 32, ..., k_max};
  * each bucket is a dense int32 [rows_b, k_b] matrix of *source* indices,
    padded with a sentinel index n that points at an appended zero slot of
    the operand vector — gathers of the sentinel contribute exactly 0, so
    no mask multiply is needed in the inner loop;
  * rows with in-degree > k_max spill to an overflow COO handled by
    segment_sum (heavy-tail rows are rare but huge in web graphs — padding
    them would dominate the footprint).

This is the layout consumed by the Pallas kernel ``repro.kernels.spmv_ell``.
Padding overhead is reported by ``ELLGraph.fill_stats`` and asserted < 2x
in tests for power-law graphs.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.structure import Graph

__all__ = ["ELLBucket", "ELLGraph", "ell_from_graph", "spmv_ell_ref",
           "ELLColsBucket", "ELLCols", "ell_cols_from_graph",
           "spmv_ell_cols_ref"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLBucket:
    row_ids: jnp.ndarray   # int32[rows_b]  — destination vertex of each row
    src_idx: jnp.ndarray   # int32[rows_b, k_b] — source indices, sentinel-padded
    k: int = dataclasses.field(metadata=dict(static=True))
    rows: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLGraph:
    buckets: tuple            # tuple[ELLBucket, ...]
    ovf_src: jnp.ndarray      # overflow COO (sorted by dst)
    ovf_dst: jnp.ndarray
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    sentinel: int = dataclasses.field(metadata=dict(static=True))  # == n

    def fill_stats(self) -> dict:
        padded = sum(b.rows * b.k for b in self.buckets)
        real = self.m - int(self.ovf_src.shape[0])
        return dict(
            padded_slots=padded,
            real_edges=self.m,
            overflow_edges=int(self.ovf_src.shape[0]),
            fill_ratio=padded / max(real, 1),
            n_buckets=len(self.buckets),
        )


def ell_from_graph(
    g: Graph,
    *,
    widths: Sequence[int] = (8, 32, 128),
    row_align: int = 8,
) -> ELLGraph:
    """Host-side conversion (one-time data-pipeline work)."""
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    in_deg = np.asarray(g.in_deg)
    n = g.n
    widths = sorted(widths)
    k_max = widths[-1]

    # CSR over dst (edges already dst-sorted)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(in_deg, out=offsets[1:])

    buckets = []
    ovf_src_parts, ovf_dst_parts = [], []
    prev_w = 0
    for w in widths:
        if w == k_max:
            rows = np.nonzero(in_deg > prev_w)[0]
        else:
            rows = np.nonzero((in_deg > prev_w) & (in_deg <= w))[0]
        prev_w = w
        if rows.size == 0:
            continue
        rows_pad = int(np.ceil(rows.size / row_align) * row_align)
        idx = np.full((rows_pad, w), n, dtype=np.int32)  # sentinel = n
        for r, v in enumerate(rows):
            lo, hi = offsets[v], offsets[v + 1]
            take = min(hi - lo, w)
            idx[r, :take] = src[lo:lo + take]
            if hi - lo > w:  # overflow tail to COO
                ovf_src_parts.append(src[lo + w:hi])
                ovf_dst_parts.append(dst[lo + w:hi])
        row_ids = np.full((rows_pad,), n, dtype=np.int32)
        row_ids[: rows.size] = rows
        buckets.append(ELLBucket(
            row_ids=jnp.asarray(row_ids),
            src_idx=jnp.asarray(idx),
            k=int(w),
            rows=rows_pad,
        ))

    ovf_src = np.concatenate(ovf_src_parts) if ovf_src_parts else np.zeros(0, np.int32)
    ovf_dst = np.concatenate(ovf_dst_parts) if ovf_dst_parts else np.zeros(0, np.int32)
    order = np.argsort(ovf_dst, kind="stable")
    return ELLGraph(
        buckets=tuple(buckets),
        ovf_src=jnp.asarray(ovf_src[order].astype(np.int32)),
        ovf_dst=jnp.asarray(ovf_dst[order].astype(np.int32)),
        n=n,
        m=g.m,
        sentinel=n,
    )


# ---------------------------------------------------------------------------
# column-partitioned ELL: the vertex-sharded serving layout
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLColsBucket:
    """One in-degree bucket, stacked across the C column blocks.

    All blocks share one padded row count so the [C, ...] arrays can be
    sharded over a mesh "model" axis with identical per-device shapes —
    the same geometry-unification trick ``Partition2D`` plays with
    ``e_pad``.  Sentinels: ``row_ids`` pads with ``n_pad`` (one past the
    dst range), ``src_idx`` with ``nc`` (the local zero slot).
    """

    row_ids: jnp.ndarray   # int32[C, rows_b]      — global dst rows
    src_idx: jnp.ndarray   # int32[C, rows_b, k_b] — block-local src indices
    k: int = dataclasses.field(metadata=dict(static=True))
    rows: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLCols:
    """C column-block ELL views of one graph (``partition_cols`` geometry).

    Block j owns every edge whose *source* falls in vertex block
    [j·nc, (j+1)·nc); destinations stay global (the R = 1 column layout is
    the identity permutation, see ``graph/partition.partition_cols``).
    Within each block, dst rows are re-bucketed by their block-local
    in-degree — a row heavy in the full graph may be light inside one
    column block, so per-block bucketing is tighter than slicing the
    global ELL.  The consuming schedule (``core/distributed.py``) runs the
    batched Pallas kernel on each device's block and ``psum_scatter``s the
    [n_pad] partials over the "model" axis.
    """

    buckets: tuple            # tuple[ELLColsBucket, ...]
    ovf_src: jnp.ndarray      # int32[C, ovf_pad] — block-local src (pad nc)
    ovf_dst: jnp.ndarray      # int32[C, ovf_pad] — global dst (pad n_pad),
    #                           per-block dst-sorted for sorted segment_sum
    n: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    nc: int = dataclasses.field(metadata=dict(static=True))  # block width;
    #                           also the local src sentinel / zero slot
    C: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))

    def signature(self) -> tuple:
        """Hashable static geometry — the jitted-loop cache key in
        ``core/distributed.py`` (operands are passed per call)."""
        return (self.n_pad, self.nc, self.C,
                tuple((b.rows, b.k) for b in self.buckets),
                int(self.ovf_src.shape[-1]))

    def fill_stats(self) -> dict:
        padded = sum(b.rows * b.k for b in self.buckets) * self.C
        real = self.m - int(np.sum(np.asarray(self.ovf_src) < self.nc))
        return dict(
            padded_slots=padded,
            real_edges=self.m,
            overflow_slots=int(self.ovf_src.shape[0] * self.ovf_src.shape[1]),
            fill_ratio=padded / max(real, 1),
            n_buckets=len(self.buckets),
            blocks=self.C,
        )


def ell_cols_from_graph(
    g: Graph,
    C: int,
    *,
    widths: Sequence[int] = (8, 32, 128),
    row_align: int = 8,
) -> ELLCols:
    """Host-side conversion of the C-way column partition to per-block ELL.

    One-time data-pipeline work, cached on the graph via
    :meth:`repro.graph.structure.Graph.ell_partitioned`.  The union of all
    blocks' (src → dst) slots is exactly the edge set — asserted
    row-for-row against :func:`ell_from_graph` in tests/test_ell_sharded.py.
    """
    if C < 1:
        raise ValueError(f"C must be >= 1, got {C}")
    n_pad = ((g.n + C - 1) // C) * C
    nc = n_pad // C
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    widths = sorted(widths)
    k_max = widths[-1]
    col = src // nc

    # per-(block, width) geometry first, so every bucket's row count can be
    # unified across blocks before any array is filled
    blk_rows: dict = {}
    blk_deg = []
    blk_offsets = []
    blk_src_local = []
    blk_dst = []
    for j in range(C):
        sel = col == j
        s_j = (src[sel] - j * nc).astype(np.int32)
        d_j = dst[sel]                      # stays globally dst-sorted
        deg_j = np.bincount(d_j, minlength=n_pad)
        offs_j = np.zeros(n_pad + 1, dtype=np.int64)
        np.cumsum(deg_j, out=offs_j[1:])
        blk_deg.append(deg_j)
        blk_offsets.append(offs_j)
        blk_src_local.append(s_j)
        blk_dst.append(d_j)
        prev_w = 0
        for w in widths:
            if w == k_max:
                rows = np.nonzero(deg_j > prev_w)[0]
            else:
                rows = np.nonzero((deg_j > prev_w) & (deg_j <= w))[0]
            prev_w = w
            blk_rows[(j, w)] = rows

    buckets = []
    ovf_parts = [([], []) for _ in range(C)]
    for w in widths:
        rows_max = max(blk_rows[(j, w)].size for j in range(C))
        if rows_max == 0:
            continue
        rows_pad = int(np.ceil(rows_max / row_align) * row_align)
        row_ids = np.full((C, rows_pad), n_pad, dtype=np.int32)
        idx = np.full((C, rows_pad, w), nc, dtype=np.int32)
        for j in range(C):
            rows = blk_rows[(j, w)]
            offs_j, s_j, d_j = blk_offsets[j], blk_src_local[j], blk_dst[j]
            row_ids[j, : rows.size] = rows
            for r, v in enumerate(rows):
                lo, hi = offs_j[v], offs_j[v + 1]
                take = min(hi - lo, w)
                idx[j, r, :take] = s_j[lo:lo + take]
                if hi - lo > w:  # overflow tail to the block's COO
                    ovf_parts[j][0].append(s_j[lo + w:hi])
                    ovf_parts[j][1].append(d_j[lo + w:hi])
        buckets.append(ELLColsBucket(
            row_ids=jnp.asarray(row_ids),
            src_idx=jnp.asarray(idx),
            k=int(w),
            rows=rows_pad,
        ))

    ovf_lens = [sum(a.size for a in parts[0]) for parts in ovf_parts]
    ovf_pad = ((max(ovf_lens) + 7) // 8) * 8 if max(ovf_lens, default=0) else 0
    ovf_src = np.full((C, ovf_pad), nc, dtype=np.int32)
    ovf_dst = np.full((C, ovf_pad), n_pad, dtype=np.int32)
    for j in range(C):
        if not ovf_lens[j]:
            continue
        s = np.concatenate(ovf_parts[j][0]).astype(np.int32)
        d = np.concatenate(ovf_parts[j][1]).astype(np.int32)
        order = np.argsort(d, kind="stable")   # sentinel pad (n_pad) stays last
        ovf_src[j, : s.size] = s[order]
        ovf_dst[j, : d.size] = d[order]
    return ELLCols(
        buckets=tuple(buckets),
        ovf_src=jnp.asarray(ovf_src),
        ovf_dst=jnp.asarray(ovf_dst),
        n=g.n,
        n_pad=n_pad,
        nc=nc,
        C=C,
        m=g.m,
    )


def spmv_ell_cols_ref(ellc: ELLCols, W: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle for the column-partitioned batched push.

    ``W`` is the pre-scaled per-source value batch, shape [B, n]; returns
    [B, n] — the sum over blocks of each block's local push, i.e. exactly
    what the mesh schedule computes with ``psum_scatter`` replaced by an
    in-process sum.  Agreement with the dense push is to float re-grouping
    (the cross-block sum), matching the distributed tolerance contract.
    """
    B = W.shape[0]
    W_pad = jnp.concatenate(
        [W, jnp.zeros((B, ellc.n_pad - ellc.n), W.dtype)], axis=1)
    y = jnp.zeros((B, ellc.n_pad + 1), W.dtype)
    for j in range(ellc.C):
        Wj = W_pad[:, j * ellc.nc:(j + 1) * ellc.nc]
        Wp = jnp.concatenate([Wj, jnp.zeros((B, 1), W.dtype)], axis=1)
        for b in ellc.buckets:
            rows_sum = jnp.sum(Wp[:, b.src_idx[j]], axis=2)   # [B, rows_b]
            y = y.at[:, b.row_ids[j]].add(rows_sum)
        if ellc.ovf_src.shape[-1]:
            y = y + jax.ops.segment_sum(
                Wp[:, ellc.ovf_src[j]].T, ellc.ovf_dst[j],
                num_segments=ellc.n_pad + 1, indices_are_sorted=True).T
    return y[:, : ellc.n]


def spmv_ell_ref(ell: ELLGraph, w: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle:  y[dst] = sum over in-edges of w[src].

    ``w`` is the *pre-scaled* per-source value (e.g. c*h*inv_deg for ITA,
    or any per-source scalar); shape [n].  Returns shape [n].
    """
    wp = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])  # sentinel slot
    y = jnp.zeros((ell.n + 1,), w.dtype)
    for b in ell.buckets:
        rows_sum = jnp.sum(wp[b.src_idx], axis=1)  # [rows_b]
        y = y.at[b.row_ids].add(rows_sum)
    if ell.ovf_src.shape[0]:
        y = y.at[:ell.n].add(
            jax.ops.segment_sum(w[ell.ovf_src], ell.ovf_dst, num_segments=ell.n,
                                indices_are_sorted=True))
    return y[: ell.n]
