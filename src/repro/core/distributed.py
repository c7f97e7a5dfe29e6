"""Distributed ITA via shard_map — the paper's Algorithm 3 at pod scale.

The paper parallelises over K CPU threads with atomic adds; here the same
commutative push is laid out over a (data=R, model=C) device grid:

1-D (``ita_distributed_1d``): dst-block edge shards, h replicated.
    per step:  local masked segment-sum  →  all_gather(new h blocks).
    Collective bytes/step: n·dtype (the gather) — independent of m, which
    is the paper's O(1)-per-message bandwidth claim surviving distribution.

2-D (``ita_distributed_2d``): the production layout (graph/partition.py).
    h column-sharded (n/C per device, row-replicated); per step:
        local segment-sum over the (i,j) edge block     [compute]
        psum_scatter over "model"                       [n/R / C each]
        all_gather over "data"                          [n/C each]
    No all-to-all, no dangling-mass all-reduce (the power method needs one
    — deleted by construction, DESIGN.md §2), and per-device h memory is
    n/C instead of n.

Both return bit-identical results to ``core.ita`` (asserted in
tests/test_distributed.py on an 8-device host mesh) because the schedule
is the same synchronous frontier — only the data layout changes.

Batched PPR (``ita_batch_distributed``): the serving shape.  A [B, n]
    personalization batch is embarrassingly data-parallel in B, so the
    batch axis shards over ``data`` and — optionally — the vertex axis
    over ``model`` via the same :class:`Partition2D` edge blocks with
    R = 1 (``graph/partition.partition_cols``).  The per-step schedule is
    ``make_ita_2d_step``'s lifted to [B, n] state:

        local push over the column edge block          [compute]
        psum_scatter over "model"                      [B/R · n/C each]

    with the row all-gather of the single-vector layout replaced by batch
    parallelism (rows never exchange — the data axis carries no per-step
    collective at all).  With C == 1 the vertex axis stays whole and each
    device simply runs the registered backend's ``push_batch`` on its
    batch shard, so results are bit-identical to ``core.batch.ita_batch``
    per backend (asserted in tests/test_batch_distributed.py).

    The C > 1 local push has two realisations, dispatched on the resolved
    ``step_impl`` (both declare ``vertex_sharded_mesh``):

      * ``"dense"`` — masked segment-sum over the block's COO edges
        (``partition_cols`` arrays, ``_batch_2d_loop``);
      * ``"ell"``   — per-block bucketed-ELL tiles through the batched
        Pallas kernel (``Graph.ell_partitioned(C)`` →
        ``spmv_ell_cols_local_batch``, ``_batch_2d_ell_loop``), the same
        kernel the single-device fast path runs, now fed block-local
        operands.  Cross-column reduction is the identical psum_scatter,
        so the two schedules agree to solver tolerance and either agrees
        with the single-device batch to ~xi.

    See docs/SHARDING.md for the layout diagrams and byte counts.

``build_pagerank_job`` exposes the 2-D step as a LoweringJob so the
paper's own workload participates in the multi-pod dry-run + roofline.
"""
from __future__ import annotations

import time
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..graph.partition import partition_1d, partition_2d, partition_cols
from ..graph.structure import Graph
from .backends import (
    STEP_IMPLS,
    choose_backend,
    get_step_impl,
)
from .batch import BatchSolverResult, _batch_ita_step, normalize_rows
from .metrics import SolverResult

__all__ = ["ita_distributed_1d", "ita_distributed_2d", "build_pagerank_job",
           "make_ita_2d_step", "make_ita_batch_step",
           "make_ita_batch_ell_step", "ita_batch_distributed",
           "resolve_mesh"]


def _vertex_sharded_impls() -> list[str]:
    """Registered backends declaring the C-way column-sharded schedule."""
    return sorted(n for n, b in STEP_IMPLS.items()
                  if b.capabilities().vertex_sharded_mesh)


def resolve_mesh(spec, *, batch_axis: str = "data",
                 col_axis: str = "model") -> Optional[Mesh]:
    """Normalize a mesh request into a ``jax.sharding.Mesh`` (or ``None``).

    Accepted forms of ``spec``:
      * ``None``          — no mesh (single-device execution);
      * a ``Mesh``        — used as-is (must carry ``batch_axis``; a missing
                            ``col_axis`` is treated as size 1);
      * ``"host"``        — all of ``jax.devices()`` in an (n_dev, 1) grid,
                            the CI fallback that exercises sharding on
                            ``--xla_force_host_platform_device_count``
                            simulated devices;
      * ``R`` / ``(R,)``  — R-way batch-parallel grid (R, 1);
      * ``(R, C)``        — R-way batch × C-way vertex grid.

    Raises ``ValueError`` when the requested grid needs more devices than
    ``jax.devices()`` provides, or the shape is malformed.
    """
    if spec is None:
        return None
    if isinstance(spec, Mesh):
        if batch_axis not in spec.axis_names:
            raise ValueError(
                f"mesh must carry a {batch_axis!r} axis for the batch "
                f"dimension; got axes {spec.axis_names}")
        return spec
    if spec == "host":
        spec = (len(jax.devices()), 1)
    if isinstance(spec, int):
        spec = (spec,)
    try:
        shape = tuple(int(x) for x in spec)
    except (TypeError, ValueError):
        raise ValueError(f"mesh spec must be None, 'host', a Mesh, an int or "
                         f"a (R,) / (R, C) tuple; got {spec!r}") from None
    if len(shape) == 1:
        shape = (shape[0], 1)
    if len(shape) != 2 or min(shape) < 1:
        raise ValueError(f"mesh shape must be (R,) or (R, C) with positive "
                         f"entries; got {spec!r}")
    n_need, n_have = shape[0] * shape[1], len(jax.devices())
    if n_need > n_have:
        raise ValueError(f"mesh {shape} needs {n_need} devices but only "
                         f"{n_have} are available (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N for a "
                         f"simulated host mesh)")
    # Auto axes: the solvers' post-loop slices and concatenations rely on
    # sharding propagation, which Explicit axes (make_mesh's default) refuse.
    return jax.make_mesh(shape, (batch_axis, col_axis),
                         axis_types=(AxisType.Auto,) * 2)


def _psum_scatter_rows(x, axis: str):
    """``psum_scatter`` of ``x`` over ``axis`` along dim 0 (tiled).

    XLA on TPU has no 64-bit reduce-scatter.  There a 64-bit ``x`` goes as
    an all-to-all of its row blocks followed by a local sum: the same bytes
    on the wire, the sum in full precision.  Every other platform and dtype
    lowers the psum_scatter itself.
    """
    def scatter(v):
        return jax.lax.psum_scatter(v, axis, scatter_dimension=0, tiled=True)

    if jnp.dtype(x.dtype).itemsize < 8:
        return scatter(x)

    def exchange(v):
        C = jax.lax.axis_size(axis)
        blocks = v.reshape(C, v.shape[0] // C, *v.shape[1:])
        return jnp.sum(jax.lax.all_to_all(blocks, axis, 0, 0), axis=0)

    return jax.lax.platform_dependent(x, tpu=exchange, default=scatter)


# ---------------------------------------------------------------------------
# 1-D: dst-sharded edges, replicated h
# ---------------------------------------------------------------------------
def ita_distributed_1d(g: Graph, mesh: Mesh, *, c: float = 0.85,
                       xi: float = 1e-10, max_iter: int = 10_000,
                       dtype=jnp.float64, axis: str = "data") -> SolverResult:
    R = mesh.shape[axis]
    part = partition_1d(g, R)
    nr, n_pad = part.nr, part.n_pad

    # padded vertex-space arrays (natural order)
    inv_deg = np.zeros(n_pad, np.float64)
    deg = np.asarray(g.out_deg)
    inv_deg[: g.n] = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    non_dangling = np.zeros(n_pad, bool)
    non_dangling[: g.n] = deg > 0
    h0 = np.zeros(n_pad, np.float64)
    h0[: g.n] = 1.0

    specs_edges = P(axis, None)
    rep = P()

    @partial(shard_map, mesh=mesh,
             in_specs=(rep, rep, specs_edges, specs_edges, rep, rep),
             out_specs=(rep, rep, rep),
             check_vma=False)
    def step(h, pi_bar, src_blk, dst_blk, inv_deg_a, nd_a):
        src_blk, dst_blk = src_blk[0], dst_blk[0]
        active = jnp.logical_and(h > xi, nd_a)
        h_act = jnp.where(active, h, 0)
        pi_bar = pi_bar + h_act
        w = h_act * inv_deg_a * c
        wp = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        contrib = wp[src_blk]
        partial_r = jax.ops.segment_sum(contrib, dst_blk, num_segments=nr + 1)[:nr]
        h_new = jax.lax.all_gather(partial_r, axis, tiled=True)   # [n_pad]
        h = jnp.where(active, 0, h) + h_new
        n_active = jnp.sum(active, dtype=jnp.int32)  # replicated: identical on all
        return h, pi_bar, n_active

    h = jnp.asarray(h0.astype(dtype))
    pi_bar = jnp.zeros_like(h)
    src_d = jnp.asarray(part.src)
    dst_d = jnp.asarray(part.dst_local)
    ideg = jnp.asarray(inv_deg.astype(dtype))
    nd = jnp.asarray(non_dangling)
    it = 0
    while it < max_iter:
        h, pi_bar, n_active = step(h, pi_bar, src_d, dst_d, ideg, nd)
        it += 1
        if int(n_active) == 0:
            break
    pi_bar = pi_bar + h
    pi = (pi_bar / jnp.sum(pi_bar))[: g.n]
    return SolverResult(pi=pi, iterations=it, residual=float(xi), ops=float("nan"),
                        converged=True, method="ita_1d")


# ---------------------------------------------------------------------------
# 2-D: column-sharded h, (row, col) edge blocks
# ---------------------------------------------------------------------------
def make_ita_2d_step(mesh: Mesh, part_shapes: dict, c: float, xi: float,
                     row_axis: str = "data", col_axis: str = "model"):
    """Build the shard_map step over static partition geometry.

    part_shapes: dict(nr=, nc=, sub=, n_pad=) — static ints.
    Takes (h_col [n_pad] P(col), pi_col P(col), src [R,C,e] P(row,col,None),
           dst [R,C,e] P(row,col,None), inv_deg_col P(col), nd_col P(col))
    """
    nr, nc = part_shapes["nr"], part_shapes["nc"]
    col_spec = P(col_axis)
    edge_spec = P(row_axis, col_axis, None)

    def step(h, pi_bar, src_blk, dst_blk, inv_deg, nd):
        # local shapes: h [nc], src_blk [1,1,e], inv_deg [nc]
        src_blk, dst_blk = src_blk[0, 0], dst_blk[0, 0]
        active = jnp.logical_and(h > xi, nd)
        h_act = jnp.where(active, h, 0)
        pi_bar = pi_bar + h_act
        w = h_act * inv_deg * c
        wp = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        contrib = wp[src_blk]
        partial_r = jax.ops.segment_sum(contrib, dst_blk, num_segments=nr + 1)[:nr]
        # reduce over columns; each column keeps its sub-chunk of the row block
        y_sub = _psum_scatter_rows(partial_r, col_axis)             # [sub]
        # assemble this column's next block from all row groups
        h_new = jax.lax.all_gather(y_sub, row_axis, axis=0, tiled=True)  # [nc]
        h = jnp.where(active, 0, h) + h_new
        # active count: column blocks are disjoint; row-replicated -> psum cols
        n_active = jax.lax.psum(jnp.sum(active, dtype=jnp.int32), col_axis)
        return h, pi_bar, n_active

    return shard_map(
        step, mesh=mesh,
        in_specs=(col_spec, col_spec, edge_spec, edge_spec, col_spec, col_spec),
        out_specs=(col_spec, col_spec, P()),
        check_vma=False,
    )


def ita_distributed_2d(g: Graph, mesh: Mesh, *, c: float = 0.85,
                       xi: float = 1e-10, max_iter: int = 10_000,
                       dtype=jnp.float64, row_axis: str = "data",
                       col_axis: str = "model") -> SolverResult:
    R, C = mesh.shape[row_axis], mesh.shape[col_axis]
    part = partition_2d(g, R, C)

    deg = np.asarray(g.out_deg)
    inv_nat = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    nd_nat = (deg > 0)
    h_col = part.to_col_layout(np.ones(g.n))
    ideg_col = part.to_col_layout(inv_nat)
    nd_col = part.to_col_layout(nd_nat, fill=False)

    step = make_ita_2d_step(mesh, dict(nr=part.nr, nc=part.nc, sub=part.sub,
                                       n_pad=part.n_pad), c, xi,
                            row_axis, col_axis)
    step = jax.jit(step)

    h = jnp.asarray(h_col.astype(dtype))
    pi_bar = jnp.zeros_like(h)
    src_d = jnp.asarray(part.src_local)
    dst_d = jnp.asarray(part.dst_local)
    ideg = jnp.asarray(ideg_col.astype(dtype))
    nd = jnp.asarray(nd_col)
    it = 0
    while it < max_iter:
        h, pi_bar, n_active = step(h, pi_bar, src_d, dst_d, ideg, nd)
        it += 1
        if int(n_active) == 0:
            break
    pi_bar = pi_bar + h
    pi_nat = np.asarray(pi_bar)[part.perm[: g.n]]
    pi = jnp.asarray(pi_nat / pi_nat.sum())
    return SolverResult(pi=pi, iterations=it, residual=float(xi), ops=float("nan"),
                        converged=True, method="ita_2d")


# ---------------------------------------------------------------------------
# batched PPR: batch on "data", vertex optionally on "model"
# ---------------------------------------------------------------------------
def _ita_batch_2d_body(nr: int, c: float, xi: float, batch_axis: str,
                       col_axis: str):
    """The per-device body of one vertex-sharded batched ITA round.

    Local shapes: H [B_loc, nc], src_blk/dst_blk [1, e], inv_deg [nc].
    Shared by :func:`make_ita_batch_step` (one shard_mapped round) and the
    fused while_loop in ``ita_batch_distributed``.
    """
    def step(H, PiBar, src_blk, dst_blk, inv_deg, nd):
        src_e, dst_e = src_blk[0], dst_blk[0]
        active = jnp.logical_and(H > xi, nd[None, :])
        H_act = jnp.where(active, H, 0)
        PiBar = PiBar + H_act
        W = H_act * inv_deg[None, :] * c
        Wp = jnp.concatenate([W, jnp.zeros((W.shape[0], 1), W.dtype)], axis=1)
        contrib = Wp[:, src_e]                                 # [B_loc, e]
        partial_r = jax.ops.segment_sum(contrib.T, dst_e,
                                        num_segments=nr + 1)[:nr]  # [nr, B_loc]
        # reduce over columns; each column keeps its vertex block
        Y = _psum_scatter_rows(partial_r, col_axis)            # [nc, B_loc]
        H = jnp.where(active, 0, H) + Y.T
        n_active = jax.lax.psum(jnp.sum(active, dtype=jnp.int32),
                                (batch_axis, col_axis))
        return H, PiBar, n_active

    return step


def make_ita_batch_step(mesh: Mesh, part_shapes: dict, c: float, xi: float,
                        batch_axis: str = "data", col_axis: str = "model"):
    """Build the shard_map step for [B, n] batched ITA, vertex-sharded.

    ``make_ita_2d_step``'s push schedule lifted to [B, n] state with the
    row axis repurposed as the batch axis: the local masked segment-sum
    and the ``psum_scatter`` over ``col_axis`` are unchanged, while the
    single-vector layout's all-gather over rows disappears entirely —
    batch rows are independent, so the batch axis moves zero bytes per
    step.

    part_shapes: dict(nr=) — static ints from ``partition_cols``
    (nr == n_pad: dst indices are global).  shard_map operands:
      H, PiBar      f64[B_pad, n_pad]  P(batch_axis, col_axis)
      src, dst      i32[C, e_pad]      P(col_axis, None) (src local to the
                                       column block, dst global)
      inv_deg, nd   [n_pad]            P(col_axis)
    Returns ``(H', PiBar', n_active)`` with n_active replicated.
    """
    state_spec = P(batch_axis, col_axis)
    edge_spec = P(col_axis, None)
    vec_spec = P(col_axis)
    return shard_map(
        _ita_batch_2d_body(part_shapes["nr"], c, xi, batch_axis, col_axis),
        mesh=mesh,
        in_specs=(state_spec, state_spec, edge_spec, edge_spec, vec_spec,
                  vec_spec),
        out_specs=(state_spec, state_spec, P()),
        check_vma=False,
    )


# --- column-sharded ELL: the bucketed-kernel realisation of the C>1 push ---
def _ell_spec_list(sig, col_axis: str) -> tuple:
    """PartitionSpecs for the flattened ELLCols leaves, leading axis = C."""
    _, _, _, bucket_sig, ovf_pad = sig
    specs = []
    for _rows, _k in bucket_sig:
        specs.append(P(col_axis, None))           # row_ids [C, rows]
        specs.append(P(col_axis, None, None))     # src_idx [C, rows, k]
    if ovf_pad:
        specs.append(P(col_axis, None))           # ovf_src [C, ovf_pad]
        specs.append(P(col_axis, None))           # ovf_dst [C, ovf_pad]
    return tuple(specs)


def _ell_leaf_list(ellc) -> tuple:
    """The ELLCols arrays in the order ``_ell_spec_list`` declares."""
    leaves = []
    for b in ellc.buckets:
        leaves += [b.row_ids, b.src_idx]
    if ellc.ovf_src.shape[-1]:
        leaves += [ellc.ovf_src, ellc.ovf_dst]
    return tuple(leaves)


def _ita_batch_2d_ell_body(sig, c: float, xi: float, batch_axis: str,
                           col_axis: str):
    """Per-device body of one vertex-sharded batched ITA round, ELL layout.

    Identical elementwise prologue and psum_scatter epilogue to
    :func:`_ita_batch_2d_body`; only the local push differs — the block's
    bucketed-ELL tiles through the batched Pallas kernel instead of a
    segment-sum over the block's COO edges.  ``sig`` is
    ``ELLCols.signature()``; the flattened leaves arrive with a local
    leading axis of 1 (their [C, ...] arrays sharded over ``col_axis``).
    """
    from ..kernels.spmv_ell import spmv_ell_cols_local_batch

    n_pad, _nc, _C, bucket_sig, ovf_pad = sig
    nb = len(bucket_sig)

    def step(H, PiBar, inv_deg, nd, *ell_ops):
        buckets = [(ell_ops[2 * i][0], ell_ops[2 * i + 1][0])
                   for i in range(nb)]
        if ovf_pad:
            ovf_src, ovf_dst = ell_ops[2 * nb][0], ell_ops[2 * nb + 1][0]
        else:
            ovf_src = ovf_dst = None
        active = jnp.logical_and(H > xi, nd[None, :])
        H_act = jnp.where(active, H, 0)
        PiBar = PiBar + H_act
        W = H_act * inv_deg[None, :] * c
        Wp = jnp.concatenate([W, jnp.zeros((W.shape[0], 1), W.dtype)], axis=1)
        partial_r = spmv_ell_cols_local_batch(
            Wp, buckets, ovf_src, ovf_dst, n_pad)          # [B_loc, n_pad]
        Y = _psum_scatter_rows(partial_r.T, col_axis)      # [nc, B_loc]
        H = jnp.where(active, 0, H) + Y.T
        n_active = jax.lax.psum(jnp.sum(active, dtype=jnp.int32),
                                (batch_axis, col_axis))
        return H, PiBar, n_active

    return step


def make_ita_batch_ell_step(mesh: Mesh, ellc, c: float, xi: float,
                            batch_axis: str = "data",
                            col_axis: str = "model"):
    """One shard_mapped vertex-sharded batched ITA round over the ELL
    blocks — the single-round form of ``_batch_2d_ell_loop``, exposed so
    tests can assert round-for-round parity with the dense schedule.

    Operands: ``(H, PiBar)`` [B_pad, n_pad] P(batch, col), the ELLCols
    leaves (P(col, None...)), then ``inv_deg`` / ``nd`` [n_pad] P(col) —
    call as ``step(H, PiBar, inv_deg, nd, *_ell_leaf_list(ellc))``.
    """
    sig = ellc.signature()
    state_spec = P(batch_axis, col_axis)
    vec_spec = P(col_axis)
    return shard_map(
        _ita_batch_2d_ell_body(sig, c, xi, batch_axis, col_axis),
        mesh=mesh,
        in_specs=(state_spec, state_spec, vec_spec, vec_spec,
                  *_ell_spec_list(sig, col_axis)),
        out_specs=(state_spec, state_spec, P()),
        check_vma=False,
    )


# The loop builders are lru_cached on their static identity (mesh objects
# hash by device grid + axis names, backend instances by identity) so a
# serving engine's repeated solve_batch calls reuse ONE traced program:
# rebuilding jit(shard_map(...)) per query would retrace every time.  The
# whole quiescence loop runs device-resident inside the shard_map — no
# per-iteration host round-trip — mirroring core/batch._ita_batch_loop.
@lru_cache(maxsize=None)
def _batch_dp_loop(mesh: Mesh, backend, c: float, xi: float, max_iter: int,
                   batch_axis: str):
    """Batch-only sharding: each device runs the *registered backend's*
    ``push_batch`` on its batch shard against replicated edge operands.

    Because every batch row's arithmetic is untouched (same ops, same edge
    order, rows never interact), results are bit-identical per backend to
    the single-device ``ita_batch`` — the property the engine's sharded
    serving path is tested for.
    """
    state_spec = P(batch_axis, None)
    rep = P()

    def local_loop(g, ctx, H0):
        # computed in the program, as core/batch._ita_batch_loop does
        inv_deg = g.inv_out_deg(H0.dtype)
        nd = jnp.logical_not(g.dangling_mask)

        def cond(state):
            _, _, n_active, it = state
            return jnp.logical_and(n_active > 0, it < max_iter)

        def body(state):
            H, PiBar, _, it = state
            H, PiBar, n_loc, *_ = _batch_ita_step(backend, g, ctx, H, PiBar,
                                                 c, xi, inv_deg, nd)
            return H, PiBar, jax.lax.psum(n_loc, batch_axis), it + 1

        init = (H0, jnp.zeros_like(H0), jnp.asarray(1, jnp.int32),
                jnp.asarray(0, jnp.int32))
        return jax.lax.while_loop(cond, body, init)

    return jax.jit(shard_map(
        local_loop, mesh=mesh,
        in_specs=(rep, rep, state_spec),
        out_specs=(state_spec, state_spec, rep, rep),
        check_vma=False,
    ))


@lru_cache(maxsize=None)
def _normalize_local(mesh: Mesh, batch_axis: str):
    """``normalize_rows`` on each device's rows of a P(batch_axis) batch."""
    spec = P(batch_axis, None)
    return jax.jit(shard_map(normalize_rows, mesh=mesh, in_specs=spec,
                             out_specs=spec))


@lru_cache(maxsize=None)
def _batch_2d_loop(mesh: Mesh, nr: int, c: float, xi: float, max_iter: int,
                   batch_axis: str, col_axis: str):
    """Fused quiescence loop around :func:`_ita_batch_2d_body`."""
    state_spec = P(batch_axis, col_axis)
    edge_spec = P(col_axis, None)
    vec_spec = P(col_axis)
    step = _ita_batch_2d_body(nr, c, xi, batch_axis, col_axis)

    def local_loop(H0, src_blk, dst_blk, inv_deg, nd):
        def cond(state):
            _, _, n_active, it = state
            return jnp.logical_and(n_active > 0, it < max_iter)

        def body(state):
            H, PiBar, _, it = state
            H, PiBar, n_active = step(H, PiBar, src_blk, dst_blk, inv_deg, nd)
            return H, PiBar, n_active, it + 1

        init = (H0, jnp.zeros_like(H0), jnp.asarray(1, jnp.int32),
                jnp.asarray(0, jnp.int32))
        return jax.lax.while_loop(cond, body, init)

    return jax.jit(shard_map(
        local_loop, mesh=mesh,
        in_specs=(state_spec, edge_spec, edge_spec, vec_spec, vec_spec),
        out_specs=(state_spec, state_spec, P(), P()),
        check_vma=False,
    ))


@lru_cache(maxsize=None)
def _batch_2d_ell_loop(mesh: Mesh, sig, c: float, xi: float, max_iter: int,
                       batch_axis: str, col_axis: str):
    """Fused quiescence loop around :func:`_ita_batch_2d_ell_body`.

    Cached on the static geometry (``ELLCols.signature()``) instead of the
    operand arrays, exactly like ``_batch_2d_loop`` caches on ``nr`` — a
    serving engine's repeated solve_batch calls reuse ONE traced program.
    """
    state_spec = P(batch_axis, col_axis)
    vec_spec = P(col_axis)
    step = _ita_batch_2d_ell_body(sig, c, xi, batch_axis, col_axis)

    def local_loop(H0, inv_deg, nd, *ell_ops):
        def cond(state):
            _, _, n_active, it = state
            return jnp.logical_and(n_active > 0, it < max_iter)

        def body(state):
            H, PiBar, _, it = state
            H, PiBar, n_active = step(H, PiBar, inv_deg, nd, *ell_ops)
            return H, PiBar, n_active, it + 1

        init = (H0, jnp.zeros_like(H0), jnp.asarray(1, jnp.int32),
                jnp.asarray(0, jnp.int32))
        return jax.lax.while_loop(cond, body, init)

    return jax.jit(shard_map(
        local_loop, mesh=mesh,
        in_specs=(state_spec, vec_spec, vec_spec,
                  *_ell_spec_list(sig, col_axis)),
        out_specs=(state_spec, state_spec, P(), P()),
        check_vma=False,
    ))


def _partition_cols_cached(g: Graph, C: int):
    """Per-graph cache for the column partition (same idiom as Graph.ell:
    host-side O(m) conversion paid once per (graph, C), invisible to the
    pytree)."""
    cache = getattr(g, "_part_cols_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(g, "_part_cols_cache", cache)
    if C not in cache:
        cache[C] = partition_cols(g, C)
    return cache[C]


def _batch_2d_operands_cached(g: Graph, mesh: Mesh, C: int, dtype,
                              col_axis: str):
    """Device-placed vertex-sharded operands, cached per (graph, grid).

    A serving engine calls ``ita_batch_distributed`` per query; the O(m)
    edge blocks and O(n) mask vectors must be uploaded and sharded ONCE,
    not per solve (the prepare-once contract).  Keyed on (mesh, C, dtype)
    in the same per-graph cache as the partition itself.
    """
    part = _partition_cols_cached(g, C)
    cache = g._part_cols_cache  # created by the call above
    key = (mesh, C, jnp.dtype(dtype).name, col_axis)
    if key not in cache:
        deg = np.asarray(g.out_deg)
        inv_nat = np.zeros(part.n_pad, np.float64)
        inv_nat[: g.n] = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
        nd_nat = np.zeros(part.n_pad, bool)
        nd_nat[: g.n] = deg > 0
        edge_sh = NamedSharding(mesh, P(col_axis, None))
        vec_sh = NamedSharding(mesh, P(col_axis))
        cache[key] = (
            jax.device_put(jnp.asarray(part.src_local[0]), edge_sh),
            jax.device_put(jnp.asarray(part.dst_local[0]), edge_sh),
            jax.device_put(jnp.asarray(inv_nat.astype(dtype)), vec_sh),
            jax.device_put(jnp.asarray(nd_nat), vec_sh),
        )
    return part, cache[key]


def _ell_cols_operands_cached(g: Graph, mesh: Mesh, C: int, dtype,
                              col_axis: str, widths: tuple, row_align: int):
    """Device-placed column-block ELL operands, cached per (graph, grid).

    Same prepare-once contract as ``_batch_2d_operands_cached``: the
    host-side bucketing comes from the ``Graph.ell_partitioned`` cache,
    and the sharded device placement (leaves over ``col_axis``, masks
    column-sharded) is paid once per (mesh, C, dtype) — not per solve.
    """
    ellc = g.ell_partitioned(C, widths=widths, row_align=row_align)
    cache = getattr(g, "_part_cols_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(g, "_part_cols_cache", cache)
    key = ("ell", mesh, C, jnp.dtype(dtype).name, col_axis,
           tuple(sorted(widths)), int(row_align))
    if key not in cache:
        deg = np.asarray(g.out_deg)
        inv_nat = np.zeros(ellc.n_pad, np.float64)
        inv_nat[: g.n] = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
        nd_nat = np.zeros(ellc.n_pad, bool)
        nd_nat[: g.n] = deg > 0
        vec_sh = NamedSharding(mesh, P(col_axis))
        leaves = tuple(
            jax.device_put(leaf, NamedSharding(
                mesh, P(col_axis, *([None] * (leaf.ndim - 1)))))
            for leaf in _ell_leaf_list(ellc))
        cache[key] = (
            leaves,
            jax.device_put(jnp.asarray(inv_nat.astype(dtype)), vec_sh),
            jax.device_put(jnp.asarray(nd_nat), vec_sh),
        )
    return ellc, cache[key]


def ita_batch_distributed(
    g: Graph,
    p_batch,
    mesh: Mesh,
    *,
    c: float = 0.85,
    xi: float = 1e-10,
    max_iter: int = 10_000,
    dtype=jnp.float64,
    step_impl: str = "dense",
    ctx=None,
    batch_axis: str = "data",
    col_axis: str = "model",
    ell_widths: tuple = (8, 32, 128),
    row_align: int = 8,
    return_state: bool = False,
) -> BatchSolverResult:
    """Mesh-sharded multi-source ITA: ``p_batch`` is [B, n], one row per query.

    Two layouts, chosen by the mesh geometry:

      * C == 1 (or no ``col_axis``): **batch-parallel**.  B shards over
        ``batch_axis``; edges, masks and the backend ctx are replicated and
        each device runs ``step_impl``'s own ``push_batch`` on its rows.
        Any *jittable* backend ("dense", "ell", or a registered custom
        layout) is accepted and the result is bit-identical to
        :func:`repro.core.batch.ita_batch` with the same backend.
      * C > 1: **batch × vertex**.  Additionally shards the [B, n] state
        and the edge blocks over ``col_axis`` (per-device state is
        B/R × n/C) with the psum_scatter schedule of ``make_ita_2d_step``.
        The cross-column reduction regroups the float sums, so agreement
        with the single-device solve is to solver tolerance (~xi), not
        bitwise.  The local push dispatches on the backend (which must
        declare ``vertex_sharded_mesh``): "dense" runs the segment-sum
        over ``partition_cols`` COO blocks, "ell" the per-block
        bucketed-ELL tiles through the batched Pallas kernel
        (``Graph.ell_partitioned(C)``; ``ell_widths`` / ``row_align``
        select the bucketing).  ``step_impl="auto"``/``None`` picks by
        declared cost among vertex-sharded backends (``choose_backend``),
        which prefers the ELL tiles on the sharded layout.

    B is padded up to a multiple of R with all-zero rows (quiet from step
    0 — they change neither the iteration count nor any real row).

    ``return_state=True`` returns ``(result, (PiBar, H))`` — the
    unnormalized per-row residual pairs (padding stripped), the same
    contract as :func:`repro.core.batch.ita_batch`; the result cache
    stores them for delta-driven revalidation.
    """
    R = mesh.shape[batch_axis]
    C = mesh.shape[col_axis] if col_axis in mesh.axis_names else 1
    p_batch = jnp.asarray(p_batch)
    if p_batch.ndim != 2 or p_batch.shape[1] != g.n:
        raise ValueError(f"p_batch must be [B, n={g.n}], got {p_batch.shape}")
    B = int(p_batch.shape[0])
    B_pad = max(((B + R - 1) // R) * R, R)
    H0 = (p_batch.astype(dtype) * g.n).astype(dtype)
    if B_pad != B:
        H0 = jnp.concatenate(
            [H0, jnp.zeros((B_pad - B, g.n), dtype)], axis=0)

    t0 = time.perf_counter()
    if C == 1:
        if step_impl in (None, "auto"):
            step_impl, _ = choose_backend(dict(n=g.n, m=g.m, mesh=(R, 1)),
                                          require=("batch_parallel_mesh",))
        backend = get_step_impl(step_impl)
        if not backend.capabilities().batch_parallel_mesh:
            raise ValueError(
                f"step_impl={step_impl!r} is host-driven and cannot run "
                f"under shard_map (declared batch_parallel_mesh=False); "
                f"use a jittable backend (e.g. 'dense')")
        if ctx is None:
            ctx = backend.prepare(g)
        run = _batch_dp_loop(mesh, backend, float(c), float(xi),
                             int(max_iter), batch_axis)
        H0 = jax.device_put(H0, NamedSharding(mesh, P(batch_axis, None)))
        H, PiBar, n_active, it = run(g, ctx, H0)
        method = f"ita_batch_dist[{step_impl}|{R}x1]"
    else:
        if step_impl in (None, "auto"):
            impl, _ = choose_backend(dict(n=g.n, m=g.m, mesh=(R, C)),
                                     require=("vertex_sharded_mesh",))
        else:
            impl = step_impl
            if not get_step_impl(impl).capabilities().vertex_sharded_mesh:
                raise ValueError(
                    f"vertex-sharded batched ITA (C={C}) needs a backend "
                    f"declaring vertex_sharded_mesh (registered: "
                    f"{_vertex_sharded_impls()}); got "
                    f"step_impl={step_impl!r}")
        if impl == "ell":
            ellc, (leaves, ideg, nd) = _ell_cols_operands_cached(
                g, mesh, C, dtype, col_axis, tuple(ell_widths),
                int(row_align))
            run = _batch_2d_ell_loop(mesh, ellc.signature(), float(c),
                                     float(xi), int(max_iter), batch_axis,
                                     col_axis)
            n_pad, operands = ellc.n_pad, (ideg, nd, *leaves)
        elif impl == "dense":
            part, (src_d, dst_d, ideg, nd) = _batch_2d_operands_cached(
                g, mesh, C, dtype, col_axis)
            run = _batch_2d_loop(mesh, part.nr, float(c), float(xi),
                                 int(max_iter), batch_axis, col_axis)
            n_pad, operands = part.n_pad, (src_d, dst_d, ideg, nd)
        else:
            # a custom backend may declare the capability without having a
            # column-sharded realisation registered here — fail loudly
            # rather than silently densifying.
            raise ValueError(
                f"backend {impl!r} declares vertex_sharded_mesh but no "
                f"column-sharded schedule is registered for it in "
                f"core/distributed.py (implemented: ['dense', 'ell'])")
        if n_pad != g.n:
            H0 = jnp.concatenate(
                [H0, jnp.zeros((B_pad, n_pad - g.n), dtype)], axis=1)
        H0 = jax.device_put(H0, NamedSharding(mesh, P(batch_axis, col_axis)))
        H, PiBar, n_active, it = run(H0, *operands)
        method = f"ita_batch_dist[{impl}|{R}x{C}]"

    it = int(it)
    if C == 1:
        # each device normalizes its own whole rows, as one device does
        Pi = _normalize_local(mesh, batch_axis)(PiBar + H)[:B]
    else:
        # a row spans the C column blocks: normalized after the slice
        Pi = normalize_rows((PiBar + H)[:B, : g.n])
    Pi = jax.block_until_ready(Pi)
    result = BatchSolverResult(
        pi=Pi, iterations=int(it), residual=float(xi),
        converged=bool(int(n_active) == 0), method=method, batch=B,
        wall_time_s=time.perf_counter() - t0)
    if return_state:
        return result, (PiBar[:B, : g.n], H[:B, : g.n])
    return result


# ---------------------------------------------------------------------------
# dry-run job (abstract shapes — no edges materialised)
# ---------------------------------------------------------------------------
def build_pagerank_job(spec, cell, mesh: Mesh):
    from ..launch.steps import LoweringJob  # local import to avoid cycle

    meta = cell.meta
    n, m = meta["n"], meta["m"]
    row_axis, col_axis = "data", "model"
    R, C = mesh.shape[row_axis], mesh.shape[col_axis]
    if "pod" in mesh.axis_names:
        # pod extends the row axis: 2 pods × 16 rows = 32 dst-block groups
        row_axis = ("pod", "data")
        R = mesh.shape["pod"] * mesh.shape["data"]

    n_pad = ((n + R * C - 1) // (R * C)) * (R * C)
    nr, nc, sub = n_pad // R, n_pad // C, n_pad // (R * C)
    e_pad = ((int(m / (R * C) * 1.3) + 8 + 7) // 8) * 8

    c, xi = 0.85, 1e-10
    dtype = jnp.float32

    col_spec = P(col_axis)
    edge_spec = P(row_axis, col_axis, None)

    def step(h, pi_bar, src_blk, dst_blk, inv_deg, nd):
        src_blk, dst_blk = src_blk[0, 0], dst_blk[0, 0]
        active = jnp.logical_and(h > xi, nd)
        h_act = jnp.where(active, h, 0)
        pi_bar = pi_bar + h_act
        w = h_act * inv_deg * c
        wp = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        contrib = wp[src_blk]
        partial_r = jax.ops.segment_sum(contrib, dst_blk, num_segments=nr + 1)[:nr]
        y_sub = jax.lax.psum_scatter(partial_r, col_axis, scatter_dimension=0,
                                     tiled=True)
        h_new = jax.lax.all_gather(y_sub, row_axis, axis=0, tiled=True)
        h = jnp.where(active, 0, h) + h_new
        n_active = jax.lax.psum(jnp.sum(active, dtype=jnp.int32), col_axis)
        return h, pi_bar, n_active

    sm = shard_map(step, mesh=mesh,
                   in_specs=(col_spec, col_spec, edge_spec, edge_spec,
                             col_spec, col_spec),
                   out_specs=(col_spec, col_spec, P()),
                   check_vma=False)

    args = (
        jax.ShapeDtypeStruct((n_pad,), dtype),
        jax.ShapeDtypeStruct((n_pad,), dtype),
        jax.ShapeDtypeStruct((R, C, e_pad), jnp.int32),
        jax.ShapeDtypeStruct((R, C, e_pad), jnp.int32),
        jax.ShapeDtypeStruct((n_pad,), dtype),
        jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
    )
    ns = lambda spec_: NamedSharding(mesh, spec_)
    in_sh = (ns(col_spec), ns(col_spec), ns(edge_spec), ns(edge_spec),
             ns(col_spec), ns(col_spec))
    return LoweringJob(
        name=f"pagerank:{cell.name}",
        step_fn=sm,
        args=args,
        in_shardings=in_sh,
        rules=None,
        donate_argnums=(0, 1),
        static_meta=dict(n=n, m=m, n_pad=n_pad, e_pad=e_pad, R=R, C=C),
    )


# ---------------------------------------------------------------------------
# beyond-paper: compressed-exchange 2-D ITA (bf16 wire + error feedback)
# ---------------------------------------------------------------------------
def make_ita_2d_step_compressed(mesh: Mesh, part_shapes: dict, c: float,
                                xi: float, row_axis: str = "data",
                                col_axis: str = "model"):
    """2-D ITA step with HALF the wire bytes: the pushed partials cross the
    ICI in bfloat16, while per-device state stays in full precision with a
    local error-feedback accumulator (the same Seide/EF trick as the
    gradient compressor in train/optimizer.py).

    The paper's central systems claim is ITA's O(1)-scalar bandwidth; this
    variant halves that constant.  Quantisation noise does not bias the
    fixed point: the un-sent residual err = partial - bf16(partial) is
    kept locally and added to the NEXT iteration's partial before
    quantisation, so all information is eventually transmitted (validated
    to the same tolerance as the exact solver in tests).
    """
    nr, nc = part_shapes["nr"], part_shapes["nc"]
    col_spec = P(col_axis)
    edge_spec = P(row_axis, col_axis, None)

    def step(h, pi_bar, err, src_blk, dst_blk, inv_deg, nd):
        src_blk, dst_blk = src_blk[0, 0], dst_blk[0, 0]
        err = err[0, 0]                                  # local [nr]
        active = jnp.logical_and(h > xi, nd)
        h_act = jnp.where(active, h, 0)
        pi_bar = pi_bar + h_act
        w = h_act * inv_deg * c
        wp = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        contrib = wp[src_blk]
        partial_r = jax.ops.segment_sum(contrib, dst_blk, num_segments=nr + 1)[:nr]
        # --- compress the wire: bf16 payload, error kept locally ---------
        payload = partial_r + err
        payload_bf16 = payload.astype(jnp.bfloat16)
        err = payload - payload_bf16.astype(payload.dtype)
        y_sub = jax.lax.psum_scatter(payload_bf16, col_axis,
                                     scatter_dimension=0, tiled=True)
        h_new = jax.lax.all_gather(y_sub, row_axis, axis=0, tiled=True)
        h = jnp.where(active, 0, h) + h_new.astype(h.dtype)
        n_active = jax.lax.psum(jnp.sum(active, dtype=jnp.int32), col_axis)
        return h, pi_bar, err[None, None], n_active

    return shard_map(
        step, mesh=mesh,
        in_specs=(col_spec, col_spec, P(row_axis, col_axis), edge_spec,
                  edge_spec, col_spec, col_spec),
        out_specs=(col_spec, col_spec, P(row_axis, col_axis), P()),
        check_vma=False,
    )


def ita_distributed_2d_compressed(g: Graph, mesh: Mesh, *, c: float = 0.85,
                                  xi: float = 1e-10, max_iter: int = 10_000,
                                  dtype=jnp.float64, row_axis: str = "data",
                                  col_axis: str = "model") -> SolverResult:
    R, C = mesh.shape[row_axis], mesh.shape[col_axis]
    part = partition_2d(g, R, C)
    deg = np.asarray(g.out_deg)
    inv_nat = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    nd_nat = (deg > 0)
    step = jax.jit(make_ita_2d_step_compressed(
        mesh, dict(nr=part.nr, nc=part.nc, sub=part.sub, n_pad=part.n_pad),
        c, xi, row_axis, col_axis))

    h = jnp.asarray(part.to_col_layout(np.ones(g.n)).astype(dtype))
    pi_bar = jnp.zeros_like(h)
    # per-device error-feedback accumulator [nr], laid out (row, col)
    err = jnp.zeros((R, C, part.nr), dtype)
    src_d = jnp.asarray(part.src_local)
    dst_d = jnp.asarray(part.dst_local)
    ideg = jnp.asarray(part.to_col_layout(inv_nat).astype(dtype))
    nd = jnp.asarray(part.to_col_layout(nd_nat, fill=False))
    it = 0
    while it < max_iter:
        h, pi_bar, err, n_active = step(h, pi_bar, err, src_d, dst_d, ideg, nd)
        it += 1
        if int(n_active) == 0:
            break
    pi_bar = pi_bar + h
    pi_nat = np.asarray(pi_bar)[part.perm[: g.n]]
    pi = jnp.asarray(pi_nat / pi_nat.sum())
    return SolverResult(pi=pi, iterations=it, residual=float(xi), ops=float("nan"),
                        converged=True, method="ita_2d_c",
                        )
