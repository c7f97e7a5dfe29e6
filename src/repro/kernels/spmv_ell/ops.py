"""Jitted wrapper: full-graph ELL SpMV + the fused ITA step built on it.

``interpret=None`` follows the backend: Pallas kernels cannot be
*compiled* by the CPU backend, so CPU runs interpret the kernel body —
correct but slow.  On TPU, Mosaic refuses the kernel (``TPU_REFUSAL``),
so the default raises there instead of running anything.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...sparse.ell import ELLGraph
from .kernel import TPU_REFUSAL, spmv_ell_bucket, spmv_ell_bucket_batch

__all__ = ["DEFAULT_BLOCK_ROWS", "spmv_ell", "spmv_ell_batch",
           "spmv_ell_cols_local_batch", "ita_step_ell"]


# One tunable home for the kernel's row-tile size: tools/autotune_ell.py
# sweeps candidates against the roofline model and reports whether this
# default still wins for a given graph/platform.
DEFAULT_BLOCK_ROWS = 256


def _interpret_default() -> bool:
    platform = jax.default_backend()
    if platform == "tpu":
        raise NotImplementedError(TPU_REFUSAL)
    return platform == "cpu"


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def spmv_ell(ell: ELLGraph, w: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
             interpret: bool | None = None) -> jnp.ndarray:
    """y = (push of per-source scalar w) over all edges; shape [n] -> [n]."""
    if interpret is None:
        interpret = _interpret_default()
    wp = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
    y = jnp.zeros((ell.n + 1,), w.dtype)
    for b in ell.buckets:
        rows_sum = spmv_ell_bucket(wp, b.src_idx, block_rows=block_rows,
                                   interpret=interpret)
        y = y.at[b.row_ids].add(rows_sum)
    if ell.ovf_src.shape[0]:
        y = y.at[: ell.n].add(
            jax.ops.segment_sum(w[ell.ovf_src], ell.ovf_dst,
                                num_segments=ell.n, indices_are_sorted=True))
    return y[: ell.n]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def spmv_ell_batch(ell: ELLGraph, W: jnp.ndarray, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Batched push: [B, n] operand rows through one edge-tile stream.

    Serves ``solve_pagerank_batch`` — every bucket's index matrix is
    streamed once and gathered against all B personalization rows.
    """
    if interpret is None:
        interpret = _interpret_default()
    B = W.shape[0]
    Wp = jnp.concatenate([W, jnp.zeros((B, 1), W.dtype)], axis=1)
    y = jnp.zeros((B, ell.n + 1), W.dtype)
    for b in ell.buckets:
        rows_sum = spmv_ell_bucket_batch(Wp, b.src_idx, block_rows=block_rows,
                                         interpret=interpret)
        y = y.at[:, b.row_ids].add(rows_sum)
    if ell.ovf_src.shape[0]:
        ovf = jax.ops.segment_sum(Wp[:, ell.ovf_src].T, ell.ovf_dst,
                                  num_segments=ell.n,
                                  indices_are_sorted=True).T
        y = y.at[:, : ell.n].add(ovf)
    return y[:, : ell.n]


def spmv_ell_cols_local_batch(Wp, buckets, ovf_src, ovf_dst, n_pad: int, *,
                              block_rows: int = DEFAULT_BLOCK_ROWS,
                              interpret: bool | None = None) -> jnp.ndarray:
    """One device's column-block batched push (the vertex-sharded layout).

    ``Wp`` is the block-local operand batch [B, nc + 1] (sentinel zero
    column last); ``buckets`` an iterable of ``(row_ids [rows_b],
    src_idx [rows_b, k_b])`` pairs from one ``ELLCols`` block; ``ovf_src``
    / ``ovf_dst`` the block's overflow COO (``None`` when the layout has
    no overflow).  Returns the [B, n_pad] *partial* dst sums this block
    contributes — the caller (``core/distributed.py``) reduces partials
    across blocks with ``psum_scatter`` over the mesh "model" axis.

    Not jitted here: it is always called inside an already-traced
    ``shard_map``/``while_loop`` body, and the inner
    ``spmv_ell_bucket_batch`` pallas_call carries its own jit.
    """
    if interpret is None:
        interpret = _interpret_default()
    B = Wp.shape[0]
    y = jnp.zeros((B, n_pad + 1), Wp.dtype)
    for row_ids, src_idx in buckets:
        rows_sum = spmv_ell_bucket_batch(Wp, src_idx, block_rows=block_rows,
                                         interpret=interpret)
        y = y.at[:, row_ids].add(rows_sum)
    if ovf_src is not None and ovf_src.shape[0]:
        y = y + jax.ops.segment_sum(Wp[:, ovf_src].T, ovf_dst,
                                    num_segments=n_pad + 1,
                                    indices_are_sorted=True).T
    return y[:, :n_pad]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ita_step_ell(
    ell: ELLGraph,
    h: jnp.ndarray,
    pi_bar: jnp.ndarray,
    c: float,
    xi: float,
    inv_deg: jnp.ndarray,
    non_dangling: jnp.ndarray,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
):
    """One ITA round over the ELL layout — same contract as core.ita_step.

    The elementwise prologue (threshold, accumulate, scale) is XLA-fused;
    the edge propagation is the Pallas kernel.  Tests assert bit-level
    agreement in fp64 with core.ita_step on random graphs.
    """
    active = jnp.logical_and(h > xi, non_dangling)
    h_act = jnp.where(active, h, 0)
    pi_bar = pi_bar + h_act
    w = h_act * inv_deg * c
    pushed = spmv_ell(ell, w, block_rows=block_rows, interpret=interpret)
    h = jnp.where(active, 0, h) + pushed
    n_active = jnp.sum(active, dtype=jnp.int32)
    return h, pi_bar, n_active
