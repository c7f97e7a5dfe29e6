"""Pallas TPU kernel: ELL-blocked sparse matrix–vector product.

The paper's hot op — one ITA push round — is `y[dst] += w[src]` over all
in-edges of every destination vertex.  In the bucketed-ELL layout
(``repro.sparse.ell``) this becomes, per bucket, a dense

    y_block[r] = sum_k  w[ idx_block[r, k] ]

TPU mapping (DESIGN.md §2, kernel-level adaptation):
  * the operand vector ``w`` (n+1 floats; sentinel zero slot last) is held
    RESIDENT IN VMEM for the whole grid — vertex state is the small, reused
    operand (n ≤ ~2.4M ⇒ ≤ ~10 MB fp32), edge blocks are the streamed one;
  * the index matrix is blocked ``(block_rows, k)`` so each grid step pulls
    one edge tile HBM→VMEM, gathers from VMEM, and row-reduces — a
    contention-free replacement for the paper's atomic adds;
  * block_rows is a multiple of 8 and k a multiple of... k ∈ {8,32,128}
    from the bucketing; the gather is lane-parallel and the reduction is a
    log-depth in-register tree over k.

Grid: 1-D over row blocks.  No cross-block accumulation — each dst row
lives in exactly one bucket row, so blocks are independent (embarrassingly
parallel, matching the paper's "completely parallel" property).

Status on the chip: Mosaic refuses both kernels (``TPU_REFUSAL``;
tests/test_tpu_compile.py holds that refusal as a strict xfail).  They run
only in interpret mode, which every caller must ask for explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["spmv_ell_bucket", "spmv_ell_bucket_batch", "DEFAULT_BLOCK_ROWS",
           "TPU_REFUSAL"]

DEFAULT_BLOCK_ROWS = 256

TPU_REFUSAL = ("the bucketed-ELL Pallas kernel does not lower on TPU: Mosaic "
               "raises 'Only 2D gather is supported' on its whole-vector "
               "gather w[idx] (and has no 64-bit types); use step_impl="
               "'dense' or 'auto'")


def _spmv_ell_kernel(w_ref, idx_ref, out_ref):
    # w_ref:   [n+1]            (VMEM-resident, whole vector)
    # idx_ref: [block_rows, k]  (one edge tile)
    # out_ref: [block_rows]
    idx = idx_ref[...]
    w = w_ref[...]
    gathered = w[idx]                       # lane-parallel VMEM gather
    out_ref[...] = jnp.sum(gathered, axis=1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def spmv_ell_bucket(
    w_padded: jnp.ndarray,   # [n+1] — sentinel zero slot at index n
    src_idx: jnp.ndarray,    # int32[rows, k], rows % block_rows == 0 not required
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
) -> jnp.ndarray:
    rows, k = src_idx.shape
    block_rows = min(block_rows, rows)
    # pad rows up to a block multiple with sentinel rows (gather 0, sum 0)
    pad = (-rows) % block_rows
    if pad:
        sentinel = jnp.full((pad, k), w_padded.shape[0] - 1, src_idx.dtype)
        src_idx = jnp.concatenate([src_idx, sentinel], axis=0)
        rows += pad
    grid = (rows // block_rows,)
    out = pl.pallas_call(
        _spmv_ell_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(w_padded.shape, lambda i: (0,)),            # whole w in VMEM
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),         # edge tile
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows,), w_padded.dtype),
        interpret=interpret,
    )(w_padded, src_idx)
    return out[: rows - pad] if pad else out


def _spmv_ell_batch_kernel(w_ref, idx_ref, out_ref):
    # w_ref:   [B, n+1]          (VMEM-resident operand matrix)
    # idx_ref: [block_rows, k]   (one edge tile, shared across the batch)
    # out_ref: [B, block_rows]
    idx = idx_ref[...]
    w = w_ref[...]
    gathered = w[:, idx]                    # [B, block_rows, k]
    out_ref[...] = jnp.sum(gathered, axis=-1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def spmv_ell_bucket_batch(
    w_padded: jnp.ndarray,   # [B, n+1] — sentinel zero column at index n
    src_idx: jnp.ndarray,    # int32[rows, k]
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
) -> jnp.ndarray:
    """Multi-source variant: one index-tile stream serves B operand rows.

    This is the batched-personalization hot path — the edge tiles (the
    large, streamed operand) are read from HBM ONCE per grid step and
    amortised over every personalization vector in the batch, so arithmetic
    intensity grows linearly in B where B·spmv_ell_bucket would re-stream
    the indices B times.
    """
    B = w_padded.shape[0]
    rows, k = src_idx.shape
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        sentinel = jnp.full((pad, k), w_padded.shape[1] - 1, src_idx.dtype)
        src_idx = jnp.concatenate([src_idx, sentinel], axis=0)
        rows += pad
    grid = (rows // block_rows,)
    out = pl.pallas_call(
        _spmv_ell_batch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(w_padded.shape, lambda i: (0, 0)),          # whole W in VMEM
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),         # edge tile
        ],
        out_specs=pl.BlockSpec((B, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, rows), w_padded.dtype),
        interpret=interpret,
    )(w_padded, src_idx)
    return out[:, : rows - pad] if pad else out
