"""Sparse substrate: the bucketed-ELL layout."""
from .ell import (
    ELLBucket,
    ELLCols,
    ELLColsBucket,
    ELLGraph,
    ell_cols_from_graph,
    ell_from_graph,
    spmv_ell_cols_ref,
    spmv_ell_ref,
)

__all__ = [
    "ELLBucket", "ELLCols", "ELLColsBucket", "ELLGraph",
    "ell_cols_from_graph", "ell_from_graph", "spmv_ell_cols_ref", "spmv_ell_ref",
]
