"""Pallas TPU kernels, each with ops.py + ref.py.

  spmv_ell  — the paper's push (bucketed-ELL SpMV) + fused ITA step

CPU container note: kernels validate under interpret=True; the ops.py
wrappers dispatch to the jnp oracle on non-TPU backends so every higher
layer still compiles (DESIGN.md §2).
"""
from .spmv_ell import ita_step_ell, spmv_ell

__all__ = ["ita_step_ell", "spmv_ell"]
