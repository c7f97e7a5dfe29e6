"""Per-kernel allclose vs. ref.py oracles — shape/dtype sweeps + hypothesis.

All Pallas kernels run under interpret=True (CPU container; TPU is the
compile target — see DESIGN.md).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings
from _propcheck import strategies as st

from repro.core import ita_step
from repro.graph import web_graph
from repro.kernels.spmv_ell import (
    ita_step_ell,
    spmv_ell,
    spmv_ell_bucket,
    spmv_ell_bucket_ref,
)
from repro.sparse import ell_from_graph, spmv_ell_ref


# ---------------------------------------------------------------------------
# spmv_ell
# ---------------------------------------------------------------------------
class TestSpmvEll:
    @pytest.mark.parametrize("rows,k", [(8, 8), (32, 8), (256, 32), (100, 128), (7, 16)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
    def test_bucket_kernel_matches_ref(self, rows, k, dtype):
        rng = np.random.default_rng(rows * k)
        n = 500
        w = jnp.asarray(rng.standard_normal(n + 1), dtype)
        w = w.at[n].set(0.0)  # sentinel slot
        idx = jnp.asarray(rng.integers(0, n + 1, size=(rows, k)), jnp.int32)
        out = spmv_ell_bucket(w, idx, block_rows=64, interpret=True)
        ref = spmv_ell_bucket_ref(w, idx)
        tol = 1e-5 if dtype == jnp.float32 else 1e-12
        np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)

    @pytest.mark.parametrize("widths", [(8, 32, 128), (4, 16, 64), (16,)])
    def test_full_graph_matches_coo(self, widths):
        g = web_graph(800, 6500, dangling_frac=0.2, seed=3)
        ell = ell_from_graph(g, widths=widths)
        w = jnp.asarray(np.random.default_rng(0).random(g.n))
        y_coo = jax.ops.segment_sum(w[g.src], g.dst, num_segments=g.n)
        np.testing.assert_allclose(spmv_ell_ref(ell, w), y_coo, atol=1e-12)
        np.testing.assert_allclose(spmv_ell(ell, w, interpret=True), y_coo, atol=1e-12)

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(20, 300), mult=st.integers(1, 8), seed=st.integers(0, 9999))
    def test_property_random_graphs(self, n, mult, seed):
        g = web_graph(n, n * mult, dangling_frac=0.15, seed=seed)
        ell = ell_from_graph(g)
        w = jnp.asarray(np.random.default_rng(seed).random(n))
        y_coo = jax.ops.segment_sum(w[g.src], g.dst, num_segments=g.n)
        np.testing.assert_allclose(spmv_ell(ell, w, interpret=True), y_coo, atol=1e-11)

    def test_ita_step_ell_matches_core(self):
        g = web_graph(600, 5000, dangling_frac=0.2, seed=4)
        ell = ell_from_graph(g)
        h = jnp.ones((g.n,), jnp.float64)
        pi_bar = jnp.zeros_like(h)
        inv_deg = g.inv_out_deg(jnp.float64)
        nd = jnp.logical_not(g.dangling_mask)
        for _ in range(5):
            h1, pb1, na1, _ = ita_step(g, h, pi_bar, 0.85, 1e-8, inv_deg, nd)
            h2, pb2, na2 = ita_step_ell(ell, h, pi_bar, 0.85, 1e-8, inv_deg, nd,
                                        interpret=True)
            np.testing.assert_allclose(h2, h1, atol=1e-13)
            np.testing.assert_allclose(pb2, pb1, atol=1e-13)
            assert int(na1) == int(na2)
            h, pi_bar = h1, pb1

    def test_fill_ratio_bounded_on_powerlaw(self):
        g = web_graph(5000, 40000, dangling_frac=0.15, seed=5)
        ell = ell_from_graph(g, widths=(4, 8, 32, 128))
        stats = ell.fill_stats()
        assert stats["fill_ratio"] < 2.5, stats
        assert stats["overflow_edges"] < 0.25 * g.m, stats


# ---------------------------------------------------------------------------
# end-to-end: ITA over the ELL/Pallas path equals the reference solver
# ---------------------------------------------------------------------------
def test_ita_ell_end_to_end():
    from repro.core import power_method

    g = web_graph(700, 5600, dangling_frac=0.2, seed=6)
    ell = ell_from_graph(g)
    pi_ref = power_method(g, tol=1e-14, max_iter=500).pi

    h = jnp.ones((g.n,), jnp.float64)
    pi_bar = jnp.zeros_like(h)
    inv_deg = g.inv_out_deg(jnp.float64)
    nd = jnp.logical_not(g.dangling_mask)
    for _ in range(400):
        h, pi_bar, n_active = ita_step_ell(ell, h, pi_bar, 0.85, 1e-14, inv_deg, nd,
                                           interpret=True)
        if int(n_active) == 0:
            break
    pi_bar = pi_bar + h
    pi = pi_bar / jnp.sum(pi_bar)
    np.testing.assert_allclose(pi, pi_ref, atol=1e-11)
