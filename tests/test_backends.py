"""Backend-layer contract: every registered step_impl is exchangeable.

The paper's §IV commutativity result says any grouping/order of pushes
yields the same pi — so every backend (dense segment-sum, frontier
compression, Pallas bucketed-ELL) must agree with the Neumann-series
oracle and the power method to tight tolerance on graphs WITH the paper's
"special vertices" (dangling, unreferenced, self-loops).  The batched
solvers must match sequential solves row-for-row.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    available_step_impls,
    get_step_impl,
    ifp,
    ita,
    ita_batch,
    ita_fixed_point,
    ita_step,
    ita_traced,
    one_hot_personalizations,
    power_method,
    power_method_batch,
    solve_pagerank_batch,
)
from repro.core.backends import (STEP_IMPLS, StepBackend, choose_backend,
                                 register_step_impl)
from repro.graph import graph_from_edges, web_graph

from _propcheck import given, settings, strategies

ALL_IMPLS = available_step_impls()
JITTABLE_IMPLS = available_step_impls(jittable_only=True)


def _special_vertex_graph():
    """Small graph exercising every special case the paper names:
    dangling (3), unreferenced (0), self-loops (2, 4), plus a normal core."""
    src = np.array([0, 0, 1, 2, 2, 4, 5, 5, 1])
    dst = np.array([1, 2, 3, 2, 5, 4, 1, 4, 5])
    return graph_from_edges(src, dst, 6)


GRAPHS = {
    "special": _special_vertex_graph,
    "web": lambda: web_graph(400, 3200, dangling_frac=0.25, seed=17),
    "unref": lambda: web_graph(300, 2100, dangling_frac=0.1, unref_boost=0.4,
                               seed=18),
}


class TestRegistry:
    def test_expected_backends_registered(self):
        assert {"dense", "frontier", "frontier_priority", "ell"} <= set(
            STEP_IMPLS)

    def test_unknown_impl_raises(self):
        with pytest.raises(KeyError):
            get_step_impl("nope")
        g = web_graph(50, 300, seed=0)
        with pytest.raises(KeyError):
            ita(g, step_impl="nope")

    def test_jittable_subset(self):
        assert set(JITTABLE_IMPLS) <= set(ALL_IMPLS)
        assert not get_step_impl("frontier").jittable

    def test_register_and_use_custom_backend(self):
        @register_step_impl("_test_double_dense")
        class _DoubleDense(StepBackend):
            def push(self, g, ctx, w):
                return jax.ops.segment_sum(w[g.src], g.dst, num_segments=g.n)

        try:
            g = web_graph(100, 700, dangling_frac=0.1, seed=3)
            pi_ref = power_method(g, tol=1e-14, max_iter=500).pi
            pi = ita(g, xi=1e-14, step_impl="_test_double_dense").pi
            np.testing.assert_allclose(pi, pi_ref, atol=1e-11)
        finally:
            del STEP_IMPLS["_test_double_dense"]


class TestPushContract:
    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_push_equals_dense_segment_sum(self, impl):
        g = web_graph(300, 2400, dangling_frac=0.2, seed=9)
        backend = get_step_impl(impl)
        ctx = backend.prepare(g)
        w = jnp.asarray(np.random.default_rng(0).random(g.n))
        ref = jax.ops.segment_sum(w[g.src], g.dst, num_segments=g.n)
        np.testing.assert_allclose(backend.push(g, ctx, w), ref, atol=1e-12)

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_push_batch_equals_rowwise_push(self, impl):
        g = web_graph(200, 1500, dangling_frac=0.15, seed=10)
        backend = get_step_impl(impl)
        ctx = backend.prepare(g)
        W = jnp.asarray(np.random.default_rng(1).random((5, g.n)))
        Y = backend.push_batch(g, ctx, W)
        for i in range(5):
            np.testing.assert_allclose(Y[i], backend.push(g, ctx, W[i]),
                                       atol=1e-12)

    @pytest.mark.parametrize("edges", ["web", "no-edges", "one-vertex-all"])
    def test_dense_push_equals_numpy_segment_sum(self, edges):
        # the segmented scan of the dense push against np.bincount
        if edges == "web":
            g = web_graph(700, 5000, dangling_frac=0.2, seed=12)
        elif edges == "no-edges":
            g = graph_from_edges(np.zeros(0, int), np.zeros(0, int), 6)
        else:  # every edge into vertex 3, vertices 0..2 and 4.. unreferenced
            g = graph_from_edges(np.arange(9), np.full(9, 3), 9)
        backend = get_step_impl("dense")
        ctx = backend.prepare(g)
        W = np.random.default_rng(2).random((3, g.n))
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        ref = np.stack([np.bincount(dst, weights=w[src], minlength=g.n)
                        for w in W])
        np.testing.assert_allclose(backend.push_batch(g, ctx, jnp.asarray(W)),
                                   ref, rtol=1e-14, atol=1e-12)
        np.testing.assert_allclose(backend.push(g, ctx, jnp.asarray(W[0])),
                                   ref[0], rtol=1e-14, atol=1e-12)
        np.testing.assert_array_equal(backend.push(g, None, jnp.asarray(W[0])),
                                      backend.push(g, ctx, jnp.asarray(W[0])))

    def test_frontier_push_empty_frontier(self):
        g = web_graph(50, 300, dangling_frac=0.1, seed=11)
        backend = get_step_impl("frontier")
        ctx = backend.prepare(g)
        y = backend.push(g, ctx, jnp.zeros((g.n,), jnp.float64))
        assert float(jnp.max(jnp.abs(y))) == 0.0


class TestEquivalenceAcrossBackends:
    """Every backend == Neumann oracle == power method, atol 1e-11."""

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    def test_ita_matches_power_and_oracle(self, impl, gname):
        g = GRAPHS[gname]()
        pi_power = power_method(g, tol=1e-14, max_iter=500).pi
        pi_oracle = ita_fixed_point(g, n_terms=300)
        pi = ita(g, xi=1e-14, step_impl=impl).pi
        np.testing.assert_allclose(pi, pi_power, atol=1e-11)
        np.testing.assert_allclose(pi, pi_oracle, atol=1e-11)

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_power_method_across_backends(self, impl):
        g = GRAPHS["web"]()
        pi_ref = power_method(g, tol=1e-14, max_iter=500).pi
        pi = power_method(g, tol=1e-14, max_iter=500, step_impl=impl).pi
        np.testing.assert_allclose(pi, pi_ref, atol=1e-11)

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_ita_step_contract(self, impl):
        """One round of any backend == one round of core ita_step."""
        from repro.core.backends import ita_step_impl

        g = GRAPHS["web"]()
        backend = get_step_impl(impl)
        ctx = backend.prepare(g)
        h = jnp.ones((g.n,), jnp.float64)
        pi_bar = jnp.zeros_like(h)
        inv_deg = g.inv_out_deg(jnp.float64)
        nd = jnp.logical_not(g.dangling_mask)
        for _ in range(4):
            h1, pb1, na1, ops1 = ita_step(g, h, pi_bar, 0.85, 1e-8, inv_deg, nd)
            h2, pb2, na2, ops2 = ita_step_impl(backend, g, ctx, h, pi_bar,
                                               0.85, 1e-8, inv_deg, nd)
            np.testing.assert_allclose(h2, h1, atol=1e-13)
            np.testing.assert_allclose(pb2, pb1, atol=1e-13)
            assert int(na1) == int(na2) and float(ops1) == float(ops2)
            h, pi_bar = h1, pb1

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_personalized_across_backends(self, impl):
        g = GRAPHS["web"]()
        p = np.zeros(g.n)
        p[:5] = 0.2
        p = jnp.asarray(p)
        pi_ref = power_method(g, p=p, tol=1e-14, max_iter=500).pi
        pi = ita(g, p=p, xi=1e-15, step_impl=impl).pi
        np.testing.assert_allclose(pi, pi_ref, atol=1e-11)

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_traced_matches_fast_path(self, impl):
        g = GRAPHS["unref"]()
        r_fast = ita(g, xi=1e-12, step_impl=impl)
        r_traced = ita_traced(g, xi=1e-12, step_impl=impl)
        np.testing.assert_allclose(r_traced.pi, r_fast.pi, atol=1e-13)
        assert r_traced.active_history[-1] <= r_traced.active_history[0]


class TestIfpAcrossBackends:
    """IFP (arXiv 2302.03245) == Neumann oracle on every step backend."""

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    @pytest.mark.parametrize("variant", ["ifp1", "ifp2"])
    def test_ifp_matches_oracle(self, impl, variant):
        g = GRAPHS["web"]()
        pi_oracle = ita_fixed_point(g, n_terms=300)
        r = ifp(g, xi=1e-14, variant=variant, step_impl=impl)
        assert r.converged
        np.testing.assert_allclose(r.pi, pi_oracle, atol=1e-11)

    @pytest.mark.parametrize("variant", ["ifp1", "ifp2"])
    def test_ifp_special_vertices(self, variant):
        g = GRAPHS["special"]()
        pi_ref = power_method(g, tol=1e-14, max_iter=500).pi
        np.testing.assert_allclose(ifp(g, xi=1e-14, variant=variant).pi,
                                   pi_ref, atol=1e-11)

    def test_ifp_variants_take_identical_rounds(self):
        """IFP2's scaled tolerance makes both variants stop after exactly
        ceil(log xi / log c) full sweeps — same round count, same answer."""
        g = GRAPHS["web"]()
        r1 = ifp(g, xi=1e-12, variant="ifp1")
        r2 = ifp(g, xi=1e-12, variant="ifp2")
        assert r1.iterations == r2.iterations
        np.testing.assert_allclose(r2.pi, r1.pi, atol=1e-13)

    def test_ifp_personalized(self):
        g = GRAPHS["web"]()
        p = np.zeros(g.n)
        p[:5] = 0.2
        p = jnp.asarray(p)
        pi_ref = power_method(g, p=p, tol=1e-14, max_iter=500).pi
        np.testing.assert_allclose(ifp(g, p=p, xi=1e-15).pi, pi_ref,
                                   atol=1e-11)

    def test_ifp_mass_exact(self):
        """The exit folds are mass-exact: sum(pi) == 1 to machine eps."""
        g = GRAPHS["unref"]()
        for variant in ("ifp1", "ifp2"):
            pi = ifp(g, xi=1e-8, variant=variant).pi  # loose xi: fold matters
            assert abs(float(jnp.sum(pi)) - 1.0) < 1e-12

    def test_ifp_bad_variant(self):
        with pytest.raises(ValueError):
            ifp(GRAPHS["special"](), variant="ifp3")


class TestPrioritySchedule:
    """D-Iteration priority order is a pure reordering: the commutative
    segment-sum computes the same push (to summation-order rounding);
    the schedule's planner value rides in its declared cost."""

    def test_priority_push_matches_fifo(self):
        g = web_graph(300, 2400, dangling_frac=0.2, seed=50)
        fifo, prio = get_step_impl("frontier"), get_step_impl("frontier_priority")
        w = jnp.asarray(np.random.default_rng(2).random(g.n))
        y_fifo = fifo.push(g, fifo.prepare(g), w)
        y_prio = prio.push(g, prio.prepare(g), w)
        np.testing.assert_allclose(y_prio, y_fifo, atol=1e-12)

    def test_priority_emission_order_is_descending(self):
        """The reordering actually happens: the host emits the frontier
        largest-|w|-first (stable, so ties keep vertex order)."""
        g = web_graph(300, 2400, dangling_frac=0.2, seed=50)
        prio = get_step_impl("frontier_priority")
        w_host = np.asarray(np.random.default_rng(2).random(g.n))
        vs = np.nonzero(w_host)[0]
        vs_sorted = vs[np.argsort(-np.abs(w_host[vs]), kind="stable")]
        assert (np.diff(np.abs(w_host[vs_sorted])) <= 0).all()
        assert set(vs_sorted) == set(vs)

    def test_priority_cost_discount_needs_undirected(self):
        prio = get_step_impl("frontier_priority")
        fifo = get_step_impl("frontier")
        stats = dict(n=10_000, m=80_000)
        assert prio.cost(stats) == pytest.approx(fifo.cost(stats))
        assert prio.cost(dict(stats, undirected=True)) == pytest.approx(
            fifo.cost(stats) * prio.undirected_cost_factor)


class TestIsUndirected:
    def test_detects_symmetry(self):
        g = web_graph(200, 1500, dangling_frac=0.1, seed=60)
        assert not g.is_undirected  # random directed web
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        g_sym = graph_from_edges(np.concatenate([src, dst]),
                                 np.concatenate([dst, src]), g.n)
        assert g_sym.is_undirected

    def test_self_loops_and_empty(self):
        src = np.array([0, 1, 2])
        dst = np.array([1, 0, 2])  # mutual pair + self-loop
        assert graph_from_edges(src, dst, 3).is_undirected
        empty = graph_from_edges(np.array([], dtype=np.int64),
                                 np.array([], dtype=np.int64), 4)
        assert empty.is_undirected

    def test_cached_on_instance(self):
        g = web_graph(100, 700, seed=61)
        assert not hasattr(g, "_undirected_cache")
        val = g.is_undirected
        assert g._undirected_cache is val  # populated once, reused

    def test_apply_edge_delta_recomputes(self):
        from repro.graph import apply_edge_delta

        src = np.array([0, 1])
        dst = np.array([1, 0])
        g = graph_from_edges(src, dst, 3)
        assert g.is_undirected
        g2 = apply_edge_delta(g, add=[(1, 2)])
        # fresh Graph: no transplanted cache, property re-evaluates
        assert not hasattr(g2, "_undirected_cache")
        assert not g2.is_undirected
        assert g.is_undirected  # original untouched

    def test_engine_transplants_cache_across_device_put(self):
        from repro.core import EnginePlan, PageRankEngine

        g = web_graph(80, 500, dangling_frac=0.1, seed=62)
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        g_sym = graph_from_edges(np.concatenate([src, dst]),
                                 np.concatenate([dst, src]), g.n)
        assert g_sym.is_undirected  # warm the cache pre-prepare
        eng = PageRankEngine(g_sym, EnginePlan(mesh=(1, 1)))
        # device_put built a NEW Graph pytree; the engine must transplant
        # the host-side cache rather than silently dropping it
        assert eng.graph is not g_sym
        assert getattr(eng.graph, "_undirected_cache", None) is True
        assert eng.graph.is_undirected


class TestDynamicAcrossBackends:
    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_incremental_update(self, impl):
        from repro.core import ita_incremental, ita_residual_state

        g0 = web_graph(400, 3000, dangling_frac=0.15, seed=20)
        pi_bar, h, _, _ = ita_residual_state(g0, xi=1e-13, step_impl=impl)
        rng = np.random.default_rng(21)
        src = np.concatenate([np.asarray(g0.src), rng.integers(0, g0.n, 15)])
        dst = np.concatenate([np.asarray(g0.dst), rng.integers(0, g0.n, 15)])
        g1 = graph_from_edges(src, dst, g0.n)
        r = ita_incremental(g0, g1, pi_bar, h, xi=1e-13, step_impl=impl)
        pi_ref = power_method(g1, tol=1e-14, max_iter=500).pi
        np.testing.assert_allclose(r.pi, pi_ref, atol=1e-10)


class TestBatchedPPR:
    def test_batch_matches_sequential_ita(self):
        g = web_graph(400, 3200, dangling_frac=0.2, seed=30)
        seeds = np.arange(8) * 7 % g.n
        P = one_hot_personalizations(g, seeds)
        rb = solve_pagerank_batch(g, P, method="ita", xi=1e-13)
        assert rb.converged and rb.pi.shape == (8, g.n)
        for i in range(8):
            pi_seq = ita(g, p=P[i], xi=1e-13).pi
            np.testing.assert_allclose(rb.pi[i], pi_seq, atol=1e-12)

    def test_batch_matches_sequential_power(self):
        g = web_graph(300, 2400, dangling_frac=0.15, seed=31)
        seeds = np.arange(8)
        P = one_hot_personalizations(g, seeds)
        rb = solve_pagerank_batch(g, P, method="power", tol=1e-12)
        for i in range(8):
            pi_seq = power_method(g, p=P[i], tol=1e-12).pi
            np.testing.assert_allclose(rb.pi[i], pi_seq, atol=1e-12)

    @pytest.mark.parametrize("impl", JITTABLE_IMPLS)
    def test_batch_backends_agree(self, impl):
        g = web_graph(250, 1800, dangling_frac=0.2, seed=32)
        P = one_hot_personalizations(g, np.arange(6))
        ref = ita_batch(g, P, xi=1e-13, step_impl="dense").pi
        out = ita_batch(g, P, xi=1e-13, step_impl=impl).pi
        np.testing.assert_allclose(out, ref, atol=1e-12)

    def test_batch_frontier_host_loop(self):
        g = web_graph(150, 1000, dangling_frac=0.2, seed=33)
        P = one_hot_personalizations(g, np.arange(4))
        ref = ita_batch(g, P, xi=1e-12, step_impl="dense").pi
        out = ita_batch(g, P, xi=1e-12, step_impl="frontier").pi
        np.testing.assert_allclose(out, ref, atol=1e-12)

    def test_batch_rows_sum_to_one(self):
        g = web_graph(200, 1400, dangling_frac=0.3, seed=34)
        P = one_hot_personalizations(g, np.arange(5))
        rb = solve_pagerank_batch(g, P, method="ita", xi=1e-12)
        np.testing.assert_allclose(np.asarray(jnp.sum(rb.pi, axis=1)),
                                   np.ones(5), atol=1e-10)

    def test_batch_shape_validation(self):
        g = web_graph(100, 600, seed=35)
        with pytest.raises(ValueError):
            solve_pagerank_batch(g, jnp.ones((g.n,)))
        with pytest.raises(KeyError):
            solve_pagerank_batch(g, jnp.ones((2, g.n)) / g.n, method="nope")

    def test_power_batch_general_personalizations(self):
        """Non-one-hot rows (mixed user profiles) work identically."""
        g = web_graph(200, 1500, dangling_frac=0.1, seed=36)
        rng = np.random.default_rng(0)
        P = rng.random((8, g.n))
        P = jnp.asarray(P / P.sum(axis=1, keepdims=True))
        rb = power_method_batch(g, P, tol=1e-12)
        for i in range(8):
            pi_seq = power_method(g, p=P[i], tol=1e-12).pi
            np.testing.assert_allclose(rb.pi[i], pi_seq, atol=1e-12)


class TestEllCache:
    def test_graph_ell_is_cached(self):
        g = web_graph(200, 1500, dangling_frac=0.1, seed=40)
        assert g.ell() is g.ell()
        assert g.ell(widths=(4, 16)) is g.ell(widths=(16, 4))  # order-insensitive
        assert g.ell() is not g.ell(widths=(4, 16))

    def test_cache_used_by_backend(self):
        g = web_graph(150, 900, dangling_frac=0.1, seed=41)
        backend = get_step_impl("ell")
        assert backend.prepare(g) is g.ell()


class TestTpuRefusal:
    """Mosaic refuses the ELL kernel; a TPU must never run or pick it."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        import importlib

        for name in ("repro.core.backends", "repro.kernels.spmv_ell.ops"):
            mod = importlib.import_module(name)
            monkeypatch.setattr(mod.jax, "default_backend", lambda: "tpu")

    def test_auto_never_picks_ell_on_tpu(self):
        for mesh in (None, (4, 1), (2, 2)):
            stats = dict(n=875_713, m=5_105_039, platform="tpu", mesh=mesh)
            name, reason = choose_backend(stats)
            assert name == "dense" and "ell" not in reason, (mesh, reason)

    def test_explicit_ell_raises_naming_the_refusal(self, on_tpu):
        from repro.core import EnginePlan, PageRankEngine

        g = web_graph(60, 400, dangling_frac=0.1, seed=13)
        with pytest.raises(ValueError, match="Only 2D gather is supported"):
            PageRankEngine(g, EnginePlan(step_impl="ell"))

    def test_kernel_default_never_interprets_on_tpu(self, on_tpu):
        from repro.kernels.spmv_ell import spmv_ell

        g = web_graph(60, 400, dangling_frac=0.1, seed=13)
        with pytest.raises(NotImplementedError, match="does not lower on TPU"):
            spmv_ell(g.ell(), jnp.ones((g.n,)))


def _float32_pairs(kind, shape, rng):
    """Float64 values that are float32 pairs ``hi + lo``, |lo| ≤ ½ ulp(hi),
    as a TPU holds them: magnitudes ``10**kind`` (signed), or ±0 and ±inf
    among such values for ``"specials"``."""
    mag = 1.0 if kind == "specials" else 10.0 ** kind
    hi = (mag * rng.uniform(1, 2, shape)
          * rng.choice([-1, 1], shape)).astype(np.float32)
    half_ulp = np.spacing(np.abs(hi)) / 2
    frac = rng.uniform(0.25, 1, shape).astype(np.float32)
    frac[rng.random(shape) < 0.1] = 0           # hi alone
    frac[rng.random(shape) < 0.05] = 1          # a tie: lo = ½ ulp(hi)
    lo = (frac * half_ulp * rng.choice([-1, 1], shape)).astype(np.float32)
    v = hi.astype(np.float64) + lo.astype(np.float64)
    if kind == "specials":
        flat = v.reshape(-1)
        flat[:8] = [0.0, -0.0, np.inf, -np.inf, 0.0, -0.0, 1.0, -1.0]
    return v


class TestFloat64Gather:
    """The dense push's float64 gather (``backends._gather``): on the TPU
    both float32 words of a value come with one index, by a split into
    ``hi + lo`` and a recombine (``_split_gather``); elsewhere the plain
    gather."""

    @pytest.mark.parametrize("rows", [None, 3], ids=["vector", "rows"])
    @pytest.mark.parametrize("kind", [-30, -20, -10, 0, "specials"])
    def test_split_gather_returns_float32_pairs_bit_for_bit(self, kind, rows):
        from repro.core.backends import _split_gather

        rng = np.random.default_rng(31)
        n = 500
        v = _float32_pairs(kind, (n,) if rows is None else (rows, n), rng)
        idx = rng.integers(0, n, 2000).astype(np.int32)
        got = np.asarray(jax.jit(_split_gather)(jnp.asarray(v),
                                                jnp.asarray(idx)))
        assert got.shape == v[..., idx].shape
        np.testing.assert_array_equal(got.view(np.uint64),
                                      v[..., idx].view(np.uint64))

    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    @pytest.mark.parametrize("jit", [True, False], ids=["jit", "eager"])
    def test_cpu_gather_is_the_plain_gather(self, dtype, jit):
        from repro.core.backends import _gather, _split_gather

        rng = np.random.default_rng(32)
        v = rng.standard_normal((4, 300)).astype(dtype)
        idx = rng.integers(0, 300, 1000).astype(np.int32)
        gather = jax.jit(_gather) if jit else _gather
        got = np.asarray(gather(jnp.asarray(v), jnp.asarray(idx)))
        assert got.dtype == dtype
        words = np.dtype(f"u{v.itemsize}")
        np.testing.assert_array_equal(got.view(words),
                                      v[..., idx].view(words))
        if dtype == np.float64:
            # a true float64 is no float32 pair: the split would drop bits
            split = np.asarray(_split_gather(jnp.asarray(v), jnp.asarray(idx)))
            assert not np.array_equal(split, v[..., idx])

    @pytest.mark.parametrize("layout", ["plain", "live"])
    @pytest.mark.parametrize("rows", [None, 5], ids=["push", "push_batch"])
    @pytest.mark.parametrize("off_core", [False, True],
                             ids=["core-list", "full-list"])
    def test_cpu_push_equals_plain_gather_push(self, monkeypatch, layout,
                                               rows, off_core):
        """A CPU push gives, in every bit, what the push gives with the
        plain float64 gather put back in every place."""
        from repro.core import backends
        from repro.core.live import LiveLayout

        g = GRAPHS["unref"]()
        dense = get_step_impl("dense")
        ctx = dense.prepare(g) if layout == "plain" else LiveLayout(g).ctx
        assert ctx.core is not None
        rng = np.random.default_rng(33)
        shape = (g.n,) if rows is None else (rows, g.n)
        w = rng.standard_normal(shape) * (rng.random(shape) < 0.6)
        if not off_core:
            w = np.where(np.asarray(ctx.in_core), w, 0.0)
        counted = dense.push_counted if rows is None else \
            dense.push_batch_counted
        y, core = counted(g, ctx, jnp.asarray(w))
        assert bool(core) == (not off_core)
        monkeypatch.setattr(backends, "_gather",
                            lambda vals, idx: vals[..., idx])
        y_plain, _ = counted(g, ctx, jnp.asarray(w))
        np.testing.assert_array_equal(np.asarray(y).view(np.uint64),
                                      np.asarray(y_plain).view(np.uint64))


def _full_pass_run_sums(vals, start, last, carry=None):
    """The segmented scan as it stood before its pass schedule: a pass
    for every shift below the list's length, each over the whole list,
    then the live layout's carry and the readout.  The scheduled scan
    must give these bits."""
    x, f = vals, start
    lead = [(0, 0)] * (x.ndim - 1)
    k = 1
    while k < x.shape[-1]:
        x = jnp.where(f, x, x + jnp.pad(x[..., :-k], lead + [(k, 0)]))
        f = f | jnp.pad(f[:-k], (k, 0), constant_values=True)
        k *= 2
    if x.shape[-1] == 0:
        return jnp.zeros(vals.shape[:-1] + last.shape, vals.dtype)
    if carry is not None:
        e = x.shape[-1] - carry.shape[0]
        main = jnp.where(carry >= 0, x[..., jnp.maximum(carry, 0)], 0)
        x = x.at[..., e:].add(main)
    return jnp.where(last >= 0, x[..., jnp.maximum(last, 0)], 0)


def _by_destination_push(g, w):
    """The dense push of ``w`` over both edge lists laid out as before
    the runs were ordered by length: sorted by destination, each run's
    sources outside the referenced core first, every pass over the whole
    list.  Returns ``(full, core)``; ``core`` is None where the core
    holds every edge."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    from_core = (g.reference_levels < 0)[src]
    order = np.argsort(2 * dst.astype(np.int64) + from_core, kind="stable")

    def walk(keep):
        s, d = src[order][keep], dst[order][keep]
        start = np.ones(d.shape, bool)
        start[1:] = d[1:] != d[:-1]
        deg = np.bincount(d, minlength=g.n)
        last = np.where(deg > 0, np.cumsum(deg) - 1, -1)
        return _full_pass_run_sums(jnp.asarray(w)[..., s], jnp.asarray(start),
                                   jnp.asarray(last))

    full = walk(np.ones(g.m, bool))
    return full, None if from_core.all() else walk(from_core[order])


def _run_length_graph(lengths, n, seed):
    """A graph whose in-degrees are ``lengths`` (one run each, on random
    distinct destinations; the other vertices have none), with random
    distinct sources for each run."""
    rng = np.random.default_rng(seed)
    dst_v = rng.choice(n, len(lengths), replace=False)
    src = np.concatenate([rng.choice(n, r, replace=False) for r in lengths])
    dst = np.repeat(dst_v, lengths)
    return graph_from_edges(src, dst, n)


def _signed_decades(shape, rng):
    """Signed values over sixty decades, a third of them exact zeros."""
    v = rng.choice([-1.0, 1.0], shape) * 10.0 ** rng.uniform(-30, 30, shape)
    return np.where(rng.random(shape) < 1 / 3, 0.0, v)


SCAN_GRAPHS = {
    # runs of 1, 2**k and 2**k + 1 edges, several of each
    "powers": lambda: _run_length_graph(
        [r for k in range(8) for r in (2 ** k, 2 ** k + 1)] * 3 + [1] * 20,
        400, seed=41),
    "one-run": lambda: graph_from_edges(np.arange(300), np.full(300, 7), 300),
    "no-edges": lambda: graph_from_edges(np.zeros(0, int), np.zeros(0, int),
                                         12),
    "web": lambda: web_graph(600, 4800, dangling_frac=0.2, unref_boost=0.3,
                             seed=42),
}


def _bits(x):
    return np.asarray(x).view(np.uint64)


class TestScanSchedule:
    """The dense push's scan walks, in its pass of shift s, only the
    prefix of its edge list that holds the runs longer than s, the runs
    listed longest first (``backends.ScanPasses``).  Every sum must keep
    the bits the full-pass scan over the list sorted by destination gave."""

    def test_schedule_of_known_runs(self):
        from repro.core.backends import ScanPasses

        def starts(lengths):
            return np.concatenate([np.eye(1, r, dtype=bool)[0]
                                   for r in lengths])
        # runs 5, 1, 3, 2: shift 1 keeps every run longer than 1, up to
        # the end of the 2; shift 2 up to the 3; shift 4 the 5 alone
        assert ScanPasses.of(starts([5, 1, 3, 2])).prefix == (11, 9, 5)
        assert ScanPasses.of(starts([5, 3, 2, 1])).prefix == (10, 8, 5)
        assert ScanPasses.of(starts([1, 1, 1])).prefix == ()
        assert ScanPasses.of(np.zeros(0, bool)).prefix == ()
        assert ScanPasses.of(starts([8])).prefix == (8, 8, 8)
        assert ScanPasses.of(starts([9])).prefix == (9, 9, 9, 9)
        assert ScanPasses.full(9).prefix == (9, 9, 9, 9)
        assert ScanPasses.full(1).prefix == ScanPasses.full(0).prefix == ()

    def test_runs_are_listed_longest_first(self):
        """Each list holds its own runs longest first, by destination
        within a length, and ``last`` points at each vertex's run end."""
        g = SCAN_GRAPHS["web"]()
        ctx = get_step_impl("dense").prepare(g)
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        in_core = np.asarray(ctx.in_core)
        for runs, keep in ((ctx, np.ones(g.m, bool)), (ctx.core,
                                                       in_core[src])):
            deg = np.bincount(dst[keep], minlength=g.n)
            start, last = np.asarray(runs.start), np.asarray(runs.last)
            first = np.flatnonzero(start)
            ends = np.append(first[1:], start.size) - 1
            has = last >= 0
            assert np.array_equal(has, deg > 0)
            assert np.array_equal(np.sort(last[has]), ends)
            v = np.flatnonzero(has)[np.argsort(last[has])]  # in list order
            assert np.array_equal(ends - first + 1, deg[v])
            assert np.all((np.diff(deg[v]) < 0)
                          | ((np.diff(deg[v]) == 0) & (np.diff(v) > 0)))

    @pytest.mark.parametrize("rows", [None, 4], ids=["push", "push_batch"])
    @pytest.mark.parametrize("name", sorted(SCAN_GRAPHS))
    def test_push_keeps_the_full_pass_bits(self, name, rows):
        from repro.core.backends import _walk

        g = SCAN_GRAPHS[name]()
        ctx = get_step_impl("dense").prepare(g)
        rng = np.random.default_rng(43)
        w = _signed_decades((g.n,) if rows is None else (rows, g.n), rng)
        full, core = _by_destination_push(g, w)
        assert np.array_equal(_bits(_walk(jnp.asarray(w), ctx)), _bits(full))
        assert (core is None) == (ctx.core is None)
        if core is not None:
            w_core = jnp.asarray(np.where(np.asarray(ctx.in_core), w, 0.0))
            _, core = _by_destination_push(g, np.asarray(w_core))
            assert np.array_equal(_bits(_walk(w_core, ctx.core)),
                                  _bits(core))

    @settings(max_examples=8, deadline=None)
    @given(seed=strategies.integers(0, 2 ** 31 - 1),
           rows=strategies.integers(0, 3))
    def test_random_power_law_runs_keep_their_bits(self, seed, rows):
        from repro.core.backends import _walk

        rng = np.random.default_rng(seed)
        n = int(rng.integers(50, 400))
        lengths = np.minimum(rng.zipf(1.6, int(rng.integers(1, n))), n)
        g = _run_length_graph(lengths.tolist(), n, seed)
        ctx = get_step_impl("dense").prepare(g)
        w = _signed_decades((n,) if rows == 0 else (rows, n), rng)
        full, _ = _by_destination_push(g, w)
        assert np.array_equal(_bits(_walk(jnp.asarray(w), ctx)), _bits(full))

    @pytest.mark.parametrize("rows", [None, 3], ids=["push", "push_batch"])
    def test_live_layout_keeps_the_full_pass_bits_after_deltas(self, rows):
        """The live layout's main regions keep their schedule through
        deltas and its insert regions take every pass; together they give
        what every pass over the whole padded list gives."""
        from repro.core import DeltaQuery, EnginePlan, PageRankEngine
        from repro.core.backends import _walk

        g = SCAN_GRAPHS["web"]()
        eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
        rng = np.random.default_rng(44)
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        have = set(zip(src.tolist(), dst.tolist()))
        for _ in range(2):
            gone = rng.choice(len(src), 15, replace=False)
            remove = [(int(src[i]), int(dst[i])) for i in gone]
            add = []
            while len(add) < 12:
                e = tuple(int(v) for v in rng.integers(0, g.n, 2))
                if e not in have:
                    have.add(e)
                    add.append(e)
            eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
            have -= set(remove)
            keep = np.ones(len(src), bool)
            keep[gone] = False
            src, dst = src[keep], dst[keep]
        ctx = eng._ctx
        assert eng.relayouts == 0 and np.any(np.asarray(ctx.carry) >= 0)
        w = _signed_decades((g.n,) if rows is None else (rows, g.n), rng)
        padded = np.concatenate([w, np.zeros(w.shape[:-1] + (1,))], -1)
        for runs in (ctx, ctx.core):
            want = _full_pass_run_sums(jnp.asarray(padded)[..., runs.src],
                                       runs.start, runs.last, runs.carry)
            assert np.array_equal(_bits(_walk(jnp.asarray(w), runs)),
                                  _bits(want))


class TestScanEdgePasses:
    """``scan_edge_passes``: the scan's passes per edge slot of each edge
    list, beside ``core_edges`` on the engine and in ``describe()``."""

    @staticmethod
    def _graph():
        return web_graph(3000, 30000, dangling_frac=0.15, unref_boost=0.3,
                         seed=45)

    def test_reported_per_list_on_the_plain_layout(self):
        from repro.core import EnginePlan, PageRankEngine

        eng = PageRankEngine(self._graph(), EnginePlan(step_impl="dense"))
        passes = eng.describe(include_plan=False)["scan_edge_passes"]
        assert passes == eng.scan_edge_passes
        assert set(passes) == {"full", "core"}
        ctx = eng._ctx
        for name, runs in (("full", ctx), ("core", ctx.core)):
            e = runs.src.shape[0]
            assert passes[name] == sum(runs.passes.prefix) / e
            # a power-law graph's runs need far fewer passes than log2 e
            assert 1 < passes[name] < np.ceil(np.log2(e)) / 2

    def test_reported_per_list_on_the_live_layout(self):
        from repro.core import DeltaQuery, EnginePlan, PageRankEngine

        g = self._graph()
        eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
        have = set(zip(np.asarray(g.src).tolist(), np.asarray(g.dst).tolist()))
        add = next((0, v) for v in range(1, g.n) if (0, v) not in have)
        eng.run(DeltaQuery(add=(add,),
                           remove=((int(g.src[0]), int(g.dst[0])),)))
        passes = eng.describe(include_plan=False)["scan_edge_passes"]
        assert passes == eng.scan_edge_passes
        assert set(passes) == {"full", "core"}
        ctx = eng._ctx
        for name, runs in (("full", ctx), ("core", ctx.core)):
            slots, cap = runs.src.shape[0], runs.carry.shape[0]
            # the main region's schedule, and every pass over the slack
            assert passes[name] == (sum(runs.passes.prefix)
                                    + cap * (cap - 1).bit_length()) / slots
            assert 1 < passes[name] < (slots - 1).bit_length()

    def test_none_off_the_dense_push(self):
        from repro.core import EnginePlan, PageRankEngine

        eng = PageRankEngine(GRAPHS["web"](), EnginePlan(step_impl="ell"))
        assert eng.scan_edge_passes is None
        assert eng.describe(include_plan=False)["scan_edge_passes"] is None
