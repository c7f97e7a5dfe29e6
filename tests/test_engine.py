"""PageRankEngine session API: parity with the legacy entry points,
prepare-once reuse, typed-config validation, serving and dynamic updates.

The engine must be a pure re-plumbing of the existing solvers: identical
bits out (it threads its prepared ctx into the very same jitted loops), no
re-preparation on repeated queries, and hard errors instead of silent
re-bucketing when a config contradicts the prepared layout.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BatchConfig,
    EnginePlan,
    ForwardPushConfig,
    ItaConfig,
    MonteCarloConfig,
    PageRankEngine,
    PowerConfig,
    available_step_impls,
    err_max_rel,
    ita,
    make_config,
    power_method,
    reference_pagerank,
    solve_pagerank_batch,
)
from repro.core.backends import STEP_IMPLS
from repro.graph import apply_edge_delta, graph_from_edges, web_graph

ALL_IMPLS = available_step_impls()


@pytest.fixture(scope="module")
def g():
    return web_graph(400, 3200, dangling_frac=0.25, seed=17)


# --------------------------------------------------------------------------
# parity: engine == legacy, bit for bit, every backend
# --------------------------------------------------------------------------
class TestParity:
    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_ita_matches_legacy(self, g, impl):
        eng = PageRankEngine(g, EnginePlan(step_impl=impl))
        r_eng = eng.solve(ItaConfig(xi=1e-12))
        r_leg = ita(g, xi=1e-12, step_impl=impl)
        assert np.array_equal(np.asarray(r_eng.pi), np.asarray(r_leg.pi))
        assert r_eng.iterations == r_leg.iterations
        assert r_eng.ops == r_leg.ops

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_power_matches_legacy(self, g, impl):
        eng = PageRankEngine(g, EnginePlan(step_impl=impl))
        r_eng = eng.solve(PowerConfig(tol=1e-12))
        r_leg = power_method(g, tol=1e-12, step_impl=impl)
        assert np.array_equal(np.asarray(r_eng.pi), np.asarray(r_leg.pi))

    @pytest.mark.parametrize("impl", ALL_IMPLS)
    def test_solve_batch_matches_legacy(self, g, impl):
        from repro.core import one_hot_personalizations

        eng = PageRankEngine(g, EnginePlan(step_impl=impl))
        P = one_hot_personalizations(g, [1, 5, 9])
        rb_eng = eng.solve_batch(P, BatchConfig(xi=1e-12))
        rb_leg = solve_pagerank_batch(g, P, method="ita", xi=1e-12,
                                      step_impl=impl)
        assert np.array_equal(np.asarray(rb_eng.pi), np.asarray(rb_leg.pi))

    @pytest.mark.parametrize("impl", available_step_impls(jittable_only=True))
    def test_donated_batch_path_matches_ita_batch(self, g, impl):
        # the path every accelerator engine takes, forced on the CPU
        from repro.core import PPRQuery, ita_batch, one_hot_personalizations

        P = one_hot_personalizations(g, [1, 5, 9, 5])
        cfg = BatchConfig(xi=1e-12)
        plain = PageRankEngine(g, EnginePlan(step_impl=impl))
        donated = PageRankEngine(g, EnginePlan(step_impl=impl))
        donated._donate = True
        assert donated.plan(PPRQuery(p_batch=P, cfg=cfg)).path == "donated-batch"
        assert plain.plan(PPRQuery(p_batch=P, cfg=cfg)).path != "donated-batch"
        ref = ita_batch(g, P, xi=1e-12, step_impl=impl)
        undonated = plain.solve_batch(P, cfg)
        for _ in range(2):  # the second call reuses the compiled loop
            got = donated.solve_batch(P, cfg)
            assert np.array_equal(np.asarray(got.pi), np.asarray(ref.pi))
            assert np.array_equal(np.asarray(got.pi),
                                  np.asarray(undonated.pi))
            assert got.iterations == ref.iterations and got.converged

    def test_donated_rows_match_one_device_share_of_a_batch_grid(self, g):
        # a (4, 1) grid runs each chip's 4 rows through the program of a
        # (1, 1) mesh; those rows must equal the donated 16-row solve's
        from repro.core import one_hot_personalizations
        from repro.core.distributed import ita_batch_distributed, resolve_mesh

        P = one_hot_personalizations(g, [3, 1, 4, 1, 5, 9, 2, 6,
                                         5, 3, 5, 8, 9, 7, 9, 3])
        cfg = BatchConfig(xi=1e-12)
        eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
        eng._donate = True
        whole = eng.solve_batch(P, cfg)
        share = ita_batch_distributed(g, P[:4], resolve_mesh((1, 1)),
                                      xi=1e-12, ctx=eng._ctx)
        assert np.array_equal(np.asarray(whole.pi)[:4], np.asarray(share.pi))

    def test_batch_power_matches_legacy(self, g):
        from repro.core import one_hot_personalizations

        eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
        P = one_hot_personalizations(g, [2, 7])
        rb_eng = eng.solve_batch(P, BatchConfig(batch_method="power",
                                                tol=1e-12))
        rb_leg = solve_pagerank_batch(g, P, method="power", tol=1e-12)
        assert np.array_equal(np.asarray(rb_eng.pi), np.asarray(rb_leg.pi))

    def test_forward_push_and_monte_carlo(self, g):
        eng = PageRankEngine(g)
        r_fp = eng.solve(ForwardPushConfig(xi=1e-13))
        assert r_fp.method == "forward_push" and r_fp.converged
        r_mc = eng.solve(MonteCarloConfig(walks_per_vertex=4, seed=3))
        pi_ref = reference_pagerank(g)
        assert float(jnp.max(jnp.abs(r_mc.pi - pi_ref))) < 0.05

    def test_traced_variant_via_method_override(self, g):
        eng = PageRankEngine(g)
        r = eng.solve(ItaConfig(xi=1e-10), method="ita_traced")
        assert r.res_history is not None and len(r.res_history) > 0

    def test_one_shot_funnel_removed(self):
        # solve_pagerank(g, method, **kwargs) completed its scheduled
        # deprecation cycle (docs/API.md §Deprecations): the engine and
        # make_config are the supported spellings now.
        import repro.core as core
        import repro.core.api as api

        assert not hasattr(core, "solve_pagerank")
        assert not hasattr(api, "solve_pagerank")


# --------------------------------------------------------------------------
# prepare-once: queries never re-derive per-graph state
# --------------------------------------------------------------------------
class TestPrepareReuse:
    def test_second_solve_reuses_ell_bucketing(self, g, monkeypatch):
        eng = PageRankEngine(g, EnginePlan(step_impl="ell"))
        r1 = eng.solve(ItaConfig(xi=1e-10))
        # after prepare, any re-bucketing or backend re-preparation is a bug
        import repro.sparse.ell as ell_mod

        def boom(*a, **k):
            raise AssertionError("re-bucketed inside a prepared engine")

        monkeypatch.setattr(ell_mod, "ell_from_graph", boom)
        monkeypatch.setattr(type(STEP_IMPLS["ell"]), "prepare", boom)
        r2 = eng.solve(ItaConfig(xi=1e-10))
        assert np.array_equal(np.asarray(r1.pi), np.asarray(r2.pi))
        assert eng.prepare_count == 1
        # control: the per-call path DOES hit prepare under the same patch
        with pytest.raises(AssertionError, match="re-bucketed"):
            ita(g, xi=1e-10, step_impl="ell")

    def test_frontier_plan_built_once(self, g, monkeypatch):
        eng = PageRankEngine(g, EnginePlan(step_impl="frontier"))

        def boom(*a, **k):
            raise AssertionError("frontier plan rebuilt")

        monkeypatch.setattr(type(STEP_IMPLS["frontier"]), "prepare", boom)
        eng.solve(ItaConfig(xi=1e-10))
        eng.solve(ItaConfig(xi=1e-10))
        assert eng.prepare_count == 1

    def test_describe(self, g):
        eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
        d = eng.describe()
        assert d["n"] == g.n and d["m"] == g.m
        assert d["step_impl"] == "dense" and d["prepare_count"] == 1
        assert d["devices"] == [0]
        assert d["n_dangling"] == int(jnp.sum(g.dangling_mask))
        assert d["n_unreferenced"] == int(jnp.sum(g.unreferenced_mask))


# --------------------------------------------------------------------------
# typed configs
# --------------------------------------------------------------------------
class TestConfigs:
    def test_make_config_dispatch(self):
        assert isinstance(make_config("ita", xi=1e-8), ItaConfig)
        assert isinstance(make_config("power", tol=1e-8), PowerConfig)
        assert isinstance(make_config("ita_traced"), ItaConfig)

    def test_unknown_method(self):
        with pytest.raises(KeyError):
            make_config("nope")

    def test_unknown_field_rejected(self):
        with pytest.raises(TypeError):
            make_config("ita", tol=1e-8)  # tol is PowerConfig vocabulary
        with pytest.raises(TypeError):
            ItaConfig(walks_per_vertex=4)

    def test_static_key_excludes_operands(self, g):
        a = ItaConfig(xi=1e-9)
        b = ItaConfig(xi=1e-9, p=jnp.ones((g.n,)) / g.n)
        assert a.static_key() == b.static_key()
        assert a.static_key() != ItaConfig(xi=1e-8).static_key()
        hash(a.static_key())  # must be usable as a cache key

    def test_engine_rejects_mismatched_impl(self, g):
        eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
        with pytest.raises(ValueError, match="prepared 'dense'"):
            eng.solve(ItaConfig(step_impl="ell"))
        with pytest.raises(ValueError, match="prepared 'dense'"):
            eng.solve_batch(jnp.ones((2, g.n)) / g.n,
                            BatchConfig(step_impl="ell"))

    def test_engine_rejects_wrong_config_type(self, g):
        eng = PageRankEngine(g)
        with pytest.raises(TypeError):
            eng.solve(BatchConfig())
        with pytest.raises(TypeError):
            eng.solve_batch(jnp.ones((2, g.n)) / g.n, ItaConfig())

    def test_solve_batch_shape_validation(self, g):
        eng = PageRankEngine(g)
        with pytest.raises(ValueError):
            eng.solve_batch(jnp.ones((g.n,)))


# --------------------------------------------------------------------------
# serving front end
# --------------------------------------------------------------------------
class TestServing:
    def test_topk_consistent_with_batch(self, g):
        from repro.core import one_hot_personalizations

        eng = PageRankEngine(g)
        seeds = [3, 17, 42]
        tk = eng.topk(seeds, k=4)
        rb = eng.solve_batch(one_hot_personalizations(g, seeds))
        assert tk.indices.shape == (3, 4) and tk.scores.shape == (3, 4)
        for b in range(3):
            row = np.asarray(rb.pi[b])
            # scores descend and equal pi at the reported indices
            assert np.all(np.diff(np.asarray(tk.scores[b])) <= 0)
            assert np.allclose(row[np.asarray(tk.indices[b])],
                               np.asarray(tk.scores[b]))
        # a PPR query ranks its own seed first on this graph
        assert int(tk.indices[0, 0]) == 3

    def test_ppr_serve_smoke(self, capsys):
        from repro.launch.ppr_serve import main

        assert main(["--smoke", "--queries", "12", "--batch", "4",
                     "--scale", "0.002"]) == 0
        out = capsys.readouterr().out
        assert "throughput" in out and "sample answer" in out


# --------------------------------------------------------------------------
# dynamic updates through the session
# --------------------------------------------------------------------------
class TestUpdate:
    def test_update_matches_reference(self, g):
        eng = PageRankEngine(g)
        r = eng.update(add=[(0, 7), (3, 11)])
        assert r.method == "ita_incremental" and r.converged
        ref = reference_pagerank(eng.graph)
        assert float(jnp.max(jnp.abs(r.pi - ref))) < 1e-10
        assert eng.graph.m == g.m + 2
        assert eng.prepare_count == 2  # one construction + one update

    def test_update_state_chains(self, g):
        eng = PageRankEngine(g)
        eng.update(add=[(2, 9)])
        r2 = eng.update(remove=[(2, 9)])
        # back to the original graph; state chained through both deltas
        ref = reference_pagerank(g)
        assert float(jnp.max(jnp.abs(r2.pi - ref))) < 1e-10
        assert eng.graph.m == g.m

    def test_queries_after_update_use_new_graph(self, g):
        eng = PageRankEngine(g, EnginePlan(step_impl="ell"))
        eng.update(add=[(1, 13)])
        r = eng.solve(ItaConfig(xi=1e-12))
        r_leg = ita(eng.graph, xi=1e-12, step_impl="ell")
        assert np.array_equal(np.asarray(r.pi), np.asarray(r_leg.pi))

    def test_apply_edge_delta_validation(self):
        g3 = graph_from_edges(np.array([0, 1]), np.array([1, 2]), 3)
        g4 = apply_edge_delta(g3, add=[(2, 0)], remove=[(0, 1)])
        assert g4.m == 2
        assert np.asarray(g4.out_deg).tolist() == [0, 1, 1]
        with pytest.raises(ValueError, match="absent"):
            apply_edge_delta(g3, remove=[(2, 2)])
        with pytest.raises(ValueError, match="existing"):
            apply_edge_delta(g3, add=[(0, 1)])
        with pytest.raises(ValueError, match="out of range"):
            apply_edge_delta(g3, add=[(0, 3)])


# --------------------------------------------------------------------------
# metrics regression (satellite): zero reference entries must not poison ERR
# --------------------------------------------------------------------------
class TestErrMaxRel:
    def test_zero_reference_entry_default_eps(self):
        pi_true = jnp.asarray([0.5, 0.5, 0.0])  # unreferenced-vertex shape
        pi = jnp.asarray([0.5, 0.4, 0.1])
        e = float(err_max_rel(pi, pi_true))
        assert np.isfinite(e)
        # zero-denominator entries contribute absolute error: max(0.2, 0.1)
        assert e == pytest.approx(0.2)

    def test_exact_match_with_zeros(self):
        pi_true = jnp.asarray([1.0, 0.0])
        assert float(err_max_rel(pi_true, pi_true)) == 0.0

    def test_eps_guard_still_applies(self):
        pi_true = jnp.asarray([1.0, 0.0])
        pi = jnp.asarray([1.0, 1e-8])
        assert float(err_max_rel(pi, pi_true, eps=1e-4)) == pytest.approx(1e-4)

    def test_unreferenced_graph_end_to_end(self):
        # a vertex with no in-edges under a one-hot personalization has
        # exactly zero reference mass -> old code returned inf/nan
        src = np.array([0, 1, 2])
        dst = np.array([1, 2, 0])
        g3 = graph_from_edges(src, dst, 4)  # vertex 3 isolated
        p = jnp.zeros((4,)).at[0].set(1.0)
        pi_ref = reference_pagerank(g3, p=p)
        assert float(pi_ref[3]) == 0.0
        r = ita(g3, p=p, xi=1e-13)
        assert np.isfinite(float(err_max_rel(r.pi, pi_ref)))
