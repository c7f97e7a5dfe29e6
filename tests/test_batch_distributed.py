"""Mesh-sharded batched PPR == single-device batched PPR.

Two tiers of coverage:

  * in-process tests on a (1, 1) mesh (the real single CPU device) for the
    machinery that must not need fake devices: config validation, engine
    error contracts, ``one_hot_personalizations`` edge cases;
  * subprocess tests on a simulated host mesh (the test_distributed.py
    pattern — the main pytest process must keep seeing one device, see
    conftest) asserting the acceptance bar: batch-parallel sharding is
    BIT-IDENTICAL to ``ita_batch`` per backend and to the unsharded
    engine, and the vertex-sharded (R, C) schedule agrees to solver
    tolerance.

The subprocess device count and the matrix grid come from
``REPRO_TEST_DEVICE_COUNT`` / ``REPRO_TEST_MESH`` (tests/_mesh_env.py) —
CI sweeps {2, 8} devices × {(2,1), (8,1), (4,2), (2,4)} grids.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _mesh_env import DEVICES, MESH, needs_devices, run_py
from repro.core import BatchConfig, EnginePlan, PageRankEngine
from repro.core.batch import ita_batch, one_hot_personalizations
from repro.core.distributed import ita_batch_distributed, resolve_mesh
from repro.graph import web_graph


# ---------------------------------------------------------------------------
# simulated host mesh (subprocess)
# ---------------------------------------------------------------------------
def test_engine_mesh_solve_batch_bit_identical():
    """The acceptance bar: EnginePlan(mesh=...) serving == unsharded engine,
    bitwise, including topk answers — on the (n_dev, 1) grid of whatever
    the matrix cell provides."""
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core import PageRankEngine, EnginePlan, one_hot_personalizations
        R = %d
        g = web_graph(600, 4200, dangling_frac=0.2, seed=5)
        P = one_hot_personalizations(g, [1, 7, 42, 99, 7, 311])
        e0 = PageRankEngine(g, EnginePlan(step_impl="dense"))
        e1 = PageRankEngine(g, EnginePlan(step_impl="dense", mesh=(R, 1)))
        r0, r1 = e0.solve_batch(P), e1.solve_batch(P)
        t0, t1 = e0.topk([1, 7, 42], k=5), e1.topk([1, 7, 42], k=5)
        print(json.dumps({
            "pi_equal": bool(jnp.array_equal(r0.pi, r1.pi)),
            "iters": [r0.iterations, r1.iterations],
            "topk_equal": bool(jnp.array_equal(t0.indices, t1.indices))
                          and bool(jnp.array_equal(t0.scores, t1.scores)),
            "mesh": e1.describe()["mesh"], "method": r1.method}))
    """ % DEVICES)
    assert out["pi_equal"], out
    assert out["topk_equal"], out
    assert out["iters"][0] == out["iters"][1], out
    assert out["mesh"] == [DEVICES, 1], out


def test_mesh_matrix_env_grid():
    """The matrix cell's own grid (REPRO_TEST_MESH): both vertex-sharded
    schedules (dense and sharded-ELL) agree with the single-device batch —
    bitwise per backend when C == 1, to solver tolerance when C > 1."""
    R, C = MESH
    if R * C > DEVICES:
        pytest.skip(f"grid {MESH} needs {R * C} devices, have {DEVICES}")
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core.batch import ita_batch, one_hot_personalizations
        from repro.core.distributed import ita_batch_distributed, resolve_mesh
        R, C = %d, %d
        g = web_graph(700, 5200, dangling_frac=0.15, seed=6)
        P = one_hot_personalizations(g, [0, 13, 256, 257, 699])
        mesh = resolve_mesh((R, C))
        out = {}
        for impl in ("dense", "ell"):
            ref = ita_batch(g, P, xi=1e-12, step_impl=impl)
            r = ita_batch_distributed(g, P, mesh, xi=1e-12, step_impl=impl)
            out[impl] = {
                "err": float(jnp.max(jnp.abs(ref.pi - r.pi))),
                "equal": bool(jnp.array_equal(ref.pi, r.pi)),
                "iters": [ref.iterations, r.iterations],
                "method": r.method}
        print(json.dumps(out))
    """ % MESH)
    for impl in ("dense", "ell"):
        r = out[impl]
        assert r["iters"][0] == r["iters"][1], (impl, out)
        assert r["method"] == f"ita_batch_dist[{impl}|{R}x{C}]", (impl, out)
        if C == 1:
            assert r["equal"], (impl, out)   # batch-parallel: bitwise
        else:
            assert r["err"] < 1e-10, (impl, out)


@needs_devices(8)
def test_ita_batch_distributed_2d_matches_single_device():
    """(4, 2) grid — vertex axis sharded over "model": the cross-column
    psum_scatter regroups float sums, so tolerance not bitwise."""
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core.batch import ita_batch, one_hot_personalizations
        from repro.core.distributed import ita_batch_distributed, resolve_mesh
        g = web_graph(900, 7000, dangling_frac=0.15, seed=4)
        P = one_hot_personalizations(g, [0, 13, 256, 257, 888])
        ref = ita_batch(g, P, xi=1e-12)
        r = ita_batch_distributed(g, P, resolve_mesh((4, 2)), xi=1e-12)
        err = float(jnp.max(jnp.abs(ref.pi - r.pi)))
        print(json.dumps({"err": err, "iters": [ref.iterations, r.iterations],
                          "method": r.method}))
    """)
    assert out["err"] < 1e-10, out
    assert out["iters"][0] == out["iters"][1], out


@pytest.mark.slow
def test_ita_batch_distributed_ell_bitwise():
    """Batch-parallel sharding preserves the ELL backend's exact numerics."""
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core.batch import ita_batch, one_hot_personalizations
        from repro.core.distributed import ita_batch_distributed, resolve_mesh
        g = web_graph(400, 2600, dangling_frac=0.2, seed=2)
        P = one_hot_personalizations(g, [3, 50, 399])
        ref = ita_batch(g, P, xi=1e-10, step_impl="ell")
        r = ita_batch_distributed(g, P, resolve_mesh((%d, 1)), xi=1e-10,
                                  step_impl="ell")
        print(json.dumps({"equal": bool(jnp.array_equal(ref.pi, r.pi)),
                          "method": r.method}))
    """ % DEVICES)
    assert out["equal"], out


@needs_devices(8)
@pytest.mark.slow
def test_engine_mesh_2d_and_update_lifecycle():
    """A vertex-sharded engine serves within tolerance and survives an
    update (re-prepare re-lays-out the new graph on the same mesh)."""
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core import PageRankEngine, EnginePlan, one_hot_personalizations
        g = web_graph(500, 3600, dangling_frac=0.15, seed=9)
        P = one_hot_personalizations(g, [2, 71, 450])
        e0 = PageRankEngine(g, EnginePlan(step_impl="dense"))
        e1 = PageRankEngine(g, EnginePlan(step_impl="dense", mesh=(4, 2)))
        err0 = float(jnp.max(jnp.abs(e0.solve_batch(P).pi - e1.solve_batch(P).pi)))
        e0.update(add=[(2, 450)]); e1.update(add=[(2, 450)])
        err1 = float(jnp.max(jnp.abs(e0.solve_batch(P).pi - e1.solve_batch(P).pi)))
        print(json.dumps({"err_before": err0, "err_after": err1,
                          "prepares": e1.prepare_count}))
    """)
    assert out["err_before"] < 1e-10, out
    assert out["err_after"] < 1e-10, out
    assert out["prepares"] == 2, out


# ---------------------------------------------------------------------------
# in-process: (1, 1) mesh on the real single device
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_graph():
    return web_graph(300, 1800, dangling_frac=0.25, seed=11)


def test_trivial_mesh_bit_identical_in_process(small_graph):
    g = small_graph
    P = one_hot_personalizations(g, [5, 9, 5])
    ref = ita_batch(g, P, xi=1e-10)
    r = ita_batch_distributed(g, P, resolve_mesh((1, 1)), xi=1e-10)
    assert jnp.array_equal(ref.pi, r.pi)
    assert r.iterations == ref.iterations
    # "auto"/None resolve on the batch-parallel (C == 1) branch too, not
    # just on C > 1 grids (regression: used to KeyError)
    for impl in ("auto", None):
        r_auto = ita_batch_distributed(g, P, resolve_mesh((1, 1)), xi=1e-10,
                                       step_impl=impl)
        assert r_auto.method == "ita_batch_dist[dense|1x1]"  # cpu cost pick
        assert jnp.array_equal(ref.pi, r_auto.pi)


def test_engine_trivial_mesh_and_opt_out(small_graph):
    g = small_graph
    P = one_hot_personalizations(g, [4, 200])
    e0 = PageRankEngine(g, EnginePlan(step_impl="dense"))
    e1 = PageRankEngine(g, EnginePlan(step_impl="dense", mesh=(1,)))
    r_sharded = e1.solve_batch(P)
    assert r_sharded.method.startswith("ita_batch_dist[")
    assert jnp.array_equal(e0.solve_batch(P).pi, r_sharded.pi)
    # shard_batch=False opts the query out of the mesh
    r_opt = e1.solve_batch(P, BatchConfig(shard_batch=False))
    assert r_opt.method == "ita_batch[dense]"
    assert jnp.array_equal(r_sharded.pi, r_opt.pi)


def test_engine_mesh_error_contracts(small_graph):
    g = small_graph
    with pytest.raises(ValueError, match="jittable"):
        PageRankEngine(g, EnginePlan(step_impl="frontier", mesh=(1, 1)))
    with pytest.raises(ValueError, match="devices"):
        resolve_mesh((1024, 1024))
    e = PageRankEngine(g, EnginePlan(step_impl="dense", mesh=(1, 1)))
    P = one_hot_personalizations(g, [0])
    with pytest.raises(ValueError, match="mesh_shape"):
        e.solve_batch(P, BatchConfig(mesh_shape=(2, 1)))
    # matching request passes
    assert e.solve_batch(P, BatchConfig(mesh_shape=(1, 1))).batch == 1
    # engine without a mesh refuses a mesh_shape request
    e_plain = PageRankEngine(g, EnginePlan(step_impl="dense"))
    with pytest.raises(ValueError, match="mesh_shape"):
        e_plain.solve_batch(P, BatchConfig(mesh_shape=(1, 1)))


def test_make_ita_batch_step_single_round(small_graph):
    """One shard_mapped vertex-sharded round == one single-device batched
    ITA round — the building-block contract of ``make_ita_batch_step``
    (the same parity ``make_ita_2d_step`` holds against ``ita_step``)."""
    from repro.core.backends import get_step_impl
    from repro.core.batch import _batch_ita_step
    from repro.core.distributed import make_ita_batch_step
    from repro.graph.partition import partition_cols

    g = small_graph
    mesh = resolve_mesh((1, 1))
    part = partition_cols(g, 1)
    assert part.n_pad == g.n  # C=1: no vertex padding, natural order
    H0 = (one_hot_personalizations(g, [5, 41]) * g.n).astype(jnp.float64)
    inv = g.inv_out_deg(jnp.float64)
    nd = jnp.logical_not(g.dangling_mask)
    step = make_ita_batch_step(mesh, dict(nr=part.nr), 0.85, 1e-10)
    H1, Pi1, n1 = step(H0, jnp.zeros_like(H0),
                       jnp.asarray(part.src_local[0]),
                       jnp.asarray(part.dst_local[0]), inv, nd)
    H2, Pi2, n2, *_ = _batch_ita_step(get_step_impl("dense"), g, None, H0,
                                     jnp.zeros_like(H0), 0.85, 1e-10, inv, nd)
    assert jnp.array_equal(H1, H2) and jnp.array_equal(Pi1, Pi2)
    assert int(n1) == int(n2)


def test_engine_single_axis_mesh(small_graph):
    """A prebuilt Mesh with only a "data" axis normalizes to (R, 1)
    everywhere — describe(), mesh_shape compatibility, serving."""
    g = small_graph
    mesh = jax.make_mesh((1,), ("data",))
    e = PageRankEngine(g, EnginePlan(step_impl="dense", mesh=mesh))
    assert e.describe()["mesh"] == (1, 1)
    P = one_hot_personalizations(g, [3])
    assert e.solve_batch(P, BatchConfig(mesh_shape=(1,))).batch == 1
    with pytest.raises(ValueError, match="data"):
        resolve_mesh(jax.make_mesh((1,), ("model",)))


def test_batch_config_mesh_knob_validation():
    assert BatchConfig().mesh_shape is None
    assert BatchConfig().shard_batch is True
    assert BatchConfig(mesh_shape=(4,)).mesh_shape == (4,)
    assert BatchConfig(mesh_shape=[8, 1]).mesh_shape == (8, 1)  # normalized
    hash(BatchConfig(mesh_shape=[8, 1]).static_key())  # stays hashable
    for bad in [(0,), (2, 0), (-1, 2), (1, 2, 3), (), "8x1", 3.5]:
        with pytest.raises(ValueError):
            BatchConfig(mesh_shape=bad)
    with pytest.raises(ValueError):
        BatchConfig(shard_batch="yes")
    with pytest.raises(ValueError):
        BatchConfig(shard_batch=1)


def test_one_hot_duplicate_seeds(small_graph):
    g = small_graph
    P = one_hot_personalizations(g, [7, 7, 7])
    assert P.shape == (3, g.n)
    assert np.array_equal(np.asarray(P[0]), np.asarray(P[1]))
    r = ita_batch(g, P, xi=1e-10)
    assert jnp.array_equal(r.pi[0], r.pi[1]) and jnp.array_equal(r.pi[1], r.pi[2])


def test_one_hot_dangling_seed(small_graph):
    g = small_graph
    dangling = int(np.flatnonzero(np.asarray(g.out_deg) == 0)[0])
    P = one_hot_personalizations(g, [dangling])
    assert float(P[0, dangling]) == 1.0 and float(jnp.sum(P)) == 1.0
    # a dangling seed cannot transmit: the ranking is its own one-hot
    r = ita_batch(g, P, xi=1e-10)
    assert r.converged
    np.testing.assert_allclose(np.asarray(r.pi[0]), np.asarray(P[0]))


def test_one_hot_empty_seed_list(small_graph):
    g = small_graph
    P = one_hot_personalizations(g, [])
    assert P.shape == (0, g.n)
    assert P.dtype == jnp.float64
    r = ita_batch(g, P, xi=1e-10)
    assert r.pi.shape == (0, g.n) and r.batch == 0
    # and through the sharded path
    r2 = ita_batch_distributed(g, P, resolve_mesh((1, 1)), xi=1e-10)
    assert r2.pi.shape == (0, g.n) and r2.converged
