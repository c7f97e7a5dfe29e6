"""Column-sharded ELL batched PPR — layout properties + mesh parity.

Three tiers:

  * layout properties (in-process, no mesh): ``Graph.ell_partitioned``
    recomposes to ``Graph.ell()`` row-for-row (same (src → dst) multiset
    per destination), the pure-jnp block oracle matches the dense push,
    the conversion is cached per (C, widths, align), and
    ``apply_edge_delta`` pins a fresh partition cache (the PR 4
    ``_ell_cache`` regression, one layout over);
  * single-round parity (in-process, (1, 1) mesh): one shard_mapped
    sharded-ELL round is BIT-identical to the single-device ELL backend
    round when C == 1 — the building-block contract;
  * mesh parity (subprocess, simulated host mesh): the sharded-ELL
    schedule on (R, C) grids matches the dense sharded schedule and the
    single-device batch to solver tolerance, ``step_impl="auto"`` on a
    C > 1 grid selects the ELL backend (and ``explain()`` says why), and
    ``engine.run(BatchQuery(...))`` executes it.

Device count / matrix grid come from ``REPRO_TEST_DEVICE_COUNT`` /
``REPRO_TEST_MESH`` (tests/_mesh_env.py), swept by the CI matrix.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _mesh_env import DEVICES, MESH, needs_devices, run_py
from _propcheck import given, settings
from _propcheck import strategies as st

from repro.core.backends import get_step_impl
from repro.core.batch import _batch_ita_step, one_hot_personalizations
from repro.core.distributed import (
    _ell_leaf_list,
    make_ita_batch_ell_step,
    resolve_mesh,
)
from repro.graph import web_graph
from repro.graph.structure import apply_edge_delta
from repro.sparse.ell import ell_cols_from_graph, spmv_ell_cols_ref


def _edges_by_dst_from_ell(ell) -> dict:
    """dst -> sorted src list, reconstructed from a full-graph ELLGraph."""
    out: dict = {}
    for b in ell.buckets:
        rows = np.asarray(b.row_ids)
        idx = np.asarray(b.src_idx)
        for r, v in enumerate(rows):
            if v == ell.sentinel:
                continue
            srcs = idx[r][idx[r] != ell.sentinel]
            out.setdefault(int(v), []).extend(srcs.tolist())
    for s, d in zip(np.asarray(ell.ovf_src), np.asarray(ell.ovf_dst)):
        out.setdefault(int(d), []).append(int(s))
    return {v: sorted(srcs) for v, srcs in out.items()}


def _edges_by_dst_from_cols(ellc) -> dict:
    """dst -> sorted GLOBAL src list, reconstructed from ELLCols blocks."""
    out: dict = {}
    for b in ellc.buckets:
        rows = np.asarray(b.row_ids)
        idx = np.asarray(b.src_idx)
        for j in range(ellc.C):
            for r, v in enumerate(rows[j]):
                if v == ellc.n_pad:
                    continue
                srcs = idx[j, r][idx[j, r] != ellc.nc] + j * ellc.nc
                out.setdefault(int(v), []).extend(srcs.tolist())
    if ellc.ovf_src.shape[-1]:
        for j in range(ellc.C):
            for s, d in zip(np.asarray(ellc.ovf_src[j]),
                            np.asarray(ellc.ovf_dst[j])):
                if d == ellc.n_pad:
                    continue
                out.setdefault(int(d), []).append(int(s) + j * ellc.nc)
    return {v: sorted(srcs) for v, srcs in out.items()}


# ---------------------------------------------------------------------------
# layout properties (no mesh)
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(n=st.integers(20, 300), mult=st.integers(2, 8),
       C=st.integers(1, 5), seed=st.integers(0, 999))
def test_ell_partitioned_recomposes_row_for_row(n, mult, C, seed):
    """The union of all column blocks' ELL+overflow slots is exactly the
    edge set of the full-graph bucketing — row-for-row, as global ids."""
    g = web_graph(n, n * mult, dangling_frac=0.2, seed=seed)
    full = _edges_by_dst_from_ell(g.ell())
    cols = _edges_by_dst_from_cols(g.ell_partitioned(C))
    assert cols == full


def test_ell_partitioned_ref_matches_dense_push():
    g = web_graph(400, 3200, dangling_frac=0.15, seed=3)
    W = jnp.asarray(np.random.default_rng(0).random((6, g.n)))
    y_dense = get_step_impl("dense").push_batch(g, None, W)
    for C in (1, 2, 3, 4):
        y_cols = spmv_ell_cols_ref(g.ell_partitioned(C), W)
        assert float(jnp.max(jnp.abs(y_cols - y_dense))) < 1e-12, C


def test_ell_partitioned_cache_identity_and_keys():
    g = web_graph(200, 1400, dangling_frac=0.2, seed=1)
    a = g.ell_partitioned(4)
    assert g.ell_partitioned(4) is a                      # cached
    assert g.ell_partitioned(2) is not a                  # distinct key
    b = g.ell_partitioned(4, widths=(8, 16))
    assert b is not a and b.signature() != a.signature()
    assert g.ell_partitioned(4, widths=(16, 8)) is b      # width order-free
    # geometry invariants
    assert a.C == 4 and a.n_pad % 4 == 0 and a.nc == a.n_pad // 4


def test_ell_partitioned_validates_C():
    g = web_graph(50, 300, seed=0)
    with pytest.raises(ValueError, match="C must be"):
        ell_cols_from_graph(g, 0)


def test_delta_pins_fresh_partition_cache():
    """apply_edge_delta must never leak the OLD edge set's column blocks —
    the regression twin of the PR 4 ``_ell_cache`` pin."""
    g = web_graph(120, 700, dangling_frac=0.2, seed=5)
    old = g.ell_partitioned(3)
    # an absent edge to add
    have = set(zip(np.asarray(g.src).tolist(), np.asarray(g.dst).tolist()))
    edge = next((s, d) for s in range(g.n) for d in range(g.n)
                if s != d and (s, d) not in have)
    g2 = apply_edge_delta(g, add=[edge])
    assert getattr(g2, "_ell_part_cache") == {}           # pinned fresh
    assert g.ell_partitioned(3) is old                    # old graph intact
    new = g2.ell_partitioned(3)
    assert new is not old
    assert _edges_by_dst_from_cols(new) != _edges_by_dst_from_cols(old)
    # and the new blocks represent exactly the new edge set
    assert sorted(_edges_by_dst_from_cols(new).get(edge[1], [])).count(
        edge[0]) == 1


def test_empty_graph_partition():
    from repro.graph.structure import graph_from_edges
    g = graph_from_edges(np.zeros(0), np.zeros(0), 10)
    ellc = g.ell_partitioned(2)
    assert ellc.buckets == () and ellc.ovf_src.shape == (2, 0)
    W = jnp.ones((2, 10))
    assert float(jnp.max(jnp.abs(spmv_ell_cols_ref(ellc, W)))) == 0.0


# ---------------------------------------------------------------------------
# single-round parity on the (1, 1) mesh (in-process)
# ---------------------------------------------------------------------------
def test_make_ita_batch_ell_step_single_round_bitwise():
    """One shard_mapped sharded-ELL round == one single-device ELL-backend
    round, BIT-identical, when C == 1 (block bucketing degenerates to the
    full-graph bucketing and the psum_scatter is the identity)."""
    g = web_graph(300, 1800, dangling_frac=0.25, seed=11)
    mesh = resolve_mesh((1, 1))
    ellc = g.ell_partitioned(1)
    H0 = (one_hot_personalizations(g, [5, 41]) * g.n).astype(jnp.float64)
    inv = g.inv_out_deg(jnp.float64)
    nd = jnp.logical_not(g.dangling_mask)
    step = make_ita_batch_ell_step(mesh, ellc, 0.85, 1e-10)
    H1, Pi1, n1 = step(H0, jnp.zeros_like(H0), inv, nd,
                       *_ell_leaf_list(ellc))
    backend = get_step_impl("ell")
    H2, Pi2, n2, *_ = _batch_ita_step(backend, g, backend.prepare(g), H0,
                                     jnp.zeros_like(H0), 0.85, 1e-10, inv, nd)
    assert jnp.array_equal(H1, H2) and jnp.array_equal(Pi1, Pi2)
    assert int(n1) == int(n2)


# ---------------------------------------------------------------------------
# mesh parity (subprocess, simulated host mesh)
# ---------------------------------------------------------------------------
@needs_devices(8)
def test_sharded_ell_matches_dense_sharded_4x2():
    """The acceptance bar: on a (4, 2) host mesh the sharded-ELL result
    matches the dense sharded schedule (and the single-device batch)
    within the declared tolerance, with identical iteration counts."""
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core.batch import ita_batch, one_hot_personalizations
        from repro.core.distributed import ita_batch_distributed, resolve_mesh
        g = web_graph(900, 7000, dangling_frac=0.15, seed=4)
        P = one_hot_personalizations(g, [0, 13, 256, 257, 888])
        mesh = resolve_mesh((4, 2))
        ref = ita_batch(g, P, xi=1e-12)
        rd = ita_batch_distributed(g, P, mesh, xi=1e-12, step_impl="dense")
        re = ita_batch_distributed(g, P, mesh, xi=1e-12, step_impl="ell")
        print(json.dumps({
            "err_ell_vs_dense": float(jnp.max(jnp.abs(rd.pi - re.pi))),
            "err_ell_vs_single": float(jnp.max(jnp.abs(ref.pi - re.pi))),
            "iters": [ref.iterations, rd.iterations, re.iterations],
            "method": re.method}))
    """)
    assert out["err_ell_vs_dense"] < 1e-10, out
    assert out["err_ell_vs_single"] < 1e-10, out
    assert len(set(out["iters"])) == 1, out
    assert out["method"] == "ita_batch_dist[ell|4x2]", out


@needs_devices(8)
def test_engine_auto_selects_ell_on_rc_mesh_and_runs_batchquery():
    """step_impl="auto" on an (R, C) engine mesh prepares the ELL backend,
    plan(BatchQuery).explain() says why, and run(BatchQuery) executes the
    sharded-ELL path with results matching a dense single-device engine."""
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core import (PageRankEngine, EnginePlan, PPRQuery,
                                TopKQuery, BatchQuery,
                                one_hot_personalizations)
        g = web_graph(600, 4200, dangling_frac=0.2, seed=5)
        P = one_hot_personalizations(g, [1, 7, 42, 99, 7, 311])
        e = PageRankEngine(g, EnginePlan(step_impl="auto", mesh=(4, 2)))
        q = BatchQuery((PPRQuery(p_batch=P),
                        TopKQuery(sources=[1, 7, 42], k=5)))
        text = e.plan(q).explain()
        env = e.run(q)
        e0 = PageRankEngine(g, EnginePlan(step_impl="dense"))
        r0 = e0.solve_batch(P)
        t0 = e0.topk([1, 7, 42], k=5)
        ppr_env, topk_env = env.result
        print(json.dumps({
            "step_impl": e.step_impl,
            "sub_backends": [sp.backend for sp in e.plan(q).sub_plans],
            "sub_paths": [sp.path for sp in e.plan(q).sub_plans],
            "err": float(jnp.max(jnp.abs(r0.pi - ppr_env.result.pi))),
            "iters": [r0.iterations, ppr_env.iterations],
            "topk_idx_equal": bool(jnp.array_equal(
                t0.indices, topk_env.result.indices)),
            "method": ppr_env.result.method,
            "explains_backend": "backend=ell" in text,
            "explains_mesh": "mesh=(4, 2)" in text,
            "explains_why": "sharded-ELL column blocks" in text
                            and "lowest est. cost" in text}))
    """)
    assert out["step_impl"] == "ell", out
    assert out["sub_backends"] == ["ell", "ell"], out
    assert out["sub_paths"] == ["distributed-batch"] * 2, out
    assert out["err"] < 1e-10, out
    assert out["iters"][0] == out["iters"][1], out
    assert out["topk_idx_equal"], out
    assert out["method"] == "ita_batch_dist[ell|4x2]", out
    assert out["explains_backend"], out
    assert out["explains_mesh"] and out["explains_why"], out


@pytest.mark.slow
def test_sharded_ell_env_grid_engine_lifecycle():
    """On the matrix cell's grid: an auto-prepared engine serves within
    tolerance and survives an update (re-prepare rebuilds the column
    blocks for the new edge set on the same mesh)."""
    R, C = MESH
    if R * C > DEVICES:
        pytest.skip(f"grid {MESH} needs {R * C} devices, have {DEVICES}")
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core import PageRankEngine, EnginePlan, one_hot_personalizations
        R, C = %d, %d
        g = web_graph(500, 3600, dangling_frac=0.15, seed=9)
        P = one_hot_personalizations(g, [2, 71, 450])
        e0 = PageRankEngine(g, EnginePlan(step_impl="dense"))
        e1 = PageRankEngine(g, EnginePlan(step_impl="auto", mesh=(R, C)))
        err0 = float(jnp.max(jnp.abs(e0.solve_batch(P).pi - e1.solve_batch(P).pi)))
        e0.update(add=[(2, 450)]); e1.update(add=[(2, 450)])
        err1 = float(jnp.max(jnp.abs(e0.solve_batch(P).pi - e1.solve_batch(P).pi)))
        print(json.dumps({"err_before": err0, "err_after": err1,
                          "impl": e1.step_impl,
                          "prepares": e1.prepare_count}))
    """ % MESH)
    assert out["err_before"] < 1e-10, out
    assert out["err_after"] < 1e-10, out
    assert out["prepares"] == 2, out
    if C > 1:
        assert out["impl"] == "ell", out
