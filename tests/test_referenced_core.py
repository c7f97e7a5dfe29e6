"""The dense push's second edge list: the referenced core's out-edges.

Paper §III: a vertex of weak-unreferenced level k (level 0: no in-edges;
level k: in-neighbours only of lower levels) receives nothing after ITA
round k.  The vertices of no finite level form the referenced core,
closed under out-edges.  ``DenseBackend.prepare`` peels the levels
(``Graph.reference_levels``) and keeps the core's out-edges as a list of
their own; each push walks it whenever its input is zero off the core.
These tests hold the peel to the paper's definition, the core list to the
full list bit for bit, the full list to a numpy sum, and the solvers'
``core_rounds`` counter to the level theory.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_step_impl, ita, ita_batch, one_hot_personalizations
from repro.core.backends import _walk
from repro.core.engine import EnginePlan, PageRankEngine
from repro.graph import erdos_renyi, graph_from_edges, random_dag, web_graph

DENSE = get_step_impl("dense")


def _dag_with_core():
    """A random DAG (generators.random_dag, the deepest level cascade) whose
    last 100 vertices, in topological order, are closed into a cycle."""
    dag = random_dag(800, 6000, seed=21)
    ring = np.arange(700, 800)
    src = np.concatenate([np.asarray(dag.src), ring])
    dst = np.concatenate([np.asarray(dag.dst), np.roll(ring, -1)])
    return graph_from_edges(src, dst, 800)


def _self_loops():
    """A web graph with many unreferenced vertices, a tenth of which hold
    a self-loop: referenced by themselves alone, so in the core."""
    g = web_graph(600, 4200, dangling_frac=0.1, unref_boost=0.4, seed=22)
    looped = np.flatnonzero(np.asarray(g.in_deg) == 0)[::10]
    return graph_from_edges(np.concatenate([np.asarray(g.src), looped]),
                            np.concatenate([np.asarray(g.dst), looped]),
                            g.n)


def _undirected():
    g = erdos_renyi(400, 1500, seed=23)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    return graph_from_edges(np.concatenate([src, dst]),
                            np.concatenate([dst, src]), g.n)


GRAPHS = {"dag": _dag_with_core, "self-loops": _self_loops,
          "undirected": _undirected}


def _brute_levels(g):
    """Levels straight from the definition, as a fixed point: a vertex
    whose in-neighbours all have levels gets one more than their highest
    (0 with none); the rest, -1, are the referenced core."""
    ins = [[] for _ in range(g.n)]
    for s, d in zip(np.asarray(g.src).tolist(), np.asarray(g.dst).tolist()):
        ins[d].append(s)
    level = [None] * g.n
    changed = True
    while changed:
        changed = False
        for v in range(g.n):
            if level[v] is None and all(level[u] is not None for u in ins[v]):
                level[v] = 1 + max((level[u] for u in ins[v]), default=-1)
                changed = True
    return np.array([-1 if lv is None else lv for lv in level])


def _core_only(g, shape, seed):
    """Random values on the core's vertices, exact zeros elsewhere."""
    w = np.random.default_rng(seed).random(shape)
    return jnp.asarray(np.where(g.reference_levels < 0, w, 0.0))


@pytest.mark.parametrize("name", sorted(GRAPHS))
@pytest.mark.parametrize("rows", [None, 6], ids=["push", "push_batch"])
def test_core_push_bitwise_equals_full_push(name, rows):
    g = GRAPHS[name]()
    ctx = DENSE.prepare(g)
    w = _core_only(g, (g.n,) if rows is None else (rows, g.n), seed=1)
    counted = DENSE.push_counted if rows is None else DENSE.push_batch_counted
    y, core = counted(g, ctx, w)
    full = _walk(w, ctx)
    if name == "undirected":
        # every vertex with an edge is referenced: no second list
        assert ctx.core is None and core is None
        assert DENSE.core_edges(ctx) is None
    else:
        assert bool(core)
        assert 0 < DENSE.core_edges(ctx) < g.m
    np.testing.assert_array_equal(np.asarray(y), np.asarray(full))


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_full_branch_matches_numpy_segment_sum(name):
    g = GRAPHS[name]()
    ctx = DENSE.prepare(g)
    W = np.random.default_rng(2).random((3, g.n))  # mass off the core too
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    ref = np.stack([np.bincount(dst, weights=w[src], minlength=g.n)
                    for w in W])
    Y, core = DENSE.push_batch_counted(g, ctx, jnp.asarray(W))
    y, core_one = DENSE.push_counted(g, ctx, jnp.asarray(W[0]))
    if ctx.core is not None:
        assert not bool(core) and not bool(core_one)
    np.testing.assert_allclose(np.asarray(Y), ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(y), ref[0], rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("edges", ["dag", "self-loops", "web", "chain",
                                   "no-edges"])
def test_peel_matches_the_definition(edges):
    if edges in GRAPHS:
        g = GRAPHS[edges]()
    elif edges == "web":
        g = web_graph(300, 1800, dangling_frac=0.2, unref_boost=0.3, seed=24)
    elif edges == "chain":  # 0 -> 1 -> ... -> 9, then a 2-cycle 10 <-> 11
        g = graph_from_edges(np.r_[np.arange(9), 10, 11, 9],
                             np.r_[np.arange(1, 10), 11, 10, 10], 12)
    else:
        g = graph_from_edges(np.zeros(0, int), np.zeros(0, int), 5)
    levels = g.reference_levels
    np.testing.assert_array_equal(levels, _brute_levels(g))
    core = levels < 0
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    assert not np.any(core[src] & ~core[dst])  # closed under out-edges
    if edges == "chain":
        assert levels.max() == 9 and core.tolist() == [False] * 10 + [True] * 2


@pytest.mark.parametrize("name", ["dag", "self-loops"])
def test_rank_solve_walks_the_core_after_the_last_level(name):
    g = GRAPHS[name]()
    K = int(g.reference_levels.max())
    r = ita(g, xi=1e-10, ctx=DENSE.prepare(g))
    assert r.converged
    assert 0 < r.core_rounds <= r.iterations
    assert r.core_rounds >= r.iterations - (K + 1)


def test_ppr_rows_seeded_in_the_core_walk_it_every_round():
    g = _self_loops()
    seeds = np.flatnonzero(g.reference_levels < 0)[:8]
    P = one_hot_personalizations(g, seeds)
    r = ita_batch(g, P, ctx=DENSE.prepare(g))
    assert r.iterations > 0 and r.core_rounds == r.iterations
    eng = PageRankEngine(g, plan=EnginePlan(step_impl="dense"))
    served = eng.topk(seeds, k=5).result
    assert served.core_rounds == served.iterations == r.iterations
    assert eng.describe(include_plan=False)["core_edges"] == \
        DENSE.core_edges(eng._ctx)


def test_no_core_list_counts_none():
    g = _undirected()
    assert ita(g, xi=1e-10).core_rounds is None
    r = ita(GRAPHS["dag"](), xi=1e-10, step_impl="frontier")
    assert r.core_rounds is None
