"""Edge deltas on the dense push's live layout (``repro.core.live``).

A dense engine takes its first ``DeltaQuery`` by laying its edge lists out
with slack; every later delta edits them in place, in fixed shapes.  These
tests hold each refresh to the reference on its own graph version (built
here with numpy set operations, never with ``apply_edge_delta``), count
the programs compiled after the first delta, overflow the slack on
purpose, check the layout's invariants, and hold the padded push to the
plain push bit for bit on a graph that never changed.

Tolerance: every refresh runs the signed cascade to ``update_xi`` 1e-12,
leaving at most 1e-12 per vertex of a total mass of n; pushed on, that
moves pi by far less than the 1e-10 the tests allow, and the reference
(power iteration to an l2 step of 1e-14) is closer still.
"""
import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DeltaQuery, EnginePlan, ItaConfig, PageRankEngine,
                        RankQuery, TopKQuery, get_step_impl,
                        reference_pagerank)
from repro.core.backends import _dense_runs, _walk
from repro.core.live import LiveLayout
from repro.graph import graph_from_edges, web_graph

DENSE = get_step_impl("dense")
TOL = 1e-10
COMPILE = "/jax/core/compile/backend_compile_duration"


def _graph():
    return web_graph(600, 4200, dangling_frac=0.15, unref_boost=0.3,
                     seed=31)


def _keys(g):
    return np.asarray(g.dst, np.int64) * g.n + np.asarray(g.src)


class Version:
    """The graph's edge set as plain numpy keys, the oracle's side."""

    def __init__(self, g):
        self.n, self.keys = g.n, np.sort(_keys(g))

    def apply(self, add, remove):
        n = self.n
        rk = np.array([d * n + s for s, d in remove], np.int64)
        ak = np.array([d * n + s for s, d in add], np.int64)
        self.keys = np.union1d(np.setdiff1d(self.keys, rk), ak)

    def graph(self):
        return graph_from_edges(self.keys % self.n, self.keys // self.n,
                                self.n)

    def has(self, s, d):
        return d * self.n + s in set(self.keys.tolist())


def _random_delta(version, rng, n_add, n_del):
    n = version.n
    remove = [(int(k % n), int(k // n))
              for k in rng.choice(version.keys, n_del, replace=False)]
    add = set()
    while len(add) < n_add:
        s, d = (int(x) for x in rng.integers(0, n, 2))
        if s != d and not version.has(s, d) and (s, d) not in remove:
            add.add((s, d))
    return sorted(add), remove


def _absent(version, s):
    """An edge from ``s`` that the version does not hold."""
    d = next(d for d in range(version.n)
             if d != s and not version.has(s, d))
    return s, d


def _special_deltas(g):
    """Deltas that move vertices between the paper's classes: a dangling
    vertex gains an edge, an unreferenced one an in-edge from the
    referenced core, a vertex loses every out-edge, and an added edge is
    deleted again."""
    out_deg, in_deg = np.asarray(g.out_deg), np.asarray(g.in_deg)
    levels = g.reference_levels
    dangling = np.flatnonzero(out_deg == 0)
    unref = np.flatnonzero((in_deg == 0) & (out_deg > 0))
    core = np.flatnonzero((levels < 0) & (out_deg >= 2))
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    a, b = (int(x) for x in dangling[:2])
    u, w = (int(x) for x in unref[:2])
    v, t, bare = (int(x) for x in core[:3])
    yield [(a, t), (v, u), (b, w)], []
    yield [], [(int(s), int(d)) for s, d in zip(src[src == bare],
                                                dst[src == bare])]
    yield [(bare, a)], [(a, t)]


def _mixed_delta(g):
    """A delta whose added edges mix the two lists' sources: into one
    destination of the referenced core, edges from the core and from
    outside it; and an edge from the core to an unreferenced vertex,
    which joins the core list's set with every original out-edge it has.
    Returns ``(add, seed_in, seed_out)``: a core vertex whose out-neighbours
    feed the destination and the joined vertex, and a vertex outside the
    core with an out-edge that stays outside it."""
    out_deg, in_deg = np.asarray(g.out_deg), np.asarray(g.in_deg)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    off = g.reference_levels >= 0
    version = Version(g)
    core = np.flatnonzero(~off & (out_deg > 0))
    seed_in = int(core[np.argmax(out_deg[core])])
    nbrs = dst[(src == seed_in) & ~off[dst]]
    v = int(next(x for x in core[np.argsort(-in_deg[core])]
                 if x != seed_in and x not in nbrs))
    u = int(np.flatnonzero((in_deg == 0) & (out_deg >= 2))[0])
    reach = set(dst[src == u].tolist()) | {u}
    rest = [int(s) for s in np.unique(src[off[src] & off[dst]])
            if s not in reach and not version.has(s, v)]
    feed_in = [int(s) for s in nbrs if not version.has(s, v)][:10]
    add = [(s, v) for s in feed_in + rest[:10]] + [(seed_in, u)]
    return add, seed_in, rest[-1]


def _check_layout(eng, version):
    """The live layout's invariants on the current edge set: S closed under
    out-edges, the edges among the rest running up the peel levels, and
    the core list holding exactly S's out-edges."""
    live = eng._live
    n, keys = version.n, version.keys
    src, dst = keys % n, keys // n
    s = live.in_s
    assert not np.any(s[src] & ~s[dst])
    rest = ~s[src] & ~s[dst]
    assert np.all(live.levels[src[rest]] < live.levels[dst[rest]])
    assert eng.core_edges == int(np.count_nonzero(s[src]))


def test_delta_chain_matches_the_oracle_on_every_version():
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense", update_xi=1e-12))
    version, rng = Version(g), np.random.default_rng(5)
    special = list(_special_deltas(g))
    for i in range(6):
        add, remove = (special[i] if i < len(special)
                       else _random_delta(version, rng, 12 - i, 9))
        env = eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
        version.apply(add, remove)
        ref_g = version.graph()
        ref = reference_pagerank(ref_g)
        assert float(jnp.max(jnp.abs(env.result.pi - ref))) < TOL, i
        assert env.result.relayouts == 0 and env.converged
        _check_layout(eng, version)
        assert eng.n_dangling == int(jnp.sum(ref_g.dangling_mask))
        assert eng.n_unreferenced == int(jnp.sum(ref_g.unreferenced_mask))
        assert np.array_equal(eng.dangling_mask, ref_g.dangling_mask)
        assert np.array_equal(eng.unreferenced_mask, ref_g.unreferenced_mask)
        assert eng.describe(include_plan=False)["m"] == ref_g.m
    assert eng.graph_version == 6 and eng.prepare_count == 7
    assert np.array_equal(np.sort(_keys(eng.graph)), version.keys)


def test_classes_change_as_the_deltas_say():
    """The special deltas do move vertices between §III's classes."""
    g = _graph()
    deltas = list(_special_deltas(g))
    (a, _), (_, u), _ = deltas[0][0]
    bare = deltas[2][0][0][0]
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    assert int(g.out_deg[a]) == 0 and int(g.in_deg[u]) == 0
    assert not eng.graph.reference_levels[u] < 0
    eng.run(DeltaQuery(add=tuple(deltas[0][0])))
    assert int(eng.graph.out_deg[a]) == 1 and int(eng.graph.in_deg[u]) == 1
    assert eng._live.in_s[u]  # referenced from the core: it joined S
    eng.run(DeltaQuery(remove=tuple(deltas[1][1])))
    assert int(eng.graph.out_deg[bare]) == 0
    eng.run(DeltaQuery(add=tuple(deltas[2][0]),
                       remove=tuple(deltas[2][1])))
    assert int(eng.graph.out_deg[a]) == 0


def test_deltas_after_the_first_compile_nothing():
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    version, rng = Version(g), np.random.default_rng(6)
    add, remove = _random_delta(version, rng, 10, 10)
    eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
    version.apply(add, remove)
    compiled = []

    def seen(name, *a, **kw):
        if name == COMPILE:
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(seen)
    try:
        for n_add, n_del in ((10, 10), (3, 17), (25, 0), (0, 6)):
            add, remove = _random_delta(version, rng, n_add, n_del)
            env = eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
            jax.block_until_ready(env.values)
            version.apply(add, remove)
    finally:
        jax.monitoring.unregister_event_duration_listener(seen)
    assert compiled == []
    ref = reference_pagerank(version.graph())
    assert float(jnp.max(jnp.abs(env.result.pi - ref))) < TOL


def test_overflowing_delta_lays_out_once():
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense", update_xi=1e-12))
    version, rng = Version(g), np.random.default_rng(7)
    add, remove = _random_delta(version, rng, 5, 5)
    assert eng.run(DeltaQuery(add=tuple(add),
                              remove=tuple(remove))).result.relayouts == 0
    version.apply(add, remove)
    slack = eng.describe(include_plan=False)["delta_capacity"]
    add, remove = _random_delta(version, rng, slack + 1, 3)
    env = eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
    version.apply(add, remove)
    assert env.result.relayouts == 1 and eng.relayouts == 1
    ref = reference_pagerank(version.graph())
    assert float(jnp.max(jnp.abs(env.result.pi - ref))) < TOL
    # laid out again from the new edge set, with slack for its size
    assert eng.describe(include_plan=False)["delta_capacity"] > slack
    add, remove = _random_delta(version, rng, 4, 4)
    env = eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
    version.apply(add, remove)
    assert env.result.relayouts == 0 and eng.relayouts == 1
    _check_layout(eng, version)


def test_invalid_delta_changes_nothing():
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    version = Version(g)
    s, d = int(g.src[0]), int(g.dst[0])
    add = [_absent(version, 1)]
    eng.run(DeltaQuery(add=tuple(add)))
    version.apply(add, [])
    before = (eng.graph_version, eng.describe(include_plan=False)["m"],
              eng.core_edges)
    with pytest.raises(ValueError, match="absent"):
        eng.run(DeltaQuery(remove=((s, d), _absent(version, s))))
    with pytest.raises(ValueError, match="existing"):
        eng.run(DeltaQuery(add=(add[0],), remove=((s, d),)))
    assert (eng.graph_version, eng.describe(include_plan=False)["m"],
            eng.core_edges) == before
    assert np.array_equal(np.sort(_keys(eng.graph)), version.keys)


@pytest.mark.parametrize("rows", [0, 3], ids=["push", "push_batch"])
@pytest.mark.parametrize("off_core", [False, True], ids=["core", "full"])
def test_padded_push_equals_plain_push_bit_for_bit(rows, off_core):
    """A layout taken on a graph that never changed pushes what the plain
    layout pushes, in every bit, on either edge list."""
    g = _graph()
    plain, live = _dense_runs(g), LiveLayout(g).ctx
    assert live.src.shape[0] > plain.src.shape[0]  # the slack is there
    rng = np.random.default_rng(8)
    shape = (rows, g.n) if rows else (g.n,)
    w = rng.standard_normal(shape) * (rng.random(shape) < 0.5)
    if not off_core:
        w = np.where(np.asarray(plain.in_core), w, 0.0)
    w = jnp.asarray(w)
    push = DENSE.push_batch if rows else DENSE.push
    y_plain, core_plain = DENSE.push_counted(g, plain, w[0] if rows else w)
    y_live, core_live = DENSE.push_counted(g, live, w[0] if rows else w)
    assert bool(core_plain) == bool(core_live) == (not off_core)
    assert np.array_equal(np.asarray(push(g, plain, w)),
                          np.asarray(push(g, live, w)))
    assert np.array_equal(np.asarray(y_plain), np.asarray(y_live))


def test_push_after_deltas_matches_numpy():
    """After deltas, both edge lists of the live layout sum what a numpy
    scatter over the live edges gives; the core list for input on S."""
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    version, rng = Version(g), np.random.default_rng(9)
    for add, remove in [*_special_deltas(g)][:2]:
        eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
        version.apply(add, remove)
    add, remove = _random_delta(version, rng, 30, 30)
    eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
    version.apply(add, remove)
    n, keys = version.n, version.keys
    for on_s in (False, True):
        w = rng.standard_normal(n)
        if on_s:
            w = np.where(eng._live.in_s, w, 0.0)
        y, core = DENSE.push_counted(eng._live.degrees, eng._ctx,
                                     jnp.asarray(w))
        want = np.zeros(n)
        np.add.at(want, keys // n, w[keys % n])
        assert bool(core) == on_s
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-12,
                                   atol=1e-12)


def test_rank_query_after_deltas_solves_the_new_graph():
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    version, rng = Version(g), np.random.default_rng(10)
    add, remove = _random_delta(version, rng, 8, 8)
    eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
    version.apply(add, remove)
    assert eng._graph is None  # the delta itself never built the graph
    r = eng.run(RankQuery(ItaConfig(xi=1e-12))).result
    ref = reference_pagerank(version.graph())
    assert float(jnp.max(jnp.abs(r.pi - ref))) < TOL
    assert r.core_rounds is not None and r.core_rounds > 0


def test_static_engine_keeps_the_plain_layout():
    """An engine that never sees a delta takes no slack."""
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    eng.run(RankQuery(ItaConfig(xi=1e-10)))
    assert eng._ctx.carry is None and eng._ctx.src.shape == (g.m,)
    assert eng.describe(include_plan=False)["delta_capacity"] is None


def test_graph_whose_core_holds_every_edge():
    """On a symmetric graph the core list is the whole list: the layout
    keeps one edge list, and every added edge goes to its insert region."""
    g0 = _graph()
    src, dst = np.asarray(g0.src), np.asarray(g0.dst)
    g = graph_from_edges(np.concatenate([src, dst]),
                         np.concatenate([dst, src]), g0.n)
    eng = PageRankEngine(g, EnginePlan(step_impl="dense", update_xi=1e-12))
    assert eng.core_edges is None
    version, rng = Version(g), np.random.default_rng(11)
    for _ in range(2):
        add, remove = _random_delta(version, rng, 9, 7)
        env = eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
        version.apply(add, remove)
        ref = reference_pagerank(version.graph())
        assert float(jnp.max(jnp.abs(env.result.pi - ref))) < TOL
        assert env.result.core_edges is None and eng._ctx.core is None


@pytest.mark.parametrize("rows", [0, 3], ids=["push", "push_batch"])
def test_lists_push_alike_after_deltas(rows):
    """After deltas that add edges into one destination from S and from
    outside it, and move a joined vertex's edges, the full list and the
    core list still push the same sums in every bit for input zero off S,
    so the push's choice of list never shows in its result."""
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    version, rng = Version(g), np.random.default_rng(12)
    add, _, _ = _mixed_delta(g)
    eng.run(DeltaQuery(add=tuple(add)))
    version.apply(add, [])
    for _ in range(2):
        add, remove = _random_delta(version, rng, 15, 10)
        eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove)))
        version.apply(add, remove)
    live, ctx = eng._live, eng._ctx
    assert live.moved.size > 0  # a joined vertex's edges moved
    shape = (rows, g.n) if rows else (g.n,)
    w = np.where(live.in_s, rng.standard_normal(shape), 0.0)
    full, core = _walk(jnp.asarray(w), ctx), _walk(jnp.asarray(w), ctx.core)
    assert np.array_equal(np.asarray(full), np.asarray(core))
    want = np.zeros(shape)
    n, keys = version.n, version.keys
    np.add.at(want, (..., keys // n), w[..., keys % n])
    np.testing.assert_allclose(np.asarray(core), want, rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("served", ["batch", "cache"])
def test_topk_rows_do_not_depend_on_the_batch_after_a_delta(served):
    """A PPR row seeded in S is the same in every bit whether its batch
    also holds a seed outside S (which walks the full list in its first
    rounds) or not: in one batch, or as a cache hit filled by such a
    batch, against a fresh solve of the row alone."""
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense", cache=True))
    add, seed_in, seed_out = _mixed_delta(g)
    eng.run(DeltaQuery(add=tuple(add)))
    assert eng._live.in_s[seed_in] and not eng._live.in_s[seed_out]
    alone = eng.run(TopKQuery(sources=[seed_in], no_cache=True)).values
    mixed = eng.run(TopKQuery(sources=[seed_in, seed_out], no_cache=True))
    rb = mixed.result.result
    assert rb.core_rounds <= rb.iterations - 2  # two on the full list
    row = mixed.values
    if served == "cache":  # filled by the mixed batch, then a hit alone
        eng.run(TopKQuery(sources=[seed_in, seed_out]))
        row = eng.run(TopKQuery(sources=[seed_in])).values
        assert eng.result_cache.stats()["hits"] == 1
    for got, want in zip(row, alone):
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
