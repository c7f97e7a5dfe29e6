"""PPR served on the live layout while edge writes land.

  * **layout-only writes** — ``DeltaQuery(rank=False)`` edits the dense
    push's live layout and acknowledges the new version with no re-rank;
    a ``TopKQuery`` after any chain of them (some of which change m)
    answers on the new graph, builds no ``Graph``, and compiles nothing
    after the first live micro-batch;
  * **the write lane** — ``PPRService.serve(reads, writes=...)`` applies
    every due write before each dispatch, stamps each answer with the
    version its micro-batch ran on, and reports every write it applied.

Each answer is held to ``reference_pagerank`` with the seed's one-hot
personalization on a graph built here from numpy set operations.
Tolerance: ITA stops once every vertex holds less than ξ = 1e-12 of
information, out of a total of n, so what it has not pushed moves a
normalized row by at most ξ / (1 − c) ≈ 7e-12 in l1; the tests allow
1e-10 per entry, and the reference (power iteration to an l2 step of
1e-14) is closer still.
"""
import copy
import glob
import os

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BatchConfig, DeltaQuery, EnginePlan, ItaConfig,
                        PageRankEngine, RankQuery, TopKQuery,
                        reference_pagerank)
from repro.graph import graph_from_edges, web_graph
from repro.serve import (AdmissionPolicy, DegradeLevel, DegradePolicy,
                         OpenLoopWorkload, PPRService, ServiceConfig,
                         VirtualClock, Write)
from test_live_delta import Version, _graph, _random_delta, _special_deltas

TOL = 1e-10
XI = 1e-12
CFG = BatchConfig(xi=XI)
COMPILE = "/jax/core/compile/backend_compile_duration"
# (inserts, deletes) of the random deltas; three of them change m
SIZES = ((10, 10), (3, 17), (25, 0), (0, 6))


def _seeds(g):
    """A seed in the referenced core, one outside it, and a dangling one."""
    levels, out_deg = g.reference_levels, np.asarray(g.out_deg)
    core = np.flatnonzero((levels < 0) & (out_deg > 0))
    off = np.flatnonzero((levels >= 0) & (out_deg > 0))
    return np.asarray([core[0], off[0], np.flatnonzero(out_deg == 0)[0],
                       core[-1]])


def _ppr_reference(version, seeds):
    ref_g = version.graph()
    return np.stack([np.asarray(reference_pagerank(
        ref_g, p=jax.nn.one_hot(s, version.n, dtype=jnp.float64)))
        for s in seeds])


def _deltas(g, version, rng):
    """The special deltas, which move vertices between the paper's
    classes, then random ones of :data:`SIZES`; each valid on the version
    the ones before it make, which ``version`` follows."""
    for add, remove in [*_special_deltas(g), *SIZES]:
        if isinstance(add, int):
            add, remove = _random_delta(version, rng, add, remove)
        version.apply(add, remove)
        yield tuple(add), tuple(remove)


@pytest.mark.parametrize("path", ["batched-while-loop", "donated-batch"])
def test_topk_after_layout_only_deltas_matches_the_oracle(path):
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    eng._donate = path == "donated-batch"  # the accelerators' path
    version, rng = Version(g), np.random.default_rng(21)
    seeds = _seeds(g)
    ms = set()
    for i, (add, remove) in enumerate(_deltas(g, version, rng)):
        env = eng.run(DeltaQuery(add=add, remove=remove, rank=False))
        assert env.plan.path == "layout" and env.values is None
        assert env.result.graph_version == eng.graph_version == i + 1
        assert env.result.relayouts == 0 and eng.live
        assert not eng.describe(include_plan=False)["has_residual_state"]
        top = eng.run(TopKQuery(sources=seeds, k=5, cfg=CFG))
        assert top.plan.path == path
        pi = np.asarray(top.result.result.pi)
        ref = _ppr_reference(version, seeds)
        assert np.max(np.abs(pi - ref)) < TOL, i
        idx, scores = (np.asarray(x) for x in top.values)
        np.testing.assert_allclose(
            scores, np.take_along_axis(ref, idx, 1), rtol=0, atol=TOL)
        assert eng._graph is None  # no Graph built, by delta or query
        ms.add(eng.m)
    assert len(ms) >= 4 and eng.m == version.keys.size


def test_live_micro_batches_compile_nothing_after_the_first():
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl="dense"))
    version, rng = Version(g), np.random.default_rng(22)
    seeds = _seeds(g)
    add, remove = _random_delta(version, rng, 10, 10)
    eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove), rank=False))
    version.apply(add, remove)
    jax.block_until_ready(
        eng.run(TopKQuery(sources=seeds, k=5, cfg=CFG)).values)
    compiled, ms = [], set()

    def seen(name, *a, **kw):
        if name == COMPILE:
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(seen)
    try:
        for n_add, n_del in SIZES:
            add, remove = _random_delta(version, rng, n_add, n_del)
            eng.run(DeltaQuery(add=tuple(add), remove=tuple(remove),
                               rank=False))
            version.apply(add, remove)
            top = eng.run(TopKQuery(sources=seeds, k=5, cfg=CFG))
            jax.block_until_ready(top.values)
            ms.add(eng.m)
    finally:
        jax.monitoring.unregister_event_duration_listener(seen)
    assert compiled == [] and len(ms) == len(SIZES)
    ref = _ppr_reference(version, seeds)
    assert np.max(np.abs(np.asarray(top.result.result.pi) - ref)) < TOL


@pytest.mark.parametrize("step_impl", ["dense", "frontier"],
                         ids=["live", "reprepared"])
def test_ranked_delta_after_a_layout_only_one_solves_again(step_impl):
    """A layout-only delta drops the ranking's residual state; the next
    ranked delta solves it from scratch on the graph as it then is."""
    g = _graph()
    eng = PageRankEngine(g, EnginePlan(step_impl=step_impl,
                                       update_xi=1e-12))
    version, rng = Version(g), np.random.default_rng(23)
    deltas = [_random_delta(version, rng, 8, 8)]
    version.apply(*deltas[0])
    for _ in range(2):
        deltas.append(_random_delta(version, rng, 6, 9))
        version.apply(*deltas[-1])
    (a0, r0), (a1, r1), (a2, r2) = deltas
    eng.run(DeltaQuery(add=tuple(a0), remove=tuple(r0)))
    assert eng.describe(include_plan=False)["has_residual_state"]
    env = eng.run(DeltaQuery(add=tuple(a1), remove=tuple(r1), rank=False))
    assert env.values is None and env.result.graph_version == 2
    assert not eng.describe(include_plan=False)["has_residual_state"]
    q = DeltaQuery(add=tuple(a2), remove=tuple(r2))
    assert "cold start" in eng.plan(q).explain()
    env = eng.run(q)
    ref = reference_pagerank(version.graph())
    assert float(jnp.max(jnp.abs(env.result.pi - ref))) < TOL
    assert env.result.graph_version == eng.graph_version == 3
    assert eng.live == (step_impl == "dense")
    # the engine's own solve agrees
    r = eng.run(RankQuery(ItaConfig(xi=1e-12))).result
    assert float(jnp.max(jnp.abs(r.pi - ref))) < TOL


def test_live_engine_plans_topk_without_the_graph_and_rank_with_it():
    """After a delta on the live layout a top-k query is planned without
    building the graph; a global ranking, which runs on it, is planned
    with it, so an undirected graph keeps the undirected-schedule
    discount."""
    g = _graph()
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    sym = graph_from_edges(np.concatenate([src, dst]),
                           np.concatenate([dst, src]), g.n)
    eng = PageRankEngine(sym, EnginePlan(step_impl="dense"))
    pairs = {(int(a), int(b)) for a, b in zip(src, dst)}
    pairs |= {(b, a) for a, b in pairs}
    u = 0
    v = next(w for w in range(1, g.n) if (u, w) not in pairs)
    eng.run(DeltaQuery(add=((u, v), (v, u)), rank=False))
    assert eng.live and eng._graph is None
    eng.plan(TopKQuery(sources=np.asarray([u]), k=3, cfg=CFG))
    assert eng._graph is None
    assert "graph is undirected" in eng.plan(RankQuery(ItaConfig())).explain()


# --------------------------------------------------------------------- #
# the write lane, on a virtual clock
# --------------------------------------------------------------------- #
B = 4


@pytest.fixture(scope="module")
def small():
    return web_graph(400, 2400, dangling_frac=0.15, seed=3)


def _service(engine, **kw):
    """Modelled time with a fixed calibration: each micro-batch takes the
    same virtual time, a write none."""
    cfg = ServiceConfig(batch_size=B, k=5, queue_cap=64, cfg=CFG,
                        time_source="model", seconds_per_unit=1e-9,
                        base_s=0.5, admission=AdmissionPolicy(rate_qps=None),
                        **kw)
    return PPRService(engine, cfg, clock=VirtualClock())


def _reads(g, n_queries=6 * B):
    return OpenLoopWorkload(g, qps=8.0, n_queries=n_queries, seed=13,
                            deadline_s=1e6, k=5)


def _writes(g, times, seed=24):
    """One valid delta per arrival time, and the versions they make."""
    version, rng = Version(g), np.random.default_rng(seed)
    writes, versions = [], [copy.deepcopy(version)]
    for t in times:
        add, remove = _random_delta(version, rng, 7, 5)
        version.apply(add, remove)
        writes.append(Write(t_arrival=t, add=tuple(add),
                            remove=tuple(remove)))
        versions.append(copy.deepcopy(version))
    return writes, versions


def _batches(rep):
    """The served requests of each micro-batch, in dispatch order."""
    served, out = iter(rep.served), []
    for _, n_real, _ in rep.batches:
        out.append([next(served) for _ in range(n_real)])
    return out


def test_write_lane_applies_due_writes_before_each_dispatch(small):
    eng = PageRankEngine(small, EnginePlan(step_impl="dense"))
    times = (0.0, 0.1, 0.9, 1.3, 1.35, 2.2)
    writes, versions = _writes(small, times)
    rep = _service(eng).serve(_reads(small), writes=reversed(writes))
    # every write acknowledged, in arrival order, one version each
    assert [a.t_arrival for a in rep.writes] == list(times)
    assert [a.graph_version for a in rep.writes] == [1, 2, 3, 4, 5, 6]
    assert all(a.relayouts == 0 and a.t_arrival <= a.t_start <= a.t_ack
               for a in rep.writes)
    batches = _batches(rep)
    assert len(batches) >= 5
    checked = set()
    for batch in batches:
        t = batch[0].t_dispatch
        # one version per micro-batch, the newest acknowledged at dispatch,
        # and every write due by then is acknowledged
        (v,) = {s.graph_version for s in batch}
        assert v == max([0] + [a.graph_version for a in rep.writes
                               if a.t_ack <= t])
        assert v == sum(w.t_arrival <= t for w in writes)
        checked.add(v)
        ref = _ppr_reference(versions[v], [s.req.seed for s in batch])
        for s, row in zip(batch, ref):
            np.testing.assert_allclose(s.scores, row[s.indices], rtol=0,
                                       atol=TOL)
    assert len(checked) >= 4  # the answers followed the writes


def test_writes_due_after_the_reads_drain_stay_unapplied(small):
    eng = PageRankEngine(small, EnginePlan(step_impl="dense"))
    writes, _ = _writes(small, (0.2, 1e6))
    rep = _service(eng).serve(_reads(small, 2 * B), writes=writes)
    assert [a.graph_version for a in rep.writes] == [1]
    assert eng.graph_version == 1


def test_no_writes_answers_bit_identical(small):
    eng = PageRankEngine(small, EnginePlan(step_impl="dense"))
    today = _service(eng).serve(_reads(small))
    lane = _service(eng).serve(_reads(small), writes=[])
    assert lane.writes == [] and today.writes == []
    assert len(today.served) == len(lane.served) == 6 * B
    for a, b in zip(today.served, lane.served):
        assert a.req.seed == b.req.seed and a.graph_version == 0
        assert np.array_equal(a.indices, b.indices)
        assert np.array_equal(a.scores, b.scores)
        assert b.graph_version == 0


def test_rung_with_its_own_engine_is_refused_where_writes_land(small):
    ladder = DegradePolicy(levels=(DegradeLevel(name="full"),
                                   DegradeLevel(name="cheap", xi_scale=1e2,
                                                step_impl="frontier")))
    eng = PageRankEngine(small, EnginePlan(step_impl="dense"))
    writes, _ = _writes(small, (0.0,))
    service = _service(eng, degrade=ladder)  # not live yet: no writes seen
    with pytest.raises(ValueError, match="never reach"):
        service.serve(_reads(small), writes=writes)
    eng.run(DeltaQuery(add=writes[0].add, remove=writes[0].remove,
                       rank=False))
    with pytest.raises(ValueError, match="never reach"):
        _service(eng, degrade=ladder)
    # a rung that only loosens xi serves the engine the writes reach
    _service(eng, degrade=DegradePolicy())


def test_write_span_holds_the_layout_edit(small, tmp_path):
    from jax.profiler import ProfileData

    eng = PageRankEngine(small, EnginePlan(step_impl="dense"))
    writes, _ = _writes(small, (0.0, 0.6))
    service = _service(eng)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = service.serve(_reads(small, 2 * B), writes=writes)
    finally:
        jax.profiler.stop_trace()
    assert len(rep.writes) == 2
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for pl in ProfileData.from_file(path).planes
              if pl.name == "/host:CPU" for ln in pl.lines
              for ev in ln.events]
    write = [(s, e) for n, s, e in events if n == "serve.write"]
    apply = [(s, e) for n, s, e in events if n == "engine.delta.apply"]
    assert len(write) == len(apply) == 2
    assert all(any(a <= s and e <= b for a, b in write) for s, e in apply)
