"""Serving tier (``repro.serve``): every policy, on a virtual clock.

The contracts under test, per docs/SERVING.md:

  * **metrics** — ``latency_summary``, the percentiles the CLI and
    ``ServiceReport.summary`` report;
  * **workload determinism** — ``zipf_seeds`` requires an explicit RNG,
    ties in the in-degree ranking break by vertex id (stable sort), and
    identical seeds give identical streams;
  * **bounded queue** — depth NEVER exceeds capacity (property test over
    random offer/drain interleavings), overflow is a typed
    :class:`Overload`, never an exception or a silent drop;
  * **deadline batcher** — dispatches on full batch or exactly when the
    head's deadline minus predicted batch cost says go;
  * **hysteretic degrade** — steps down only after sustained overload,
    up only after sustained calm; a square-wave depth signal does NOT
    flap the level (the dead band + patience counters);
  * **the service loop** — on a virtual clock with modeled batch cost
    the whole tier is deterministic; answers served through it are
    bit-identical to direct ``engine.run`` when no degradation is
    active; under overload it sheds typed rejections, keeps the queue
    bounded, degrades (tagging envelopes ``degraded=True``) and
    recovers.

Everything here runs on :class:`VirtualClock` — no wall-clock sleeps.
"""

import dataclasses

import numpy as np
import pytest
from _propcheck import given, settings, strategies

from repro.core import (
    BatchConfig,
    CachePolicy,
    EnginePlan,
    PageRankEngine,
    TopKQuery,
)
from repro.graph import web_graph
from repro.serve import (
    AdmissionPolicy,
    BoundedQueue,
    ClosedLoopWorkload,
    CostModel,
    DeadlineBatcher,
    DegradeLevel,
    DegradePolicy,
    OpenLoopWorkload,
    Overload,
    PPRService,
    ServiceConfig,
    TokenBucket,
    VirtualClock,
    latency_summary,
    zipf_seeds,
)
from repro.serve.service import EngineExecutor
from repro.serve.workload import Request, zipf_rank

CFG = BatchConfig(batch_method="ita", xi=1e-6)
K = 5


@pytest.fixture(scope="module")
def g():
    return web_graph(400, 2400, dangling_frac=0.15, seed=3)


@pytest.fixture(scope="module")
def engine(g):
    return PageRankEngine(g, EnginePlan(step_impl="dense"))


def _svc_cfg(engine, **kw):
    """Deterministic simulation config: modeled time, fixed calibration."""
    base = dict(batch_size=8, k=K, cfg=CFG, time_source="model", seconds_per_unit=1e-9)
    base.update(kw)
    return ServiceConfig(**base)


# --------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------- #
class TestMetrics:
    def test_latency_summary_keys(self):
        s = latency_summary(np.array([1.0, 2.0, 3.0, 4.0]))
        assert s["count"] == 4
        assert s["p50_ms"] == pytest.approx(2.5)
        assert s["max_ms"] == 4.0
        assert set(s) >= {"count", "p50_ms", "p90_ms", "p99_ms", "mean_ms", "max_ms"}
        assert latency_summary(np.array([]))["count"] == 0


# --------------------------------------------------------------------- #
# workload determinism (satellite 2)
# --------------------------------------------------------------------- #
class TestZipfSeeds:
    def test_requires_rng(self, g):
        with pytest.raises(TypeError):
            zipf_seeds(g, 8, 1.1, None)

    def test_same_seed_same_stream(self, g):
        a = zipf_seeds(g, 64, 1.1, 42)
        b = zipf_seeds(g, 64, 1.1, np.random.default_rng(42))
        assert np.array_equal(a, b)
        assert not np.array_equal(a, zipf_seeds(g, 64, 1.1, 43))

    def test_uniform_at_zero_alpha(self, g):
        s = zipf_seeds(g, 256, 0.0, 0)
        assert s.min() >= 0 and s.max() < g.n

    def test_tie_stable_ranks(self):
        # all in-degrees equal -> rank must be the identity (id-stable),
        # not whatever the platform's unstable sort happened to emit
        stub = type("G", (), {"in_deg": np.ones(16), "n": 16})()
        assert np.array_equal(zipf_rank(stub), np.arange(16))
        # two tie groups: high-degree ids first (each in id order)
        deg = np.array([1, 2, 1, 2])
        stub2 = type("G", (), {"in_deg": deg, "n": 4})()
        assert np.array_equal(zipf_rank(stub2), np.array([1, 3, 0, 2]))

    def test_open_loop_deterministic(self, g):
        w1 = OpenLoopWorkload(g, qps=100.0, n_queries=32, seed=5)
        w2 = OpenLoopWorkload(g, qps=100.0, n_queries=32, seed=5)
        assert [r.t_arrival for r in w1.requests] == [r.t_arrival for r in w2.requests]
        assert [r.seed for r in w1.requests] == [r.seed for r in w2.requests]


# --------------------------------------------------------------------- #
# token bucket
# --------------------------------------------------------------------- #
class TestTokenBucket:
    def test_burst_then_throttle_then_refill(self):
        b = TokenBucket(rate=10.0, burst=3.0)
        assert all(b.try_acquire(0.0) for _ in range(3))
        assert not b.try_acquire(0.0)
        assert b.retry_after(0.0) == pytest.approx(0.1)
        # 0.25 s later: 2.5 tokens accrued
        assert b.try_acquire(0.25) and b.try_acquire(0.25)
        assert not b.try_acquire(0.25)
        # burst caps accumulation
        assert b.tokens(100.0) == pytest.approx(3.0)

    def test_validates(self):
        with pytest.raises(ValueError):
            TokenBucket(rate=0.0, burst=4)
        with pytest.raises(ValueError):
            TokenBucket(rate=1.0, burst=0.5)


# --------------------------------------------------------------------- #
# bounded queue — the property test (satellite 3)
# --------------------------------------------------------------------- #
def _req(i, t=0.0):
    return Request(req_id=i, seed=i % 7, t_arrival=t, deadline=t + 1.0)


class TestBoundedQueue:
    @settings(max_examples=30, deadline=None)
    @given(
        cap=strategies.integers(1, 24),
        n_ops=strategies.integers(1, 120),
        drain=strategies.integers(1, 12),
        period=strategies.integers(2, 9),
    )
    def test_depth_never_exceeds_cap(self, cap, n_ops, drain, period):
        # interleave offers with periodic pops; whatever the pattern, the
        # bound holds, overflow is typed, and conservation balances
        q = BoundedQueue(cap)
        popped, rejected = [], []
        for i in range(n_ops):
            ov = q.offer(_req(i, t=float(i)), now=float(i))
            if ov is not None:
                assert isinstance(ov, Overload)
                assert ov.reason == "queue_full"
                assert ov.depth == cap
                rejected.append(ov)
            assert q.depth <= cap
            if i % period == period - 1:
                popped.extend(q.pop_batch(drain))
        assert q.depth <= cap
        assert q.enqueued == n_ops - len(rejected)
        assert q.enqueued == len(popped) + q.depth
        assert q.rejected == len(rejected)
        assert q.max_depth <= cap
        # FIFO: popped req_ids strictly increase
        ids = [r.req_id for r in popped]
        assert ids == sorted(ids)

    def test_oldest_age(self):
        q = BoundedQueue(4)
        assert q.oldest() is None and q.oldest_age(5.0) == 0.0
        q.offer(_req(0, t=1.0), now=1.0)
        q.offer(_req(1, t=2.0), now=2.0)
        assert q.oldest().req_id == 0
        assert q.oldest_age(3.5) == pytest.approx(2.5)

    def test_validates(self):
        with pytest.raises(ValueError):
            BoundedQueue(0)


# --------------------------------------------------------------------- #
# deadline batcher
# --------------------------------------------------------------------- #
class TestDeadlineBatcher:
    def _batcher(self, B=4, spu=1.0, units=1.0, safety=0.0):
        model = CostModel(seconds_per_unit=spu, ewma=0.0)
        return DeadlineBatcher(B, model, batch_cost_units=units, safety_s=safety)

    def test_full_batch_dispatches(self):
        b, q = self._batcher(B=2, spu=1e-3), BoundedQueue(8)
        q.offer(_req(0), 0.0)
        assert b.should_dispatch(q, 0.0) is None
        q.offer(_req(1), 0.0)
        assert b.should_dispatch(q, 0.0) == "full"

    def test_deadline_trigger_is_exact(self):
        # head deadline t=5, predicted batch 1 s -> trigger at exactly 4
        b, q = self._batcher(B=8), BoundedQueue(8)
        q.offer(Request(req_id=0, seed=0, t_arrival=0.0, deadline=5.0), 0.0)
        assert b.trigger_time(q) == pytest.approx(4.0)
        assert b.should_dispatch(q, 3.999) is None
        assert b.should_dispatch(q, 4.0) == "deadline"

    def test_safety_margin_and_empty_queue(self):
        b, q = self._batcher(B=8, safety=0.5), BoundedQueue(8)
        assert b.trigger_time(q) == float("inf")
        assert b.should_dispatch(q, 0.0, flush=True) is None  # empty
        q.offer(Request(req_id=0, seed=0, t_arrival=0.0, deadline=5.0), 0.0)
        assert b.trigger_time(q) == pytest.approx(3.5)

    def test_flush_drains_partial(self):
        b, q = self._batcher(B=8), BoundedQueue(8)
        q.offer(Request(req_id=0, seed=0, t_arrival=0.0, deadline=99.0), 0.0)
        assert b.should_dispatch(q, 0.0) is None
        assert b.should_dispatch(q, 0.0, flush=True) == "flush"
        assert b.stats()["flush"] == 1

    def test_cost_model_ewma_and_validation(self):
        m = CostModel(seconds_per_unit=1.0, ewma=0.5)
        m.observe(1.0, 3.0)  # spu sample 3 -> 0.5*1 + 0.5*3 = 2
        assert m.seconds_per_unit == pytest.approx(2.0)
        m2 = CostModel(seconds_per_unit=1.0, ewma=0.0)
        m2.observe(1.0, 100.0)  # frozen model ignores samples
        assert m2.seconds_per_unit == 1.0
        with pytest.raises(ValueError):
            CostModel(seconds_per_unit=0.0)
        with pytest.raises(ValueError):
            CostModel(seconds_per_unit=1.0, ewma=1.5)


# --------------------------------------------------------------------- #
# hysteretic degrade (satellite 3: no flapping on a square wave)
# --------------------------------------------------------------------- #
class TestDegradePolicy:
    def test_steps_down_after_patience_only(self):
        p = DegradePolicy(hi=10, lo=2, patience_down=3, patience_up=2)
        assert [p.observe(20), p.observe(20)] == [0, 0]
        assert p.observe(20) == 1  # third consecutive over -> down
        # recovery needs patience_up consecutive under
        assert p.observe(1) == 1
        assert p.observe(1) == 0
        assert [t[1:] for t in p.transitions] == [(0, 1), (1, 0)]

    def test_square_wave_never_flaps(self):
        # load square wave: depth alternates above hi and below lo every
        # observation — each flip resets the other streak, so a policy
        # with patience >= 2 must hold level 0 forever
        p = DegradePolicy(hi=10, lo=2, patience_down=2, patience_up=2)
        wave = [20, 1] * 50
        levels = [p.observe(d) for d in wave]
        assert levels == [0] * len(wave)
        assert p.transitions == []

    def test_dead_band_resets_streaks(self):
        p = DegradePolicy(hi=10, lo=2, patience_down=2, patience_up=2)
        p.observe(20)
        p.observe(5)  # dead band: resets the over-streak
        assert p.observe(20) == 0  # needs 2 consecutive again
        assert p.observe(20) == 1

    def test_ladder_bounds(self):
        p = DegradePolicy(hi=4, lo=1, patience_down=1, patience_up=1)
        n_levels = len(p.levels)
        for _ in range(n_levels + 3):  # saturates at the last rung
            lvl = p.observe(99)
        assert lvl == n_levels - 1
        for _ in range(n_levels + 3):  # and back to full fidelity
            lvl = p.observe(0)
        assert lvl == 0

    def test_validates(self):
        with pytest.raises(ValueError):
            DegradePolicy(hi=4, lo=4)
        with pytest.raises(ValueError):
            DegradePolicy(levels=[DegradeLevel(name="x", xi_scale=10.0)])
        with pytest.raises(ValueError):
            DegradeLevel(name="tighter", xi_scale=0.1)
        with pytest.raises(ValueError):
            DegradePolicy(patience_down=0)


# --------------------------------------------------------------------- #
# cache-aware admission: the non-counting peek
# --------------------------------------------------------------------- #
class TestCachePeek:
    def test_peek_counts_nothing_and_tracks_freshness(self, g):
        eng = PageRankEngine(g, EnginePlan(step_impl="dense", cache=CachePolicy()))
        cache = eng.result_cache
        assert cache.peek(3, CFG, eng.graph_version) is False
        eng.run(TopKQuery(sources=np.arange(8), k=K, cfg=CFG))
        before = cache.stats()
        assert cache.peek(3, CFG, eng.graph_version) is True
        assert cache.peek(399, CFG, eng.graph_version) is False
        # a different static config is a different entry
        other = dataclasses.replace(CFG, xi=CFG.xi * 10)
        assert cache.peek(3, other, eng.graph_version) is False
        # probing moved no counters (the whole point of peek)
        assert cache.stats() == before
        # stale after a graph delta: peek refuses (revalidation costs
        # device work, so the request must queue like a miss)
        assert cache.peek(3, CFG, eng.graph_version + 1) is False


# --------------------------------------------------------------------- #
# the service loop on a virtual clock (tentpole integration)
# --------------------------------------------------------------------- #
class TestService:
    def test_bit_identical_to_direct_engine_run(self, g, engine):
        svc = PPRService(engine, _svc_cfg(engine, queue_cap=64), clock=VirtualClock())
        wl = OpenLoopWorkload(g, qps=50.0, n_queries=24, seed=11, deadline_s=10.0, k=K)
        rep = svc.serve(wl)
        assert len(rep.served) == 24 and not rep.shed
        served = sorted(rep.served, key=lambda s: s.req.req_id)
        seeds = np.asarray([s.req.seed for s in served])
        direct = engine.run(TopKQuery(sources=seeds, k=K, cfg=CFG)).result
        for i, s in enumerate(served):
            assert np.array_equal(s.indices, np.asarray(direct.indices[i]))
            assert np.array_equal(s.scores, np.asarray(direct.scores[i]))
            assert not s.degraded

    def test_overload_sheds_typed_and_keeps_queue_bounded(self, g, engine):
        cap = 8
        cfg = _svc_cfg(engine, queue_cap=cap, seconds_per_unit=1e-6)
        svc = PPRService(engine, cfg, clock=VirtualClock())
        wl = OpenLoopWorkload(g, qps=1e6, n_queries=200, seed=1, deadline_s=0.01, k=K)
        rep = svc.serve(wl)
        assert rep.shed and all(isinstance(o, Overload) for o in rep.shed)
        assert {o.reason for o in rep.shed} == {"queue_full"}
        assert all(o.retry_after_s >= 0.0 for o in rep.shed)
        assert rep.queue_stats["max_depth"] <= cap
        s = rep.summary()
        assert s["served"] + s["shed"] == 200
        assert s["shed_frac"] > 0.0

    def test_throttle_sheds_typed(self, g, engine):
        pol = AdmissionPolicy(rate_qps=10.0, burst=4.0)
        cfg = _svc_cfg(engine, queue_cap=64, admission=pol)
        svc = PPRService(engine, cfg, clock=VirtualClock())
        wl = OpenLoopWorkload(g, qps=1e4, n_queries=64, seed=2, deadline_s=1.0, k=K)
        rep = svc.serve(wl)
        throttled = [o for o in rep.shed if o.reason == "throttled"]
        assert throttled and rep.admission_stats["throttled"] == len(throttled)
        assert all(o.retry_after_s > 0.0 for o in throttled)

    def test_degrade_engages_tags_and_recovers(self, g, engine):
        # two-phase open loop: sustained 5x overload, then calm — the
        # ladder must step down during the burst (tagging envelopes),
        # then return to full fidelity during the calm tail
        class Recording(EngineExecutor):
            def __init__(self):
                self.envs = []

            def __call__(self, *a, **kw):
                env = super().__call__(*a, **kw)
                self.envs.append(env)
                return env

        rec = Recording()
        units = float(engine.plan(TopKQuery(sources=np.zeros(8, np.int64), k=K, cfg=CFG)).cost)
        spu = 0.01 / units  # t_batch = 10 ms, capacity = 800 q/s
        policy = DegradePolicy(hi=12, lo=3, patience_down=2, patience_up=2)
        cfg = _svc_cfg(engine, queue_cap=32, seconds_per_unit=spu, degrade=policy)
        svc = PPRService(engine, cfg, clock=VirtualClock(), executor=rec)
        # ~400 arrivals in a 0.1 s burst, then ~200 more at a calm 100
        # q/s (if the burst covered all 600, no calm-phase dispatches
        # would ever be observed and recovery could not happen)
        wl = OpenLoopWorkload(
            g, qps=[(0.1, 4000.0), (10.0, 100.0)], n_queries=600, seed=4, deadline_s=0.2, k=K
        )
        rep = svc.serve(wl)
        s = rep.summary()
        assert s["degraded_frac"] > 0.0
        downs = [t for t in policy.transitions if t[2] > t[1]]
        ups = [t for t in policy.transitions if t[2] < t[1]]
        assert downs and ups, policy.transitions
        assert policy.level == 0  # recovered by the calm tail
        # every degraded answer is tagged, on the Served record AND the
        # engine envelope; full-fidelity ones are not
        assert any(e.degraded for e in rec.envs)
        assert any(not e.degraded for e in rec.envs)
        by_level = {x.degraded for x in rep.served}
        assert by_level == {True, False}
        # degraded levels only ever LOOSEN xi
        assert all(lv.xi_scale >= 1.0 for lv in policy.levels)

    def test_cache_bypass_skips_queue(self, g):
        eng = PageRankEngine(g, EnginePlan(step_impl="dense", cache=CachePolicy()))
        hot = np.arange(8)
        eng.run(TopKQuery(sources=hot, k=K, cfg=CFG))  # warm the cache
        svc = PPRService(eng, _svc_cfg(eng, queue_cap=64), clock=VirtualClock())
        wl = OpenLoopWorkload(g, qps=100.0, n_queries=32, seed=6, deadline_s=10.0, k=K)
        # force the stream onto the warmed seeds
        for r in wl.requests:
            r.seed = int(hot[r.req_id % len(hot)])
        rep = svc.serve(wl)
        assert rep.admission_stats["bypassed"] == 32
        assert all(x.cache_hit for x in rep.served)
        assert rep.queue_stats["enqueued"] == 0
        assert rep.summary()["cache_bypass_frac"] == 1.0
        # bypassed answers still match a direct run bit-for-bit
        direct = eng.run(TopKQuery(sources=hot, k=K, cfg=CFG)).result
        for x in rep.served:
            j = int(np.where(hot == x.req.seed)[0][0])
            assert np.array_equal(x.indices, np.asarray(direct.indices[j]))
            assert np.array_equal(x.scores, np.asarray(direct.scores[j]))

    def test_closed_loop_accounting(self, g, engine):
        svc = PPRService(engine, _svc_cfg(engine, queue_cap=32), clock=VirtualClock())
        wl = ClosedLoopWorkload(g, clients=8, n_queries=40, seed=7, deadline_s=10.0, k=K)
        rep = svc.serve(wl)
        assert len(rep.served) == 40 and not rep.shed
        assert wl.drained
        s = rep.summary()
        assert s["qps"] > 0 and s["latency"]["count"] == 40
        # per-request latency includes queue wait: at least the modeled
        # service time of the batch that answered it
        assert all(x.latency_s > 0 for x in rep.served)
        assert s["batches"] == len(rep.batches) == 5

    def test_virtual_clock_sim_is_deterministic(self, g, engine):
        def run_once():
            cfg = _svc_cfg(engine, queue_cap=16, seconds_per_unit=1e-5)
            svc = PPRService(engine, cfg, clock=VirtualClock())
            wl = OpenLoopWorkload(g, qps=5e4, n_queries=100, seed=9, deadline_s=0.05, k=K)
            rep = svc.serve(wl)
            s = rep.summary()
            return (
                s["served"],
                s["shed"],
                s["batches"],
                s["latency"]["p99_ms"],
                s["deadline_miss_frac"],
            )

        assert run_once() == run_once()

    def test_service_config_validates(self, engine):
        with pytest.raises(ValueError):
            ServiceConfig(time_source="wishful")
        with pytest.raises(ValueError):
            ServiceConfig(batch_size=0)
