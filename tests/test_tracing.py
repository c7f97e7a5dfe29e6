"""The program's own spans, scopes and counters.

  * **device scopes** — the ITA round's HLO names its stages with
    ``jax.named_scope``: ``push/{gather,scan,readout}`` under
    ``ita_round/push``, in both edge lists' branches of the dense push, in
    the single-vector loop and in the batched loop alike;
  * **the batched edge counter** — ``BatchSolverResult.ops`` is Formula 15
    summed over the rows: equal to the sum of each row's own ``ita`` solve,
    on the plain batched loop and on the engine's donated path;
  * **queue wait** — ``Served.t_dispatch`` splits each request's latency
    into its wait in the queue and its micro-batch's service;
  * **host spans** — a profiler trace of the serving loop holds the
    program's ``TraceAnnotation`` spans, nested as the layers call.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BatchConfig, EnginePlan, PageRankEngine, ita, ita_batch
from repro.core.backends import _ita_loop_jit, get_step_impl
from repro.core.batch import (
    _ita_batch_loop,
    one_hot_personalizations,
    power_method_batch,
)
from repro.graph import web_graph
from repro.serve import (
    AdmissionPolicy,
    ClosedLoopWorkload,
    PPRService,
    ServiceConfig,
    VirtualClock,
)
from repro.serve.service import NullExecutor

XI = 1e-8
STAGES = ("gather", "scan", "readout")


@pytest.fixture(scope="module")
def g():
    return web_graph(300, 2000, dangling_frac=0.15, seed=1)


@pytest.fixture(scope="module")
def seeds(g):
    """Five seed vertices that push: none of them dangling."""
    return np.flatnonzero(~np.asarray(g.dangling_mask))[[0, 7, 41, 123, 200]]


def _compiled_text(g, seeds, loop: str) -> str:
    backend = get_step_impl("dense")
    ctx = backend.prepare(g)
    assert ctx.core is not None  # the push chooses between two edge lists
    if loop == "rank":
        h0 = jnp.ones((g.n,), jnp.float64)
        lowered = _ita_loop_jit.lower(g, ctx, h0, jnp.zeros_like(h0), 0.85,
                                      XI, 100, backend, False)
    else:
        H0 = one_hot_personalizations(g, seeds) * g.n
        lowered = _ita_batch_loop.lower(g, ctx, H0, 0.85, XI, 100, backend)
    return lowered.compile().as_text()


@pytest.mark.parametrize("loop", ["rank", "batch"])
def test_push_stages_named_in_compiled_hlo(g, seeds, loop):
    names = set(re.findall(r'op_name="([^"]*)"',
                           _compiled_text(g, seeds, loop)))
    for stage in STAGES:
        # each stage as a profile reads it (bench/program_trace.py: the
        # scope's segments in a row), inside the loop's ITA round and push
        under = {name[:name.index(f"/push/{stage}/")] for name in names
                 if "/while/body/ita_round/push/" in name
                 and f"/push/{stage}/" in name}
        # once in the full list's branch, once in the core list's
        assert len(under) == 2, (stage, under)


@pytest.mark.parametrize("path", ["ita_batch", "donated"])
def test_batched_ops_is_sum_of_single_source_ops(g, seeds, path):
    P = one_hot_personalizations(g, seeds)
    if path == "ita_batch":
        res = ita_batch(g, P, xi=XI)
    else:
        engine = PageRankEngine(g, EnginePlan(step_impl="dense"))
        res = engine._solve_batch_donated(P, BatchConfig(xi=XI))
    singles = [ita(g, p=P[i], xi=XI) for i in range(len(seeds))]
    assert all(r.ops > 0 for r in singles)
    assert res.ops == sum(r.ops for r in singles)
    assert res.iterations == max(r.iterations for r in singles)


def test_batched_ops_none_where_not_counted(g, seeds):
    P = one_hot_personalizations(g, seeds[:2])
    assert power_method_batch(g, P, tol=1e-8).ops is None


def test_queue_wait_from_t_dispatch(g):
    """A closed loop of 2B clients on modelled time: the first micro-batch
    waits nothing, every later one waits exactly one batch's service."""
    B = 4
    engine = PageRankEngine(g, EnginePlan(step_impl="dense"))
    cfg = ServiceConfig(batch_size=B, k=5, queue_cap=64,
                        cfg=BatchConfig(xi=1e-6), time_source="model",
                        seconds_per_unit=1e-9, base_s=0.25,
                        admission=AdmissionPolicy(rate_qps=None))
    svc = PPRService(engine, cfg, clock=VirtualClock(),
                     executor=NullExecutor())
    wl = ClosedLoopWorkload(g, clients=2 * B, n_queries=4 * B, seed=3,
                            deadline_s=1e6)
    rep = svc.serve(wl)
    assert len(rep.served) == 4 * B and len(rep.batches) == 4
    service_s = rep.batches[0][0]
    assert service_s > 0.25
    starts = np.cumsum([0.0] + [b[0] for b in rep.batches[:-1]])
    for i, s in enumerate(rep.served):
        batch = i // B
        assert s.t_dispatch == starts[batch]
        wait = s.t_dispatch - s.req.t_arrival
        assert wait == pytest.approx(0.0 if batch == 0 else service_s,
                                     abs=1e-12)
        assert s.latency_s == pytest.approx(wait + (s.t_done - s.t_dispatch),
                                            abs=1e-12)


def _host_spans(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for pl in pd.planes if pl.name == "/host:CPU"
            for ln in pl.lines for ev in ln.events]


def test_serving_host_spans_nest(g, tmp_path):
    B = 4
    engine = PageRankEngine(g, EnginePlan(step_impl="dense"))
    cfg = ServiceConfig(batch_size=B, k=5, queue_cap=16,
                        cfg=BatchConfig(xi=1e-6), seconds_per_unit=1e-9,
                        admission=AdmissionPolicy(rate_qps=None))
    svc = PPRService(engine, cfg, clock=VirtualClock())
    wl = ClosedLoopWorkload(g, clients=B, n_queries=B, seed=5,
                            deadline_s=1e6)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = svc.serve(wl)
    finally:
        jax.profiler.stop_trace()
    assert len(rep.served) == B
    events = _host_spans(str(tmp_path))

    def spans(name):
        found = [(s, e) for n, s, e in events if n == name]
        assert found, name
        return found

    def inside(inner, outer):
        return all(any(a <= s and e <= b for a, b in spans(outer))
                   for s, e in spans(inner))

    assert len(spans("serve.ingest")) == B
    (batch,) = spans("serve.batch")
    assert inside("engine.plan", "serve.batch")
    assert inside("engine.exec", "serve.batch")
    assert inside("solve.wait", "engine.exec")
    assert inside("serve.assemble", "serve.batch")
    (assemble,) = spans("serve.assemble")
    assert max(e for _, e in spans("engine.exec")) <= assemble[0]
    assert not any(batch[0] <= s < batch[1] for s, _ in spans("serve.ingest"))
