"""PPR top-k served through ``repro.serve.PPRService``: the ``topk`` driver.

The mix's parameters: micro-batch ``batch``, top ``k``, queue
``queue_cap`` (admission off, no degrade ladder), and a closed loop of
``clients`` with ``think_s`` between requests, whose seed vertices are
Zipf(``zipf``) over in-degree rank, drawn from the mix's ``stream_seed``
(``bench/workload.py``).  Popularity is ranked on the base graph, so
every run sends the same requests under its own labels.

The window starts no new micro-batch once its ``seconds`` have passed;
the one running then completes and counts, and the window ends with it.
Requests still queued at the close are neither answered nor failed.

What decides ``correct``: a sample drawn from the run's seed with one
answered slot in each half of each micro-batch of the window, and every
answer in the window for the seed vertices of those slots: the PPR row
behind it (``row_l1``) and the served top-k (``topk_err``) against the
float64 reference.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

import reference
import workload

SPANS = ("serve.loop", "serve.dispatch")

# In a closed loop whose requests carry no deadline, every dispatch is a
# full batch, or a flush after the close: the service's cost model is
# never consulted.  A calibration given up front keeps ``serve`` from
# measuring one with ``calibrate()``'s two whole micro-batches.
SECONDS_PER_UNIT = 1.0


def make(engine, config: dict, dtype, graph, warm_xi: float, *,
         stream_seed: int, clients: int, think_s: float, zipf: float,
         k: int, batch: int, queue_cap: int):
    """The driver of one run, from the mix's parameters."""
    src, dst, perm = graph
    in_deg = np.bincount(dst, minlength=config["n"])
    rank = perm[workload.zipf_rank(in_deg[perm])]
    return TopKDriver(engine, config, dtype, warm_xi, rank, dict(
        stream_seed=stream_seed, clients=clients, think_s=think_s, zipf=zipf,
        k=k, batch=batch, queue_cap=queue_cap))


class _Windowed:
    """The engine's own executor, timed, until the close; nothing after."""

    def __init__(self, clock, close_at: float):
        from repro.serve.service import EngineExecutor

        self.inner = EngineExecutor()
        self.clock = clock
        self.close_at = close_at
        self.calls = []            # per executor call: timing and env, or None
        self.t_closed = None       # host time of the first call after close

    def __call__(self, engine, sources, k, cfg):
        if self.clock.now() >= self.close_at:
            if self.t_closed is None:
                self.t_closed = time.perf_counter()
            self.calls.append(None)
            return None
        with TraceAnnotation("serve.dispatch"):
            t0 = time.perf_counter()
            env = self.inner(engine, sources, k, cfg)
            self.calls.append(dict(t0=t0, t1=time.perf_counter(), env=env))
        return env


class TopKDriver:
    def __init__(self, engine, config, dtype, warm_xi, rank, mix):
        from repro.core import BatchConfig

        self.engine = engine
        self.mix = mix
        self.cfg = BatchConfig(c=config["c"], xi=config["xi"], dtype=dtype)
        self.warm_xi = warm_xi
        self.rank = rank
        self.calls, self.tier_s = [], None
        self.report = self.executor = None

    def _service(self, cfg, executor, clock):
        from repro.serve import AdmissionPolicy, PPRService, ServiceConfig

        mix = self.mix
        return PPRService(self.engine, ServiceConfig(
            batch_size=mix["batch"], k=mix["k"], queue_cap=mix["queue_cap"],
            admission=AdmissionPolicy(rate_qps=None, burst=float(mix["batch"]),
                                      cache_bypass=False),
            cfg=cfg, time_source="wall", seconds_per_unit=SECONDS_PER_UNIT),
            clock=clock, executor=executor)

    def _stream(self, seed: int, alpha: float, clients: int, close_at: float):
        seeds = workload.ZipfSeeds(self.rank, alpha,
                                   np.random.default_rng(seed))
        return workload.ClosedLoop(seeds, clients=clients,
                                   think_s=self.mix["think_s"],
                                   close_at=close_at)

    def warm_up(self, seconds: float):
        """One full micro-batch through the serving tier at the cell's
        shapes, answers assembled, with a threshold that stops it after
        one round; then the window's own service and requests, so that
        the window holds nothing but serving."""
        from repro.serve import WallClock

        clock = WallClock()
        cfg = dataclasses.replace(self.cfg, xi=self.warm_xi)
        service = self._service(cfg, _Windowed(clock, float("inf")), clock)
        service.serve(self._stream(0, 0.0, self.mix["batch"], 1e-9))
        self.clock = WallClock()
        self.executor = _Windowed(self.clock, seconds)
        self.service = self._service(self.cfg, self.executor, self.clock)
        self.stream = self._stream(self.mix["stream_seed"], self.mix["zipf"],
                                   self.mix["clients"], seconds)

    def window(self):
        with TraceAnnotation("serve.loop"):
            self.clock.restart()
            t_open = time.perf_counter()
            self.report = self.service.serve(self.stream)
            t_end = self.executor.t_closed or time.perf_counter()
        ran = [c for c in self.executor.calls if c is not None]
        self.calls = [dict(t0=c["t0"] - t_open, t1=c["t1"] - t_open,
                           rows=self.mix["batch"],
                           iterations=int(c["env"].iterations), ops=None)
                      for c in ran]
        self.tier_s = (t_end - t_open) - sum(c["t1"] - c["t0"] for c in ran)

    def _answered(self):
        return [s for s in self.report.served if s.indices is not None]

    def end_to_end(self) -> dict:
        done = self._answered()
        if not done:
            raise RuntimeError("the window answered no request")
        lat = np.asarray([s.latency_s for s in done])
        return dict(ppr_qps=len(done) / max(s.t_done for s in done),
                    ppr_p95_ms=float(np.percentile(lat, 95)) * 1e3)

    def attempted(self) -> int:
        return len(self._answered())

    def collect(self, rng) -> dict:
        """The checked answers, with their rows, on the host.

        One slot is drawn from each half of each micro-batch the window
        ran, so a fault that spares one half of a batch still meets a
        checked slot; every answer for those slots' seed vertices is
        checked, with the PPR row the engine computed behind it.
        """
        batches, served = [], iter(self.report.served)
        for (_, n_real, _), call in zip(self.report.batches,
                                        self.executor.calls):
            slots = [next(served) for _ in range(n_real)]
            if call is not None:
                batches.append((call["env"], slots))
        chosen = set()
        for _, slots in batches:
            half = (len(slots) + 1) // 2
            for part in (slots[:half], slots[half:]):
                if part:
                    chosen.add(part[int(rng.integers(len(part)))].req.seed)
        host, answers = {}, []
        for env, slots in batches:
            for r, s in enumerate(slots):
                if s.req.seed in chosen:
                    if id(env) not in host:
                        host[id(env)] = np.asarray(env.result.result.pi,
                                                   np.float64)
                    answers.append((s.req.seed, np.asarray(s.indices),
                                    np.asarray(s.scores, np.float64),
                                    host[id(env)][r]))
        self.report = self.executor = self.service = self.engine = None
        return dict(k=self.mix["k"], seeds=sorted(chosen), answers=answers)


def check(config: dict, src, dst, held: dict) -> list:
    """One dict per checked answer: ``row_l1``, the L1 distance of the
    engine's PPR row behind it, and ``topk_err``
    (:func:`reference.topk_err`) of the served top-k."""
    n, seeds = config["n"], held["seeds"]
    P = np.zeros((len(seeds), n))
    P[np.arange(len(seeds)), seeds] = 1.0
    ref, _ = reference.pagerank_rows(src, dst, n, P, c=config["c"])
    ref = dict(zip(seeds, ref))
    return [dict(row_l1=reference.finite(np.abs(row - ref[v]).sum()),
                 topk_err=reference.finite(
                     reference.topk_err(idx, sc, ref[v], held["k"])))
            for v, idx, sc, row in held["answers"]]
