"""PPR top-k served while a crawl's link churn is written into the live
layout: the ``ppr_delta`` driver.

Reads are the ``topk`` driver's: ``repro.serve.PPRService`` answers a
closed loop of ``clients`` (``think_s``, Zipf(``zipf``) seeds drawn from
``stream_seed``) in micro-batches of ``batch``, top ``k``, queue
``queue_cap``.  Writes are the ``delta`` driver's stream
(:func:`delta.run_stream`, from the same ``stream_seed``): ``deltas``
deltas of ``inserts`` inserts and ``deletes`` deletes.  The first
``warm_up`` are applied in set-up, through the service's write lane
(the first takes the engine's layout for deltas and compiles its edit);
the window offers the rest, one due every ``write_every_s`` seconds from
``write_every_s`` on, to the same lane, which applies each due write
before the next dispatch as ``DeltaQuery(..., rank=False)``.

The window starts no new micro-batch once its ``seconds`` have passed;
the one running then completes and counts, and the window ends with it.
Requests still queued at the close are not answered.  The lane applies
a write that fell due before the next dispatch, also when that dispatch
comes after the close and answers nothing; the driver keeps the writes
acknowledged up to the window's end.

What decides ``correct``:

  * ``row_l1`` and ``topk_err``: the ``topk`` driver's sample (one
    answered slot in each half of each micro-batch, and every answer for
    those slots' seed vertices), each against the float64 reference on
    the graph version its answer reports (``Served.graph_version``), the
    versions built here from the stream with numpy alone
    (:func:`delta.versions`);
  * ``stale_batches``: micro-batches dispatched on a version older than
    the newest write acknowledged before them, or whose answers report
    more than one version;
  * ``relayouts``: writes that laid the program's graph out again, which
    a cell sized within the program's slack never does.

A program with no layout-only delta (``DeltaQuery.rank``) or no version on
its answers (``Served.graph_version``) cannot run the mix: set-up fails.
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict

import numpy as np
from jax.profiler import TraceAnnotation

import plugins
import reference
import workload
from plugins import SetupError

topk = plugins.load("drivers", "topk")
delta = plugins.load("drivers", "delta")

SPANS = topk.SPANS + ("serve.write", "engine.delta.apply")


def _fields(cls) -> set:
    return {f.name for f in dataclasses.fields(cls)}


def make(engine, config: dict, dtype, graph, warm_xi: float, *,
         stream_seed: int, clients: int, think_s: float, zipf: float,
         k: int, batch: int, queue_cap: int, inserts: int, deletes: int,
         deltas: int, warm_up: int, write_every_s: float):
    """The driver of one run, from the mix's parameters."""
    from repro.core import DeltaQuery
    from repro.serve import service

    if "rank" not in _fields(DeltaQuery):
        raise SetupError("the program has no layout-only delta "
                         "(DeltaQuery has no 'rank'): every write would "
                         "re-rank the whole graph")
    if "graph_version" not in _fields(service.Served):
        raise SetupError("the program's answers carry no graph version "
                         "(Served has no 'graph_version'): no answer can "
                         "be checked on the graph it was computed on")
    if not 1 <= warm_up < deltas:
        raise SetupError(f"warm_up {warm_up} must be at least 1 and below "
                         f"deltas {deltas}")
    src, dst, perm = graph
    in_deg = np.bincount(dst, minlength=config["n"])
    rank = perm[workload.zipf_rank(in_deg[perm])]
    stream = delta.run_stream(graph, config["n"], stream_seed, inserts,
                              deletes, deltas)
    return PPRDeltaDriver(engine, config, dtype, warm_xi, rank, dict(
        stream_seed=stream_seed, clients=clients, think_s=think_s, zipf=zipf,
        k=k, batch=batch, queue_cap=queue_cap), stream, warm_up,
        float(write_every_s))


def _ack(a) -> dict:
    return dict(t_arrival=a.t_arrival, t_start=a.t_start, t_ack=a.t_ack,
                graph_version=a.graph_version, relayouts=a.relayouts)


class PPRDeltaDriver(topk.TopKDriver):
    def __init__(self, engine, config, dtype, warm_xi, rank, mix, stream,
                 warm_up, write_every_s):
        super().__init__(engine, config, dtype, warm_xi, rank, mix)
        self.stream = stream
        self.warm = warm_up
        self.every = write_every_s
        # the version before any write: version v holds the stream's first
        # v - base deltas
        self.base = engine.graph_version
        self.setup_acks, self.window_acks = [], []

    def warm_up(self, seconds: float):
        """The set-up writes and one full micro-batch through the serving
        tier on the layout they leave, with a threshold that stops it
        after one round; then the window's own service, requests and
        writes."""
        from repro.serve import WallClock, Write

        clock = WallClock()
        cfg = dataclasses.replace(self.cfg, xi=self.warm_xi)
        service = self._service(cfg, topk._Windowed(clock, float("inf")),
                                clock)
        report = service.serve(
            self._stream(0, 0.0, self.mix["batch"], 1e-9),
            writes=[Write(0.0, add, remove)
                    for add, remove in self.stream[:self.warm]])
        self.setup_acks = list(report.writes)
        self.clock = WallClock()
        self.executor = topk._Windowed(self.clock, seconds)
        self.service = self._service(self.cfg, self.executor, self.clock)
        self.reads = self._stream(self.mix["stream_seed"], self.mix["zipf"],
                                  self.mix["clients"], seconds)
        self.writes = [Write(self.every * (i + 1), add, remove)
                       for i, (add, remove) in enumerate(self.stream[self.warm:])]

    def window(self):
        with TraceAnnotation("serve.loop"):
            self.clock.restart()
            t_open = time.perf_counter()
            self.report = self.service.serve(self.reads, writes=self.writes)
            t_end = self.executor.t_closed or time.perf_counter()
        # the service's clock restarted as the window opened: its times
        # are the window's
        acks = self.window_acks = [a for a in self.report.writes
                                   if a.t_ack <= t_end - t_open]
        ran = [(call, slots) for call, slots in self._batches()
               if call is not None]
        # each micro-batch records the writes acknowledged since the one
        # before it; the last, also those applied after it
        self.calls, t_prev = [], -float("inf")
        for i, (call, slots) in enumerate(ran):
            t = slots[0].t_dispatch if i < len(ran) - 1 else float("inf")
            self.calls.append(dict(
                t0=call["t0"] - t_open, t1=call["t1"] - t_open,
                rows=self.mix["batch"],
                iterations=int(call["env"].iterations), ops=None,
                t_dispatch=slots[0].t_dispatch,
                graph_version=slots[0].graph_version,
                writes=[_ack(a) for a in acks if t_prev < a.t_ack <= t]))
            t_prev = t
        # the serving tier's own host time: neither the engine's
        # micro-batches nor the write lane
        self.tier_s = ((t_end - t_open)
                       - sum(c["t1"] - c["t0"] for c, _ in ran)
                       - sum(a.t_ack - a.t_start for a in acks))

    def _batches(self):
        """Each micro-batch's executor call (None after the close) and the
        requests it served."""
        served = iter(self.report.served)
        return [(call, [next(served) for _ in range(n_real)])
                for (_, n_real, _), call in zip(self.report.batches,
                                                self.executor.calls)]

    def collect(self, rng) -> dict:
        """The checked answers, each with its PPR row on the host and the
        version it reports; the staleness of every micro-batch the window
        ran, and the relayouts of every write applied."""
        batches = [(call["env"], slots) for call, slots in self._batches()
                   if call is not None]
        # the window's acknowledgements, on its own clock; before them,
        # the set-up writes'
        stale = 0
        for _, slots in batches:
            versions = {s.graph_version for s in slots}
            acked = [a.graph_version for a in self.window_acks
                     if a.t_ack <= slots[0].t_dispatch]
            newest = max(acked, default=self.base + self.warm)
            stale += len(versions) > 1 or min(versions) < newest
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
                                    host[id(env)][r],
                                    s.graph_version - self.base))
        relayouts = sum(float("inf") if a.relayouts is None
                        else a.relayouts
                        for a in self.setup_acks + self.window_acks)
        newest = max((a[4] for a in answers), default=0)
        self.report = self.executor = self.service = self.engine = None
        return dict(k=self.mix["k"], answers=answers,
                    stream=self.stream[:max(newest, 0)],
                    stale_batches=float(stale), relayouts=float(relayouts))


def check(config: dict, src, dst, held: dict) -> list:
    """One dict per checked answer: ``row_l1`` and ``topk_err`` against
    the reference on the graph version it reports, and the window's
    ``stale_batches`` and ``relayouts``."""
    n, c = config["n"], config["c"]
    wanted = defaultdict(set)  # deltas applied -> seed vertices answered
    for v, _, _, _, applied in held["answers"]:
        wanted[applied].add(v)
    base = np.sort(np.asarray(dst, np.int64) * n + np.asarray(src))
    graphs = enumerate([base, *delta.versions(src, dst, n, held["stream"])])
    ref = {}
    for applied, keys in graphs:
        seeds = sorted(wanted.get(applied, ()))
        if not seeds:
            continue
        P = np.zeros((len(seeds), n))
        P[np.arange(len(seeds)), seeds] = 1.0
        rows, _ = reference.pagerank_rows(keys % n, keys // n, n, P, c=c)
        ref.update({(applied, v): row for v, row in zip(seeds, rows)})
    out = []
    for v, idx, sc, row, applied in held["answers"]:
        want = ref.get((applied, v))
        if want is None:  # a version the stream never made
            row_l1 = topk_err = float("inf")
        else:
            row_l1 = reference.finite(np.abs(row - want).sum())
            topk_err = reference.finite(
                reference.topk_err(idx, sc, want, held["k"]))
        out.append(dict(row_l1=row_l1, topk_err=topk_err,
                        stale_batches=held["stale_batches"],
                        relayouts=held["relayouts"]))
    return out
