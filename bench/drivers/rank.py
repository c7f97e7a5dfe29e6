"""Global ranking as a batch job reruns it: the ``rank`` driver.

One caller runs ``engine.run(RankQuery(ItaConfig(xi, c)))`` back to back,
each solve ended by ``block_until_ready``.  The mix takes no parameters.
The window starts no new solve once its ``seconds`` have passed; the one
running then completes and counts, and the window ends with it.

What decides ``correct``: every solve of the window, its whole normalized
pi against the float64 reference (``l1``, the L1 distance).
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

import reference

SPANS = ("rank.solve",)


def make(engine, config: dict, dtype, graph, warm_xi: float):
    """The driver of one run; the mix has no parameters."""
    return RankDriver(engine, config, dtype, warm_xi)


class RankDriver:
    def __init__(self, engine, config, dtype, warm_xi):
        from repro.core import ItaConfig, RankQuery

        self.engine = engine
        self.query = RankQuery(ItaConfig(c=config["c"], xi=config["xi"],
                                         dtype=dtype))
        self.warm = RankQuery(ItaConfig(c=config["c"], xi=warm_xi,
                                        dtype=dtype))
        self.calls, self.values, self.tier_s = [], [], None

    def _solve(self, query):
        import jax

        with TraceAnnotation("rank.solve"):
            t0 = time.perf_counter()
            env = self.engine.run(query)
            jax.block_until_ready(env.values)
            return env, t0, time.perf_counter()

    def warm_up(self, seconds: float):
        self._solve(self.warm)
        self.seconds = seconds

    def window(self):
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < self.seconds:
            env, t0, t1 = self._solve(self.query)
            self.calls.append(dict(t0=t0 - t_open, t1=t1 - t_open, rows=1,
                                   iterations=int(env.iterations),
                                   ops=float(env.result.ops)))
            self.values.append(env.values)

    def end_to_end(self) -> dict:
        return dict(rank_solve_s=self.calls[-1]["t1"] / len(self.calls))

    def attempted(self) -> int:
        return len(self.calls)

    def collect(self, rng) -> dict:
        """Every solve's pi, on the host; the engine's state is dropped."""
        pis = [np.asarray(v, np.float64) for v in self.values]
        self.values = self.engine = None
        return dict(pis=pis)


def check(config: dict, src, dst, held: dict) -> list:
    """One dict per solve: ``l1``, its pi's L1 distance from the
    reference (every solve ranks the same graph, so one reference)."""
    n = config["n"]
    ref, _ = reference.pagerank_rows(src, dst, n, np.full((1, n), 1.0 / n),
                                     c=config["c"])
    return [dict(l1=reference.finite(np.abs(pi - ref[0]).sum()))
            for pi in held["pis"]]
