"""A crawl's ranking kept fresh under link churn: the ``delta`` driver.

One writer runs ``engine.run(DeltaQuery(add, remove))`` back to back, each
refresh ended by ``block_until_ready`` on the refreshed pi.  The mix's
parameters:

  * ``inserts``, ``deletes``: edges added and removed by each delta;
  * ``deltas``: the stream's length; ``warm_up`` of them are applied in
    set-up (the first takes the engine's residual state and its layout
    for deltas), the rest are the window's.  The window also ends when
    the stream is spent;
  * ``stream_seed``: the stream is drawn from it in the base graph's ids
    and mapped through the run's relabelling, so every seed does the same
    work.  Each delta deletes edges uniformly over those present, then
    adds edges with a source uniform over all vertices and a destination
    drawn with weight in-degree + 1 (preferential attachment), with no
    self-loop, no edge present and none deleted in the same delta.

The window starts no new refresh once its ``seconds`` have passed; the one
running then completes and counts, and the window ends with it.

What decides ``correct``: every refresh of the window, its whole pi
against the float64 reference on the graph version it was given (``l1``),
each version built here from the stream with numpy alone; and
``relayouts``, whether a refresh had to lay the program's graph out again,
which a cell sized within the program's slack never does.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

import reference
from plugins import SetupError

SPANS = ("delta.refresh", "engine.delta.apply")


def make(engine, config: dict, dtype, graph, warm_xi: float, *,
         stream_seed: int, inserts: int, deletes: int, deltas: int,
         warm_up: int):
    """The driver of one run, from the mix's parameters."""
    if "delta_capacity" not in engine.describe(include_plan=False):
        raise SetupError("the program keeps no layout for edge deltas "
                         "(PageRankEngine.describe() has no "
                         "'delta_capacity'): each refresh would rebuild and "
                         "compile")
    if not 1 <= warm_up < deltas:
        raise SetupError(f"warm_up {warm_up} must be at least 1 and below "
                         f"deltas {deltas}")
    stream = run_stream(graph, config["n"], stream_seed, inserts, deletes,
                        deltas)
    return DeltaDriver(engine, stream, warm_up)


def run_stream(graph, n: int, seed: int, inserts: int, deletes: int,
               deltas: int) -> list:
    """The stream of :func:`base_stream`, drawn in the base graph's ids
    and mapped to the run's labels; ``graph`` is ``(src, dst, perm)``."""
    src, dst, perm = graph
    base = np.empty(n, np.int64)
    base[perm] = np.arange(n)
    return [(perm[a], perm[r]) for a, r in base_stream(
        base[src], base[dst], n, seed, inserts, deletes, deltas)]


def base_stream(src, dst, n: int, seed: int, inserts: int, deletes: int,
                deltas: int) -> list:
    """``deltas`` deltas over the graph ``(src, dst)``, as ``(add,
    remove)`` pairs of int64 ``[k, 2]`` ``(src, dst)`` arrays."""
    rng = np.random.default_rng(seed)
    n64 = np.int64(n)
    keys = np.sort(np.asarray(dst, np.int64) * n64 + np.asarray(src))
    out = []
    for _ in range(deltas):
        gone = rng.choice(keys.size, size=deletes, replace=False)
        removed = keys[gone]
        keys = np.delete(keys, gone)
        weight = np.cumsum(np.bincount(keys // n64, minlength=n) + 1.0)
        added = np.empty(0, np.int64)
        while added.size < inserts:
            k = 2 * (inserts - added.size)
            s = rng.integers(0, n, size=k)
            d = np.minimum(np.searchsorted(weight, rng.random(k) * weight[-1],
                                           side="right"), n - 1)
            cand = d * n64 + s
            pos = np.minimum(np.searchsorted(keys, cand), keys.size - 1)
            ok = (s != d) & (keys[pos] != cand) & ~np.isin(cand, removed)
            cand = np.concatenate([added, cand[ok]])
            _, first = np.unique(cand, return_index=True)
            added = cand[np.sort(first)][:inserts]
        keys = np.insert(keys, np.searchsorted(keys, np.sort(added)),
                         np.sort(added))
        out.append(tuple(np.stack([x % n64, x // n64], axis=1)
                         for x in (added, removed)))
    return out


class DeltaDriver:
    def __init__(self, engine, stream, warm_up):
        self.engine = engine
        self.stream = stream
        self.next = 0
        self.warm = warm_up
        self.calls, self.values, self.tier_s = [], [], None

    def _refresh(self):
        import jax
        from repro.core import DeltaQuery

        add, remove = self.stream[self.next]
        with TraceAnnotation("delta.refresh"):
            t0 = time.perf_counter()
            env = self.engine.run(DeltaQuery(add=add, remove=remove))
            jax.block_until_ready(env.values)
            t1 = time.perf_counter()
        self.next += 1
        return env, t0, t1

    def warm_up(self, seconds: float):
        while self.next < self.warm:
            self._refresh()
        self.seconds = seconds

    def window(self):
        t_open = time.perf_counter()
        while (time.perf_counter() - t_open < self.seconds
               and self.next < len(self.stream)):
            env, t0, t1 = self._refresh()
            res = env.result
            self.calls.append(dict(
                t0=t0 - t_open, t1=t1 - t_open, rows=1,
                iterations=int(env.iterations), ops=float(res.ops),
                delta=self.next - 1,
                relayouts=getattr(res, "relayouts", None),
                core_edges=getattr(res, "core_edges", None)))
            self.values.append(env.values)

    def end_to_end(self) -> dict:
        return dict(rank_solve_s=self.calls[-1]["t1"] / len(self.calls))

    def attempted(self) -> int:
        return len(self.calls)

    def collect(self, rng) -> dict:
        """Every refresh's pi, on the host, with the stream up to the last
        refresh; the engine's state is dropped."""
        pis = [np.asarray(v, np.float64) for v in self.values]
        self.values = self.engine = None
        return dict(pis=pis, stream=self.stream[:self.next],
                    refreshes=[c["delta"] for c in self.calls],
                    relayouts=[c["relayouts"] for c in self.calls])


def versions(src, dst, n: int, stream):
    """The graph after each delta of ``stream``, as sorted key arrays
    ``dst * n + src``, one by one."""
    n64 = np.int64(n)
    keys = np.sort(np.asarray(dst, np.int64) * n64 + np.asarray(src))
    for add, remove in stream:
        keys = np.setdiff1d(keys, remove[:, 1] * n64 + remove[:, 0],
                            assume_unique=True)
        keys = np.union1d(keys, add[:, 1] * n64 + add[:, 0])
        yield keys


def check(config: dict, src, dst, held: dict) -> list:
    """One dict per refresh: ``l1``, its pi's L1 distance from the
    reference on the graph version after its delta, and ``relayouts``."""
    n = config["n"]
    uniform = np.full((1, n), 1.0 / n)
    checked = dict(zip(held["refreshes"], zip(held["pis"],
                                              held["relayouts"])))
    out = []
    for i, keys in enumerate(versions(src, dst, n, held["stream"])):
        if i not in checked:
            continue
        pi, relayouts = checked[i]
        ref, _ = reference.pagerank_rows(keys % n, keys // n, n, uniform,
                                         c=config["c"])
        out.append(dict(l1=reference.finite(np.abs(pi - ref[0]).sum()),
                        relayouts=float("inf") if relayouts is None
                        else float(relayouts)))
    return out
