"""Request generators — Poisson arrivals over Zipf-skewed PPR seeds.

Two standard load shapes drive every serving experiment:

  * **open loop** (:class:`OpenLoopWorkload`) — arrivals follow a Poisson
    process at a fixed offered rate, regardless of how the service is
    doing.  This is the overload-honest shape: when the service falls
    behind, requests keep coming and the queue/admission policies must
    answer for it (the coordinated-omission trap of closed-loop
    benchmarks).
  * **closed loop** (:class:`ClosedLoopWorkload`) — N logical clients
    each wait for their previous request to finish (plus think time)
    before issuing the next.  Offered load self-throttles to service
    capacity; with zero think time this is the saturating drain loop the
    old benchmark driver ran.

Beside the reads, :class:`Write` is one timed edge delta for the
service's write lane.

Both read loads are deterministic functions of an explicit seed: the
arrival gaps, the Zipf seed stream and the client interleaving all come
from one ``numpy.random.Generator``, so identical seeds give identical
request streams — the property the drift-checked serving benchmark stands on.

:func:`zipf_seeds` (moved here from ``launch/ppr_serve.py``) carries the
determinism contract: the RNG is **required** (no module-global state),
and tied in-degree ranks are broken by vertex id via a stable sort on the
``(-in_deg, id)`` key, so equal-degree vertices rank identically on every
platform and numpy version.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List

import numpy as np

__all__ = [
    "Request",
    "Write",
    "OpenLoopWorkload",
    "ClosedLoopWorkload",
    "zipf_seeds",
    "zipf_rank",
]


def zipf_rank(g) -> np.ndarray:
    """Popularity rank over in-degree, ties broken by vertex id.

    ``rank[0]`` is the most-referenced vertex.  ``np.argsort`` with
    ``kind="stable"`` on the negated in-degree already orders ties by
    ascending id deterministically; the explicit contract (and the test
    pinning it) is what the cross-platform serving bench relies on.
    """
    return np.argsort(-np.asarray(g.in_deg), kind="stable")


def zipf_seeds(g, n_queries: int, alpha: float, rng) -> np.ndarray:
    """Seed vertices for a query stream, Zipf-skewed by in-degree rank.

    ``alpha=0`` is uniform; larger alpha concentrates queries on popular
    (high in-degree) vertices — the realistic serving distribution.

    ``rng`` is required: an int seed or a ``numpy.random.Generator``.
    Identical seeds produce identical streams (ties in the in-degree
    ranking are id-stable, see :func:`zipf_rank`) — passing ``None``
    raises instead of silently drawing from global state.
    """
    if rng is None:
        raise TypeError(
            "zipf_seeds requires an explicit rng (int seed or "
            "numpy.random.Generator); None would break the deterministic "
            "query-stream contract"
        )
    if not isinstance(rng, np.random.Generator):
        rng = np.random.default_rng(int(rng))
    if alpha <= 0:
        return rng.integers(0, g.n, size=int(n_queries))
    rank = zipf_rank(g)
    w = 1.0 / np.arange(1, g.n + 1, dtype=np.float64) ** float(alpha)
    return rank[rng.choice(g.n, size=int(n_queries), p=w / w.sum())]


@dataclasses.dataclass
class Request:
    """One PPR request as the serving tier sees it.

    ``deadline`` is absolute (same clock as ``t_arrival``); the batcher
    compares it against predicted batch cost to decide dispatch.
    """

    req_id: int
    seed: int
    t_arrival: float
    deadline: float
    client: int = 0


class OpenLoopWorkload:
    """Poisson arrivals at ``qps``, seeds Zipf-skewed, fixed count.

    ``qps`` may also be a list of ``(duration_s, qps)`` phases — the
    square-wave and step loads the degrade-policy tests drive.  All
    arrival times are precomputed at construction (one RNG draw pass), so
    the schedule is independent of how the service behaves — the open
    loop's defining property.
    """

    def __init__(
        self,
        g,
        qps,
        n_queries: int,
        *,
        zipf: float = 1.1,
        seed: int = 0,
        deadline_s: float = 0.25,
        k: int = 5,
    ):
        rng = np.random.default_rng(int(seed))
        n_queries = int(n_queries)
        phases = qps if isinstance(qps, (list, tuple)) else [(None, qps)]
        times: List[float] = []
        t, phase_i, phase_t0 = 0.0, 0, 0.0
        while len(times) < n_queries:
            dur, rate = phases[phase_i]
            if rate <= 0:
                raise ValueError(f"offered qps must be > 0, got {rate!r}")
            gap = float(rng.exponential(1.0 / float(rate)))
            if dur is not None and t + gap > phase_t0 + float(dur) and phase_i + 1 < len(phases):
                # next phase starts where this one ends; re-draw there
                phase_t0 += float(dur)
                t = max(t, phase_t0)
                phase_i += 1
                continue
            t += gap
            times.append(t)
        seeds = zipf_seeds(g, n_queries, zipf, rng)
        dl = float(deadline_s)
        self.requests = [
            Request(req_id=i, seed=int(seeds[i]), t_arrival=times[i], deadline=times[i] + dl)
            for i in range(n_queries)
        ]
        self.deadline_s = float(deadline_s)
        self.k = int(k)
        self._next = 0

    # -- the event-loop interface -------------------------------------- #
    def next_time(self) -> float:
        if self._next >= len(self.requests):
            return float("inf")
        return self.requests[self._next].t_arrival

    def take_due(self, now: float) -> List[Request]:
        due = []
        while self._next < len(self.requests) and self.requests[self._next].t_arrival <= now:
            due.append(self.requests[self._next])
            self._next += 1
        return due

    def on_complete(self, req: Request, t: float) -> None:
        pass  # open loop: completions never shape arrivals

    def on_reject(self, req: Request, t: float) -> None:
        pass

    @property
    def drained(self) -> bool:
        return self._next >= len(self.requests)


class ClosedLoopWorkload:
    """N clients, each one-request-in-flight, optional think time.

    A client issues its next request ``think_s`` after its previous one
    completes *or is rejected* (a shed request consumed the client's
    turn).  With ``think_s=0`` and ``clients == batch size`` this is the
    saturating micro-batch drain the legacy serving driver measured —
    offered load tracks service capacity, so nothing queues unboundedly.
    """

    def __init__(
        self,
        g,
        clients: int,
        n_queries: int,
        *,
        zipf: float = 1.1,
        seed: int = 0,
        think_s: float = 0.0,
        deadline_s: float = 0.25,
        k: int = 5,
    ):
        if int(clients) < 1:
            raise ValueError(f"clients must be >= 1, got {clients}")
        rng = np.random.default_rng(int(seed))
        self._seeds = zipf_seeds(g, int(n_queries), zipf, rng)
        self.n_queries = int(n_queries)
        self.deadline_s = float(deadline_s)
        self.think_s = float(think_s)
        self.k = int(k)
        self._issued = 0
        self._inflight = 0
        # (t_ready, client) min-ordered; all clients ready at t=0
        n_clients = min(int(clients), self.n_queries)
        self._ready: List[tuple] = [(0.0, c) for c in range(n_clients)]

    def _make(self, t: float, client: int) -> Request:
        req = Request(
            req_id=self._issued,
            seed=int(self._seeds[self._issued]),
            t_arrival=t,
            deadline=t + self.deadline_s,
            client=client,
        )
        self._issued += 1
        self._inflight += 1
        return req

    def next_time(self) -> float:
        if self._issued >= self.n_queries or not self._ready:
            return float("inf")
        return min(t for t, _ in self._ready)

    def take_due(self, now: float) -> List[Request]:
        due = []
        # stable order: by ready time, then client id — determinism
        self._ready.sort()
        still_waiting = []
        for t, c in self._ready:
            if t <= now and self._issued < self.n_queries:
                due.append(self._make(t, c))
            else:
                still_waiting.append((t, c))
        self._ready = still_waiting
        return due

    def _client_done(self, req: Request, t: float) -> None:
        self._inflight -= 1
        if self._issued < self.n_queries:
            self._ready.append((t + self.think_s, req.client))

    def on_complete(self, req: Request, t: float) -> None:
        self._client_done(req, t)

    def on_reject(self, req: Request, t: float) -> None:
        self._client_done(req, t)

    @property
    def drained(self) -> bool:
        return self._issued >= self.n_queries and self._inflight == 0


@dataclasses.dataclass(frozen=True)
class Write:
    """One edge delta offered to the serving tier at ``t_arrival``.

    ``add``/``remove`` are ``(src, dst)`` pairs, as ``DeltaQuery`` takes
    them.
    """

    t_arrival: float
    add: Any = ()
    remove: Any = ()
