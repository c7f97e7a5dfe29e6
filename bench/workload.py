"""The closed-loop request generator of serving mixes, cut off at the close.

Copied from ``repro.serve.workload`` (``zipf_rank``, ``zipf_seeds``,
``ClosedLoopWorkload``) so that the traffic cannot move when the program
does, and given a close: no request is issued at or after ``close_at`` on
the serving clock.  It speaks the event-loop interface
``repro.serve.PPRService.serve`` drives (``next_time``, ``take_due``,
``on_complete``, ``on_reject``).  Requests carry no deadline.

Seed vertices are Zipf-skewed over a popularity rank, drawn from an
explicit ``numpy.random.Generator``: the same seed gives the same request
stream.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Request", "zipf_rank", "ZipfSeeds", "ClosedLoop"]

INF = float("inf")


@dataclasses.dataclass
class Request:
    req_id: int
    seed: int
    t_arrival: float
    deadline: float
    client: int = 0


def zipf_rank(in_deg) -> np.ndarray:
    """Vertices by popularity: descending in-degree, ties by vertex id."""
    return np.argsort(-np.asarray(in_deg), kind="stable")


class ZipfSeeds:
    """Seed vertices, ``P(rank r) ~ r**-alpha`` over a popularity ``rank``
    (``rank[0]`` the most popular vertex); ``alpha == 0`` is uniform."""

    def __init__(self, rank, alpha: float, rng: np.random.Generator):
        self.rank = np.asarray(rank)
        self.rng = rng
        self.alpha = float(alpha)
        n = self.rank.size
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** max(self.alpha, 0)
        self.cdf = np.cumsum(w / w.sum())

    def draw(self, size: int) -> np.ndarray:
        u = self.rng.random(size)
        pos = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                         self.rank.size - 1)
        return self.rank[pos]


class ClosedLoop:
    """``clients`` callers, one request each in flight, ``think_s`` apart."""

    def __init__(self, seeds: ZipfSeeds, *, clients: int, think_s: float,
                 close_at: float):
        if int(clients) < 1:
            raise ValueError(f"clients must be >= 1, got {clients}")
        self.seeds = seeds
        self.think_s = float(think_s)
        self.close_at = float(close_at)
        self.issued = 0
        self._ready = [(0.0, c) for c in range(int(clients))]

    def _make(self, t: float, client: int) -> Request:
        req = Request(req_id=self.issued, seed=int(self.seeds.draw(1)[0]),
                      t_arrival=t, deadline=INF, client=client)
        self.issued += 1
        return req

    def next_time(self) -> float:
        t = min((t for t, _ in self._ready), default=INF)
        return t if t < self.close_at else INF

    def take_due(self, now: float):
        self._ready.sort()
        due = [self._make(t, c) for t, c in self._ready
               if t <= now and t < self.close_at]
        self._ready = [(t, c) for t, c in self._ready
                       if not (t <= now and t < self.close_at)]
        return due

    def on_complete(self, req: Request, t: float) -> None:
        self._ready.append((t + self.think_s, req.client))

    on_reject = on_complete
