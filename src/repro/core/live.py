"""The dense push's layout for a graph that takes edge deltas.

A :class:`~repro.core.engine.PageRankEngine` on the dense backend keeps
this layout from its first ``DeltaQuery`` on.  Its shapes stay fixed while
deltas fit the slack it was laid out with, so a refresh compiles nothing,
and a delta costs host work in proportion to the edges it changes, never
to m:

  * each of the push's two edge lists (all edges, and the out-edges of the
    set ``S`` the core list serves; :class:`~repro.core.backends.DenseRuns`)
    keeps the order it was laid out in, its runs longest first, followed
    by an insert region of ``slack`` slots (:data:`SLACK`, a share of m).
    A delta never changes a main-region run's length (a deleted edge
    keeps its slot), so the main region keeps the scan schedule it was
    laid out with (:class:`~repro.core.backends.ScanPasses`); the insert
    region, rebuilt on every delta, takes every pass over its slots;
  * a deleted edge's source becomes the pad vertex n, which pushes zero;
  * ``S`` starts as the referenced core (paper §III) and only grows.  The
    core list must hold every live out-edge of ``S``, and ``S`` must stay
    closed under out-edges, or information would stay outside it in every
    round.  So a vertex an added edge reaches from ``S`` joins it, with
    all it reaches.  An added edge between two vertices outside ``S``
    that does not run from a lower peel level to a higher one could close
    a cycle there; its destination joins too.  What stays outside ``S``
    then keeps levels, and the core list still takes over after as many
    rounds;
  * a joined vertex's out-edges leave the full list's main region (their
    slots point at the pad vertex) and go to both insert regions, beside
    the edges added since the layout.  The insert regions are rebuilt
    whole on every delta, sorted by destination; within a destination's
    run the full list puts the sources outside ``S`` first, and the core
    list holds exactly the rest, in the same order.

So, once its input is zero off ``S``, each of the full list's runs, in the
main region and in the insert region alike, is the core list's run behind
exact zeros, and the two lists push the same sums bit for bit
(:func:`~repro.core.backends._run_sums`), as on the plain layout: a row's
result never depends on which list its batch walked.  The host keeps each
base edge's slot in either main region (``full_pos``, ``core_pos``) and
each vertex's main run end (``main_last``), in that region's own order.

A delta that does not fit lays the whole graph out again, once, with fresh
slack: ``relayouts`` counts those.  A layout taken on a graph that never
changes pushes exactly what the plain layout pushes, bit for bit.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.structure import Degrees, Graph, LiveEdges
from .backends import DenseRuns, _core_order, _runs

__all__ = ["SLACK", "LiveLayout"]

# Insert capacity of each edge list, as a share of the edges laid out.  A
# day of hourly deltas of 0.149% of m (25% new links a week) fills 5.4% of
# m on web-Google's stand-in: 3.5% added, 1.9% out-edges of the vertices
# that joined S.  A sixteenth holds that; the 29th delta lays out again.
SLACK = 1 / 16


class _ListUpdate(NamedTuple):
    """One edge list's change, in fixed shapes (padded with ``n``)."""

    src: jnp.ndarray   # int32[C]: the insert region's sources
    dst: jnp.ndarray   # int32[C]: their destinations, sorted; n for a pad
    kill: jnp.ndarray  # int32[C]: main-list positions deleted (C + e: none)


def _refill(runs: DenseRuns, main_last, up: _ListUpdate) -> DenseRuns:
    """``runs`` with deleted edges pointed at the pad vertex and the
    insert region, its run flags, carries and readout positions redone."""
    n = main_last.shape[0]
    cap = up.src.shape[0]
    e = runs.src.shape[0] - cap
    live = up.dst < n
    before = jnp.concatenate([jnp.full((1,), -1, up.dst.dtype), up.dst[:-1]])
    after = jnp.concatenate([up.dst[1:], jnp.full((1,), n, up.dst.dtype)])
    end = live & (up.dst != after)
    own = main_last[jnp.minimum(up.dst, n - 1)]
    src = runs.src.at[up.kill].set(n, mode="drop")
    return runs._replace(
        src=jax.lax.dynamic_update_slice_in_dim(src, up.src, e, 0),
        start=jax.lax.dynamic_update_slice_in_dim(
            runs.start, ~live | (up.dst != before), e, 0),
        last=main_last.at[jnp.where(end, up.dst, n)].set(
            e + jnp.arange(cap, dtype=jnp.int32), mode="drop"),
        carry=jnp.where(end & (own >= 0), own, -1).astype(jnp.int32))


@jax.jit
def _relaid(ctx: DenseRuns, main_last, core_main_last, full: _ListUpdate,
            core: Optional[_ListUpdate], joined, degrees: Degrees, touched,
            out_deg, in_deg):
    """The layout after one delta, on the device; every shape as before."""
    ctx = _refill(ctx, main_last, full)
    if core is not None:
        ctx = ctx._replace(core=_refill(ctx.core, core_main_last, core),
                           in_core=ctx.in_core.at[joined].set(True,
                                                              mode="drop"))
    return ctx, Degrees(
        out_deg=degrees.out_deg.at[touched].set(out_deg, mode="drop"),
        in_deg=degrees.in_deg.at[touched].set(in_deg, mode="drop"),
        n=degrees.n)


def _padded(values: np.ndarray, size: int, fill: int) -> jnp.ndarray:
    out = np.full(size, fill, np.int32)
    out[:values.size] = values
    return jnp.asarray(out)


class LiveLayout:
    """The dense push's edge lists for a graph taking deltas, the host
    bookkeeping that keeps them, and the graph's degrees on the device.

    ``ctx`` is the backend context the push reads, ``degrees`` the
    :class:`~repro.graph.structure.Degrees` the loop reads; ``edges`` is
    the host edge set (:class:`~repro.graph.structure.LiveEdges`).
    """

    def __init__(self, g: Graph):
        n = self.n = g.n
        self.edges = LiveEdges(g)
        self.slack = max(int(np.ceil(g.m * SLACK)), 64)
        self.levels = np.array(g.reference_levels)
        self.in_s = self.levels < 0
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        order, core_order, _ = _core_order(g)
        self.full_pos = np.empty(g.m, np.int32)
        self.full_pos[order] = np.arange(g.m, dtype=np.int32)
        full = _runs(src[order], dst[order], n, self.slack)
        self.main_last = full.last
        self.core_main_last = None
        self.core_pos = None
        if core_order is not None:
            core = _runs(src[core_order], dst[core_order], n, self.slack)
            self.core_main_last = core.last
            self.core_pos = np.full(g.m, -1, np.int32)
            self.core_pos[core_order] = np.arange(core_order.size,
                                                  dtype=np.int32)
            self.core_alive = core_order.size
            full = full._replace(core=core,
                                 in_core=jnp.asarray(self.in_s.copy()))
        # keys of the base edges whose source joined S: they left the full
        # list's main region for the insert regions
        self.moved = np.empty(0, np.int64)
        self.core_inserted = 0  # edges in the core list's insert region
        self.ctx = full
        self.degrees = Degrees(out_deg=jnp.asarray(g.out_deg),
                               in_deg=jnp.asarray(g.in_deg), n=n)

    @property
    def core_edges(self) -> Optional[int]:
        """Live edges in the core list, or None where there is none."""
        if self.core_pos is None:
            return None
        return self.core_alive + self.core_inserted

    def apply(self, add=(), remove=()) -> bool:
        """Apply one delta; returns whether it had to lay the graph out
        again (it did not fit the slack)."""
        n = self.n
        removed_base, removed, added = self.edges.apply(add, remove)
        touched = np.unique(np.concatenate(
            [removed % n, removed // n, added % n, added // n]))
        kill = self.full_pos[removed_base]
        kill_core = np.empty(0, np.int32)
        joined = np.empty(0, np.int64)
        inserted = self.edges.inserted
        if self.core_pos is not None:
            kill_core = self.core_pos[removed_base]
            kill_core = kill_core[kill_core >= 0]
            self.core_alive -= kill_core.size
            src, dst = added % n, added // n
            # the destinations that may no longer stay outside S
            seeds = dst[~self.in_s[dst]
                        & (self.in_s[src] | (self.levels[src]
                                             >= self.levels[dst]))]
            joined = self._close(seeds)
            moving = self.edges.out_base(joined)
            kill = np.concatenate([kill, self.full_pos[moving]])
            self.moved = np.union1d(self.moved[~np.isin(self.moved, removed)],
                                    self.edges.base[moving])
            inserted = np.union1d(inserted, self.moved)
            # within each destination's run, the sources outside S first
            from_s = self.in_s[inserted % n]
            order = np.argsort(2 * (inserted // n) + from_s, kind="stable")
            inserted, from_s = inserted[order], from_s[order]
        fits = (inserted.size <= self.slack
                and max(kill.size, joined.size) <= self.slack
                and touched.size <= 2 * self.slack)
        if not fits:
            self.__init__(self.edges.graph())
            return True
        full = self._update(inserted, kill)
        core = None
        if self.core_pos is not None:
            core = self._update(inserted[from_s], kill_core)
            self.core_inserted = int(from_s.sum())
        self.ctx, self.degrees = _relaid(
            self.ctx, self.main_last, self.core_main_last, full, core,
            _padded(joined, self.slack, n), self.degrees,
            _padded(touched, 2 * self.slack, n),
            _padded(self.edges.out_deg[touched], 2 * self.slack, 0),
            _padded(self.edges.in_deg[touched], 2 * self.slack, 0))
        return False

    def _update(self, inserted: np.ndarray, kill: np.ndarray) -> _ListUpdate:
        n, cap = self.n, self.slack
        return _ListUpdate(src=_padded(inserted % n, cap, n),
                           dst=_padded(inserted // n, cap, n),
                           kill=_padded(kill, cap, 2 ** 31 - 1))

    def _close(self, seeds: np.ndarray) -> np.ndarray:
        """Add ``seeds`` and every vertex they reach to ``S``; returns the
        vertices that joined."""
        joined = []
        front = np.unique(seeds)
        while front.size:
            self.in_s[front] = True
            joined.append(front)
            reach = np.unique(self.edges.out_edges(front) // self.n)
            front = reach[~self.in_s[reach]]
        return np.concatenate(joined) if joined else np.empty(0, np.int64)
