"""Graph container used by every sparse layer in the framework.

The representation is a dst-sorted COO edge list plus per-vertex degree
metadata.  This single structure backs:

  * the paper's ITA / power-method / forward-push / Monte-Carlo solvers
    (``repro.core``),
  * the 1-D / 2-D edge partitioners used by the distributed runtime
    (``repro.graph.partition``).

Design notes (TPU adaptation, see DESIGN.md §2):
  - Edges are sorted by destination so that the scatter-add of the push step
    becomes a *sorted* ``jax.ops.segment_sum`` — contention-free and
    deterministic, unlike the paper's CPU atomic adds.
  - All arrays are int32: vertex counts in scope (≤ ~2.5M for ogb_products)
    and edge counts (≤ ~115M) fit comfortably; int32 halves index bandwidth
    versus int64, which matters because ITA's push is bandwidth-bound.
  - The structure is a pytree (NamedTuple of arrays + static ints via
    aux data), so it can be donated/sharded by pjit directly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Graph", "Degrees", "LiveEdges", "graph_from_edges",
           "apply_edge_delta", "validate_graph"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """A directed graph in dst-sorted COO form.

    Attributes
    ----------
    src, dst : int32[m]
        Edge endpoints, sorted by (dst, src).  Edge ``(src[k], dst[k])``
        means information flows ``src[k] -> dst[k]``.
    out_deg : int32[n]
        Out-degree per vertex.  ``out_deg[i] == 0``  ⇔  dangling vertex.
    in_deg : int32[n]
        In-degree per vertex.   ``in_deg[i] == 0``   ⇔  unreferenced vertex.
    n, m : static ints (aux data, not traced).
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    out_deg: jnp.ndarray
    in_deg: jnp.ndarray
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))

    # ---- derived masks (cheap, computed on demand; kept out of the pytree) ----
    @property
    def dangling_mask(self) -> jnp.ndarray:
        """bool[n] — vertices with no out-edges (the paper's V_D)."""
        return self.out_deg == 0

    @property
    def unreferenced_mask(self) -> jnp.ndarray:
        """bool[n] — vertices with no in-edges (exit after one push)."""
        return self.in_deg == 0

    @property
    def n_dangling(self) -> jnp.ndarray:
        return jnp.sum(self.dangling_mask.astype(jnp.int32))

    @property
    def avg_degree(self) -> float:
        return self.m / max(self.n, 1)

    @property
    def is_undirected(self) -> bool:
        """True iff the edge set is symmetric (every (u, v) has its (v, u)).

        The detectable structural property the planner exploits (see
        ``choose_backend``): on a symmetric edge set the priority-ordered
        diffusion schedule ("frontier_priority") declares a cost discount,
        because descending-residual sweeps drain mass along both edge
        directions at once instead of round-tripping it.  Host-side O(m)
        check, cached outside the pytree like the layout caches — the
        engine transplants the cache across ``device_put`` copies of the
        same edge set, and :func:`apply_edge_delta` returns a fresh graph
        so a delta always recomputes.  Empty graphs are trivially
        symmetric; self-loops are their own reverse.
        """
        cached = getattr(self, "_undirected_cache", None)
        if cached is None:
            src = np.asarray(self.src, dtype=np.int64)
            dst = np.asarray(self.dst, dtype=np.int64)
            fwd = dst * np.int64(self.n) + src  # sorted-unique by invariant
            rev = np.sort(src * np.int64(self.n) + dst)
            cached = bool(np.array_equal(fwd, rev))
            object.__setattr__(self, "_undirected_cache", cached)
        return cached

    @property
    def reference_levels(self) -> np.ndarray:
        """int32[n] — each vertex's weak-unreferenced level (paper §III),
        -1 for the referenced core.

        Level 0 is an unreferenced vertex (no in-edges); level k a vertex
        whose in-neighbours all have levels below k.  Under ITA a vertex of
        level k receives nothing after round k, whatever h₀ is.  Vertices
        of no finite level (on a cycle or a self-loop, or reached from one)
        form the referenced core, which is closed under out-edges.  Peeled
        Kahn-style on the host: each pass takes off the vertices whose
        in-edges all come from vertices already peeled, in K + 1 passes of
        O(m) for a deepest level K.  Cached outside the pytree like
        :attr:`is_undirected`.
        """
        cached = getattr(self, "_levels_cache", None)
        if cached is None:
            src, dst = np.asarray(self.src), np.asarray(self.dst)
            remaining = np.asarray(self.in_deg, np.int64).copy()
            cached = np.full(self.n, -1, np.int32)
            peel = np.flatnonzero(remaining == 0)
            level = 0
            while peel.size:
                cached[peel] = level
                peeled_now = np.zeros(self.n, bool)
                peeled_now[peel] = True
                remaining -= np.bincount(dst[peeled_now[src]],
                                         minlength=self.n)
                peel = np.flatnonzero((remaining == 0) & (cached < 0))
                level += 1
            object.__setattr__(self, "_levels_cache", cached)
        return cached

    @property
    def graph_version(self) -> int:
        """Monotone edge-set version, bumped by :func:`apply_edge_delta`.

        Freshly built graphs are version 0; every delta produces a graph
        stamped one higher than its parent.  The engine exposes this as
        ``PageRankEngine.graph_version`` and the result cache
        (``repro.core.cache``) keys entries on it, so an answer computed
        against an older edge set can never be served verbatim after a
        delta — it is either revalidated or recomputed.  Stored outside
        the pytree (like the layout caches): jit/vmap boundaries see only
        the edge arrays, and flattened copies reset to 0.
        """
        return int(getattr(self, "_graph_version", 0))

    def inv_out_deg(self, dtype=jnp.float64) -> jnp.ndarray:
        """1/deg with 0 at dangling vertices (the raw-P column scale)."""
        deg = self.out_deg.astype(dtype)
        return jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 0.0)

    def stats(self) -> dict:
        """Host-side summary matching the paper's Table 3 columns."""
        return dict(
            n=self.n,
            m=self.m,
            nd=int(jax.device_get(self.n_dangling)),
            n_unref=int(jax.device_get(jnp.sum(self.unreferenced_mask))),
            deg=round(self.avg_degree, 2),
        )

    # ---- cached layouts -----------------------------------------------------
    def ell(self, *, widths: tuple = (8, 32, 128), row_align: int = 8):
        """Bucketed-ELL view of this graph (``repro.sparse.ell``), cached.

        Conversion is host-side O(m) work; solvers and kernels that consume
        the ELL layout (the ``"ell"`` step backend) go
        through here so the cost is paid once per (graph, widths) pair.
        The cache lives outside the pytree: jit/vmap boundaries see only
        the edge arrays, and flattened copies simply rebuild on first use.
        """
        key = (tuple(sorted(widths)), int(row_align))
        cache = getattr(self, "_ell_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_ell_cache", cache)
        if key not in cache:
            from ..sparse.ell import ell_from_graph
            cache[key] = ell_from_graph(self, widths=key[0], row_align=row_align)
        return cache[key]

    def ell_partitioned(self, C: int, *, widths: tuple = (8, 32, 128),
                        row_align: int = 8):
        """C-way column-partitioned ELL view (``repro.sparse.ELLCols``),
        cached per (C, widths, row_align).

        The vertex-sharded serving layout: block j holds the ELL bucketing
        of the edges whose *source* lies in vertex block [j·nc, (j+1)·nc)
        — the ``partition_cols`` geometry — stacked into [C, ...] arrays
        so a mesh "model" axis shards them with uniform per-device shapes.
        Same caching contract as :meth:`ell`: host-side O(m) conversion
        paid once, cache invisible to the pytree, and a fresh cache pinned
        by :func:`apply_edge_delta` so a delta never serves stale blocks.
        """
        key = (int(C), tuple(sorted(widths)), int(row_align))
        cache = getattr(self, "_ell_part_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_ell_part_cache", cache)
        if key not in cache:
            from ..sparse.ell import ell_cols_from_graph
            cache[key] = ell_cols_from_graph(self, key[0], widths=key[1],
                                             row_align=row_align)
        return cache[key]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Degrees:
    """A graph's degrees without its edges.

    What the ITA loop reads of a graph whose edges live in a prepared push
    layout (the dense backend's layout for edge deltas,
    ``repro.core.live``): it stands in for the :class:`Graph` there, with
    the same fields and masks, and its shapes do not change when edges do.
    """

    out_deg: jnp.ndarray
    in_deg: jnp.ndarray
    n: int = dataclasses.field(metadata=dict(static=True))

    dangling_mask = Graph.dangling_mask
    unreferenced_mask = Graph.unreferenced_mask
    inv_out_deg = Graph.inv_out_deg


def graph_from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    n: int,
    *,
    dedup: bool = True,
    drop_self_loops: bool = False,
) -> Graph:
    """Build a dst-sorted :class:`Graph` from host edge arrays.

    Host-side (numpy) on purpose: graph construction is data-pipeline work,
    done once per dataset; the resulting arrays are device-resident.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError(f"src/dst must be equal-length 1-D, got {src.shape} {dst.shape}")
    if src.size:
        if src.min() < 0 or src.max() >= n or dst.min() < 0 or dst.max() >= n:
            raise ValueError("edge endpoint out of range")
    if drop_self_loops:
        keep = src != dst
        src, dst = src[keep], dst[keep]
    if dedup and src.size:
        # unique over (dst, src) pairs; also yields the dst-major sort we want.
        key = dst * np.int64(n) + src
        key = np.unique(key)
        dst = (key // n).astype(np.int32)
        src = (key % n).astype(np.int32)
    else:
        order = np.lexsort((src, dst))
        src = src[order].astype(np.int32)
        dst = dst[order].astype(np.int32)
    out_deg = np.bincount(src, minlength=n).astype(np.int32)
    in_deg = np.bincount(dst, minlength=n).astype(np.int32)
    return Graph(
        src=jnp.asarray(src),
        dst=jnp.asarray(dst),
        out_deg=jnp.asarray(out_deg),
        in_deg=jnp.asarray(in_deg),
        n=int(n),
        m=int(src.size),
    )


def _pairs(edges) -> np.ndarray:
    """``(src, dst)`` pairs as int64 [k, 2]."""
    if not isinstance(edges, np.ndarray):
        edges = list(edges)
    return np.asarray(edges, dtype=np.int64).reshape(-1, 2)


def _search(sorted_keys: np.ndarray, keys: np.ndarray):
    """Position of each key in ``sorted_keys``, and whether it is there."""
    if not sorted_keys.size:
        return np.zeros(keys.shape, np.int64), np.zeros(keys.shape, bool)
    pos = np.minimum(np.searchsorted(sorted_keys, keys), sorted_keys.size - 1)
    return pos, sorted_keys[pos] == keys


class LiveEdges:
    """A graph's edge set as it takes edge deltas, in O(delta) host work.

    The edges it started from stay in their sorted key array (``base``,
    key ``dst * n + src``, the :class:`Graph` order); a deleted one is
    only cleared in ``alive``.  Inserted edges are the sorted keys
    ``inserted``.  The degrees, ``m`` and the dangling and unreferenced
    counts are kept up to date.  :meth:`graph` materializes the current
    edge set as a :class:`Graph`, which is O(m) and only on demand.
    """

    def __init__(self, g: "Graph"):
        self.n = g.n
        src = np.asarray(g.src, dtype=np.int64)
        self.base = np.asarray(g.dst, dtype=np.int64) * g.n + src
        self.alive = np.ones(g.m, bool)
        self.inserted = np.empty(0, np.int64)
        self.out_deg = np.array(g.out_deg, dtype=np.int32)
        self.in_deg = np.array(g.in_deg, dtype=np.int32)
        self.m = g.m
        self.n_dangling = int(np.count_nonzero(self.out_deg == 0))
        self.n_unreferenced = int(np.count_nonzero(self.in_deg == 0))
        self.version = g.graph_version
        self._by_src = None

    def _find(self, keys: np.ndarray):
        """Base index of each key (-1 where none is alive), and whether
        each key is an inserted edge."""
        pos, hit = _search(self.base, keys)
        if self.base.size:
            hit &= self.alive[pos]
        return np.where(hit, pos, -1), _search(self.inserted, keys)[1]

    def apply(self, add=(), remove=()):
        """Remove then add edges; returns ``(removed_base, removed,
        added)``: the base indices deleted, and the keys removed and
        added.  Removing an absent edge, adding a present one, a
        duplicate in either list or an endpoint out of range raises
        ``ValueError`` and changes nothing (a silent no-op would
        desynchronize a session's residual state from its graph)."""
        n = np.int64(self.n)
        add, remove = _pairs(add), _pairs(remove)
        for name, arr in (("add", add), ("remove", remove)):
            if arr.size and (arr.min() < 0 or arr.max() >= n):
                raise ValueError(f"{name} edge endpoint out of range for "
                                 f"n={self.n}")
        rkey = remove[:, 1] * n + remove[:, 0]
        if np.unique(rkey).size != rkey.size:
            raise ValueError("duplicate edges in remove list")
        rbase, rins = self._find(rkey)
        missing = (rbase < 0) & ~rins
        if missing.any():
            raise ValueError(f"cannot remove absent edges: "
                             f"{remove[missing][:4].tolist()}")
        akey = add[:, 1] * n + add[:, 0]
        if np.unique(akey).size != akey.size:
            raise ValueError("duplicate edges in add list")
        abase, ains = self._find(akey)
        present = ((abase >= 0) | ains) & ~np.isin(akey, rkey)
        if present.any():
            raise ValueError(f"cannot add existing edges: "
                             f"{add[present][:4].tolist()}")
        removed_base = rbase[rbase >= 0]
        self.alive[removed_base] = False
        kept = self.inserted[~np.isin(self.inserted, rkey[rins])]
        self.inserted = np.union1d(kept, akey)
        touched = np.unique(np.concatenate([add.ravel(), remove.ravel()]))
        was_dangling = np.count_nonzero(self.out_deg[touched] == 0)
        was_unref = np.count_nonzero(self.in_deg[touched] == 0)
        np.add.at(self.out_deg, add[:, 0], 1)
        np.add.at(self.in_deg, add[:, 1], 1)
        np.subtract.at(self.out_deg, remove[:, 0], 1)
        np.subtract.at(self.in_deg, remove[:, 1], 1)
        self.n_dangling += (np.count_nonzero(self.out_deg[touched] == 0)
                            - was_dangling)
        self.n_unreferenced += (np.count_nonzero(self.in_deg[touched] == 0)
                                - was_unref)
        self.m += akey.size - rkey.size
        self.version += 1
        return removed_base, rkey, akey

    def out_base(self, vertices: np.ndarray) -> np.ndarray:
        """Indices into ``base`` of the live base out-edges of
        ``vertices``."""
        if self._by_src is None:  # CSR by source over the base, once
            order = np.argsort(self.base % self.n, kind="stable")
            offsets = np.zeros(self.n + 1, np.int64)
            np.cumsum(np.bincount(self.base % self.n, minlength=self.n),
                      out=offsets[1:])
            self._by_src = (order, offsets)
        order, offsets = self._by_src
        vertices = np.asarray(vertices, np.int64)
        count = offsets[vertices + 1] - offsets[vertices]
        shift = np.repeat(offsets[vertices] - np.cumsum(count) + count, count)
        idx = order[np.arange(count.sum()) + shift]
        return idx[self.alive[idx]]

    def out_edges(self, vertices: np.ndarray) -> np.ndarray:
        """Keys of the live out-edges of ``vertices``."""
        ins = self.inserted[np.isin(self.inserted % self.n, vertices)]
        return np.concatenate([self.base[self.out_base(vertices)], ins])

    def graph(self) -> "Graph":
        """The current edge set as a fresh :class:`Graph` (O(m)), stamped
        with the version: one per delta applied since ``g``."""
        key = np.union1d(self.base[self.alive], self.inserted)
        g = graph_from_edges(key % self.n, key // self.n, self.n,
                             dedup=False)
        # Monotone version stamp: the engine and the result cache key
        # prepared/cached state on it, so a delta'd graph is *visibly* a
        # different edge set even to layers that never inspect src/dst
        # (tests/test_cache.py::test_stale_entry_never_served_after_delta).
        object.__setattr__(g, "_graph_version", self.version)
        return g


def apply_edge_delta(g: Graph, add=(), remove=()) -> Graph:
    """New :class:`Graph` = ``g`` plus ``add`` minus ``remove`` edge lists.

    ``add``/``remove`` are iterables of ``(src, dst)`` pairs (or empty),
    validated as :meth:`LiveEdges.apply` validates them.  Host-side O(m)
    by design, like :func:`graph_from_edges` — dynamic-graph mutation is
    data-pipeline work; the incremental solver (``repro.core.dynamic``)
    then corrects the ranking on device without a from-scratch solve.  A
    session that takes many deltas keeps a :class:`LiveEdges` instead
    (``repro.core.live``).  The new graph starts with no layout caches,
    so nothing can inherit the OLD edge set's ELL buckets
    (tests/test_query_plan.py::TestDeltaEllCache,
    tests/test_ell_sharded.py::test_delta_pins_fresh_partition_cache).
    """
    live = LiveEdges(g)
    live.apply(add=add, remove=remove)
    g_new = live.graph()
    object.__setattr__(g_new, "_ell_cache", {})
    object.__setattr__(g_new, "_ell_part_cache", {})
    object.__setattr__(g_new, "_part_cols_cache", {})
    return g_new


def validate_graph(g: Graph) -> None:
    """Cheap invariants; used by tests and the data pipeline."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    assert src.shape == (g.m,) and dst.shape == (g.m,)
    assert g.out_deg.shape == (g.n,) and g.in_deg.shape == (g.n,)
    assert int(np.sum(np.asarray(g.out_deg))) == g.m
    assert int(np.sum(np.asarray(g.in_deg))) == g.m
    if g.m:
        assert np.all(np.diff(dst.astype(np.int64) * g.n + src) > 0), "edges not dst-sorted/unique"


def csr_from_graph(g: Graph, by: str = "src") -> tuple[np.ndarray, np.ndarray]:
    """Host-side CSR (offsets, indices).

    ``by='src'`` gives out-neighbour lists (random-walk / Monte-Carlo use);
    ``by='dst'`` gives in-neighbour lists (pull-style SpMV).
    """
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    if by == "src":
        order = np.argsort(src, kind="stable")
        keys, vals = src[order], dst[order]
        deg = np.asarray(g.out_deg)
    elif by == "dst":
        keys, vals = dst, src  # already dst-sorted
        deg = np.asarray(g.in_deg)
    else:
        raise ValueError(by)
    offsets = np.zeros(g.n + 1, dtype=np.int64)
    np.cumsum(deg, out=offsets[1:])
    del keys
    return offsets, vals.astype(np.int32)
