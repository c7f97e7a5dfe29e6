"""Synthetic directed-graph generators.

The paper evaluates on four web crawls (Table 3).  Those datasets are not
shippable here, so the data pipeline generates *stat-matched* synthetic
graphs: same vertex count, edge count, dangling-vertex count and average
degree, with power-law in-degrees (web-like).  The generators are the same
code used for property tests (hypothesis sweeps the knobs) and for the
scaled-down CPU benchmark graphs.

Everything is host-side numpy with an explicit seed — deterministic,
reproducible, shard-friendly (generation is rank-0 work in the launcher).
"""
from __future__ import annotations

import numpy as np

from .structure import Graph, graph_from_edges

__all__ = [
    "web_graph",
    "erdos_renyi",
    "random_dag",
    "TABLE3_PRESETS",
    "paper_dataset",
]


def _powerlaw_weights(n: int, gamma: float, rng: np.random.Generator) -> np.ndarray:
    """Zipf-ish attachment weights with random permutation (no id bias)."""
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-gamma)
    rng.shuffle(w)
    return w / w.sum()


def web_graph(
    n: int,
    m: int,
    *,
    dangling_frac: float = 0.1,
    unref_boost: float = 0.0,
    gamma_in: float = 0.9,
    gamma_out: float = 0.7,
    seed: int = 0,
) -> Graph:
    """Power-law directed graph with a controlled dangling-vertex fraction.

    Construction:
      1. choose ``n_d = dangling_frac * n`` vertices to have out-degree 0;
      2. distribute the m edge *sources* over the remaining vertices with
         power-law(gamma_out) weights (heavy-tailed out-degrees);
      3. draw edge *destinations* from power-law(gamma_in) weights over all
         vertices — the tail of that distribution naturally produces
         unreferenced vertices (paper's "special vertices"); ``unref_boost``
         re-weights a random subset to zero to force more of them.

    Self-loops are kept (the constructive definition handles them — §III),
    duplicate edges are merged, so the realized m can be slightly below the
    requested m; generators compensate by oversampling 3%.
    """
    if not 0 <= dangling_frac < 1:
        raise ValueError("dangling_frac in [0,1)")
    rng = np.random.default_rng(seed)
    n_d = int(round(dangling_frac * n))
    perm = rng.permutation(n)
    non_dangling = perm[n_d:]

    w_out = _powerlaw_weights(non_dangling.size, gamma_out, rng)
    w_in = _powerlaw_weights(n, gamma_in, rng)
    if unref_boost > 0:
        kill = rng.random(n) < unref_boost
        w_in = np.where(kill, 0.0, w_in)
        w_in /= w_in.sum()

    m_draw = int(m * 1.03) + 8
    src = non_dangling[rng.choice(non_dangling.size, size=m_draw, p=w_out)]
    dst = rng.choice(n, size=m_draw, p=w_in)
    g = graph_from_edges(src, dst, n, dedup=True)
    # Trim to at most m edges (keep determinism: drop a random subset).
    if g.m > m:
        keep = np.sort(rng.choice(g.m, size=m, replace=False))
        g = graph_from_edges(np.asarray(g.src)[keep], np.asarray(g.dst)[keep], n, dedup=False)
    return g


def erdos_renyi(n: int, m: int, *, seed: int = 0) -> Graph:
    """Uniform random directed graph (few special vertices — the control)."""
    rng = np.random.default_rng(seed)
    m_draw = int(m * 1.05) + 8
    src = rng.integers(0, n, size=m_draw)
    dst = rng.integers(0, n, size=m_draw)
    g = graph_from_edges(src, dst, n, dedup=True)
    if g.m > m:
        keep = np.sort(rng.choice(g.m, size=m, replace=False))
        g = graph_from_edges(np.asarray(g.src)[keep], np.asarray(g.dst)[keep], n, dedup=False)
    return g


def random_dag(n: int, m: int, *, seed: int = 0) -> Graph:
    """Random DAG (edges only from lower to higher topological id).

    DAGs maximise the paper's "weak unreferenced vertex" cascade: once the
    sources converge, convergence sweeps down the order and ITA's active set
    collapses — the best case for Formula (15).
    """
    rng = np.random.default_rng(seed)
    m_draw = int(m * 1.1) + 8
    a = rng.integers(0, n, size=m_draw)
    b = rng.integers(0, n, size=m_draw)
    keep = a != b
    a, b = a[keep], b[keep]
    src = np.minimum(a, b)
    dst = np.maximum(a, b)
    g = graph_from_edges(src, dst, n, dedup=True)
    if g.m > m:
        keep = np.sort(rng.choice(g.m, size=m, replace=False))
        g = graph_from_edges(np.asarray(g.src)[keep], np.asarray(g.dst)[keep], n, dedup=False)
    return g


# ---------------------------------------------------------------------------
# Paper Table 3 presets — full-size stats, and a
# `scale` knob so CPU benchmarks run the same *shape* of graph smaller.
# ---------------------------------------------------------------------------
TABLE3_PRESETS: dict[str, dict] = {
    # name:                n,        m,        nd,     deg
    "web-Stanford": dict(n=281_903, m=2_312_497, nd=172, deg=8.21),
    "Stanford-Berkeley": dict(n=683_446, m=7_583_376, nd=68_062, deg=12.32),
    "web-Google": dict(n=875_713, m=5_105_039, nd=136_259, deg=6.90),
    "in-2004": dict(n=1_382_870, m=16_917_053, nd=282_268, deg=15.37),
}


def paper_dataset(name: str, *, scale: float = 1.0, seed: int = 0) -> Graph:
    """Stat-matched synthetic stand-in for one of the paper's datasets.

    ``scale`` shrinks n and m proportionally (dangling fraction preserved),
    so the CPU reproduction runs the paper's graph *shapes* at tractable
    size; ``scale=1.0`` is the published size.
    """
    p = TABLE3_PRESETS[name]
    n = max(int(p["n"] * scale), 64)
    m = max(int(p["m"] * scale), 4 * n)
    dangling_frac = p["nd"] / p["n"]
    return web_graph(n, m, dangling_frac=dangling_frac, seed=seed)
