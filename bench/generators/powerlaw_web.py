"""A stat-matched web graph: exactly a crawl's n, m and dangling count.

The configuration names a published crawl (``n`` vertices, ``m`` edges,
``n_dangling`` vertices without out-edges).  The crawl itself cannot be
shipped, so this builds a synthetic stand-in with exactly those three
numbers:

  * ``n_dangling`` vertices get no out-edge; every other vertex gets one
    out-edge first, so the dangling count is exact;
  * the remaining edges draw their sources from weights ``r**-gamma_out``
    over the non-dangling vertices and their destinations from weights
    ``r**-gamma_in`` over all vertices (``r`` a shuffled rank; the shape
    of ``repro.graph.web_graph``), oversampled, deduplicated, and trimmed
    to exactly ``m`` at random.

Weights ``r**-gamma`` give a degree distribution with a power-law tail of
exponent ``1 + 1 / gamma``: ``gamma_in`` 0.9 gives 2.1, the in-degree
exponent Broder et al. measured on a web crawl ("Graph structure in the
Web", WWW 2000).  Self-loops are kept, duplicates are not.  Everything is
host numpy in bulk, from ``dataset_seed``.
"""
from __future__ import annotations

import numpy as np


def _powerlaw(size: int, gamma: float, rng: np.random.Generator) -> np.ndarray:
    w = np.arange(1, size + 1, dtype=np.float64) ** (-float(gamma))
    rng.shuffle(w)
    return w / w.sum()


def base_edges(config: dict, *, dataset_seed: int, gamma_in: float,
               gamma_out: float, oversample: float):
    """``(src, dst)`` of a graph with exactly the configuration's ``n``,
    ``m`` and ``n_dangling``, sorted dst-major, unique, int64."""
    n, m, n_dangling = config["n"], config["m"], config["n_dangling"]
    rng = np.random.default_rng(dataset_seed)
    perm = rng.permutation(n)
    active = perm[n_dangling:]
    if m < active.size:
        raise ValueError(f"m={m} cannot give each of {active.size} "
                         f"non-dangling vertices an out-edge")
    w_out = _powerlaw(active.size, gamma_out, rng)
    w_in = _powerlaw(n, gamma_in, rng)
    n64 = np.int64(n)
    floor = rng.choice(n, size=active.size, p=w_in) * n64 + active
    extra = m - active.size
    draw = int(extra * oversample) + 8
    src = active[rng.choice(active.size, size=draw, p=w_out)]
    dst = rng.choice(n, size=draw, p=w_in)
    key = np.setdiff1d(np.unique(dst * n64 + src), floor, assume_unique=True)
    if key.size < extra:
        raise ValueError(f"oversample {oversample} left {key.size} distinct "
                         f"extra edges, {extra} needed")
    key = key[rng.choice(key.size, size=extra, replace=False)]
    key = np.sort(np.concatenate([floor, key]))
    return key % n64, key // n64
