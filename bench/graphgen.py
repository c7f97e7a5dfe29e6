"""The edge list one run solves: a configuration's base graph, relabelled.

The configuration's ``generator`` names its kind, the file
``bench/generators/<kind>.py``, whose ``base_edges(config, **params)``
builds the base graph from the rest of ``generator``'s keys (a key it
does not take is an error).  A run's ``--seed`` relabels the base graph's
vertices by a random permutation: every run then solves the same graph up
to the order of its vertices and edges, so every seed does the same work
and compiles the same shapes.
"""
from __future__ import annotations

import numpy as np

import plugins

__all__ = ["relabel", "run_edges"]


def relabel(src, dst, n: int, seed: int):
    """The same graph with vertex ids permuted from ``seed``, dst-major.

    Returns ``(src, dst, perm)``: vertex ``i`` of the input is ``perm[i]``.
    """
    perm = np.random.default_rng(seed).permutation(n).astype(np.int64)
    key = np.sort(perm[dst] * np.int64(n) + perm[src])
    return key % n, key // n, perm


def run_edges(config: dict, seed: int):
    """The base graph of ``config``, relabelled from ``seed``.
    Returns ``(src, dst, perm)``."""
    params = dict(config["generator"])
    kind = params.pop("kind")
    gen = plugins.load("generators", kind)
    src, dst = plugins.call(gen.base_edges, f"generator {kind!r}", config,
                            **params)
    return relabel(src, dst, config["n"], seed)
