"""The seeded stand-in graphs have the published sizes on every seed."""
import numpy as np

import graphgen
from conftest import SMALL


def _stats(src, dst, n):
    out_deg = np.bincount(src, minlength=n)
    return src.size, int((out_deg == 0).sum())


def test_exact_sizes_sorted_and_unique():
    n = SMALL["n"]
    for seed in (0, 1, 2**40 + 3):
        src, dst, _ = graphgen.run_edges(SMALL, seed)
        m, dangling = _stats(src, dst, n)
        assert m == SMALL["m"] and dangling == SMALL["n_dangling"]
        key = dst * n + src
        assert np.all(np.diff(key) > 0)  # dst-major, no duplicates
        assert src.min() >= 0 and src.max() < n and dst.max() < n


def test_seed_relabels_the_same_graph():
    n = SMALL["n"]
    *a, perm_a = graphgen.run_edges(SMALL, 5)
    *b, perm_b = graphgen.run_edges(SMALL, 6)
    assert not np.array_equal(a[0], b[0])
    # vertex perm_a[i] of run 5 is vertex perm_b[i] of run 6
    to_b = np.empty(n, np.int64)
    to_b[perm_a] = perm_b
    key_b = np.sort(to_b[a[1]] * n + to_b[a[0]])
    assert np.array_equal(key_b, b[1] * n + b[0])
    *again, _ = graphgen.run_edges(SMALL, 5)
    assert np.array_equal(a[0], again[0]) and np.array_equal(a[1], again[1])
