"""The copied float64 reference against the program's own reference."""
import numpy as np
import pytest

import graphgen
import reference
from conftest import SMALL


@pytest.fixture(scope="module")
def edges():
    return graphgen.run_edges(SMALL, 12345)[:2]


def _program_reference(src, dst, n, p=None):
    import jax
    import jax.numpy as jnp
    from repro.core import reference_pagerank
    from repro.graph import graph_from_edges

    jax.config.update("jax_enable_x64", True)
    g = graph_from_edges(src, dst, n, dedup=False)
    return np.asarray(reference_pagerank(
        g, p=None if p is None else jnp.asarray(p)), np.float64)


def test_global_ranking_matches_reference_pagerank(edges):
    src, dst = edges
    n = SMALL["n"]
    ref, it = reference.pagerank_rows(src, dst, n, np.full((1, n), 1.0 / n),
                                      c=0.85)
    assert ref.shape == (1, n) and it > 100
    # both stop at an l2 step of 1e-14; they differ in summation order only
    assert np.abs(ref[0] - _program_reference(src, dst, n)).sum() < 1e-12


def test_personalized_rows_match_one_at_a_time(edges):
    src, dst = edges
    n = SMALL["n"]
    seeds = [0, 17, int(np.argmax(np.bincount(dst, minlength=n)))]
    P = np.zeros((3, n))
    P[np.arange(3), seeds] = 1.0
    rows, _ = reference.pagerank_rows(src, dst, n, P, c=0.85)
    for i in range(3):
        alone = _program_reference(src, dst, n, P[i])
        assert np.abs(rows[i] - alone).sum() < 1e-12
        assert abs(rows[i].sum() - 1.0) < 1e-12


def test_topk_err_reads_ties_as_zero_and_errors_as_gaps():
    ref = np.array([0.1, 0.5, 0.2, 0.2, 0.0])
    # either tied vertex may be served
    assert reference.topk_err([1, 2, 3], ref[[1, 2, 3]], ref, 3) == 0.0
    assert reference.topk_err([1, 3, 2], ref[[1, 3, 2]], ref, 3) == 0.0
    # a vertex that is not in the top-3 reads its gap
    assert reference.topk_err([1, 2, 0], ref[[1, 2, 0]], ref,
                              3) == pytest.approx(0.1)
    # a wrong score reads its gap
    assert reference.topk_err([1, 2, 3], [0.5, 0.2, 0.25], ref,
                              3) == pytest.approx(0.05)
    # the wrong number of vertices, or a vertex outside the graph, is inf
    assert reference.topk_err([1, 2], [0.5, 0.2], ref, 3) == np.inf
    assert reference.topk_err([1, 2, 9], [0.5, 0.2, 0.2], ref, 3) == np.inf


def test_l1_bound_is_the_chip_smoke_bound():
    # 2 c xi / (1 - c) + c / (1 - c) sqrt(n) tol at web-Google size
    assert reference.l1_bound(875713, c=0.85, xi=1e-10) == pytest.approx(
        1.186e-9, rel=1e-3)
