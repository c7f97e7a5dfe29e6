"""The ranking CLI, ``python -m repro.launch.pagerank``: each branch of
``main(argv)`` at a small ``--scale``, its exit code and what it prints.

Where a branch solves, the values it prints are checked against
``reference_pagerank`` on the same graph (``paper_dataset`` at the CLI's
default dataset and seed).  ``--explain`` prints the plan and solves nothing.
"""
import ast

import numpy as np
import pytest

from repro.core import one_hot_personalizations, reference_pagerank
from repro.graph import paper_dataset
from repro.launch.pagerank import main

SCALE = "0.002"

# The solvers stop at the CLI's default xi = 1e-10 with at most that much
# unsent per vertex: on this graph the printed values were within 5.1e-11
# of the reference (power; ITA 7.7e-13), so 1e-9 leaves a factor of 20.
EXACT = 1e-9
# Forward push leaves up to xi of residual at every vertex, dangling ones
# included, and settles only (1 - c) of what it pushes: 6.9e-9 on this graph.
PUSH = 1e-7
# Monte Carlo is a sample (16 seeded walks a vertex): 3.5e-4 on this graph,
# against top values of 0.01 to 0.075.
SAMPLE = 2e-3


@pytest.fixture(scope="module")
def graph():
    return paper_dataset("web-Google", scale=float(SCALE), seed=0)


@pytest.fixture(scope="module")
def pi_ref(graph):
    return np.asarray(reference_pagerank(graph))


def _printed_list(out: str, label: str) -> list:
    """The ``[(vertex, value), ...]`` list printed after ``label``."""
    line = next(ln for ln in out.splitlines() if label in ln)
    return ast.literal_eval(line.split(label, 1)[1].strip())


def _check_top5(out: str, pi_ref: np.ndarray, tol: float, exact_order: bool):
    top = _printed_list(out, "top-5 vertices:")
    idx = [int(i) for i, _ in top]
    vals = np.array([v for _, v in top])
    assert len(idx) == 5 and np.all(np.diff(vals) <= 0), top
    np.testing.assert_allclose(vals, pi_ref[idx], rtol=0, atol=tol)
    want = [int(i) for i in np.argsort(-pi_ref)[:5]]
    assert idx == want if exact_order else idx[0] == want[0], (idx, want)


@pytest.mark.parametrize("method,tol,exact_order", [
    ("ita", EXACT, True),
    ("power", EXACT, True),
    ("forward_push", PUSH, True),
    ("ifp", EXACT, True),
    ("monte_carlo", SAMPLE, False),
])
def test_rank_methods(capsys, pi_ref, method, tol, exact_order):
    assert main(["--scale", SCALE, "--method", method]) == 0
    out = capsys.readouterr().out
    assert f"method={method}" in out and "converged=True" in out, out
    _check_top5(out, pi_ref, tol, exact_order)


@pytest.mark.parametrize("method", ["ita", "power"])
def test_batched_ppr(capsys, graph, method):
    assert main(["--scale", SCALE, "--method", method, "--batch", "4"]) == 0
    out = capsys.readouterr().out
    assert "batched PPR:" in out and "'converged': True" in out, out
    rows = [ln for ln in out.splitlines() if ln.strip().startswith("seed ")]
    assert len(rows) == 4, out
    for ln in rows:
        s = int(ln.split()[1].rstrip(":"))
        top = ast.literal_eval(ln.split("top-3", 1)[1].strip())
        ref = np.asarray(reference_pagerank(
            graph, p=one_hot_personalizations(graph, [s])[0]))
        # a one-hot row ranks its own seed first
        assert int(top[0][0]) == s, (s, top)
        np.testing.assert_allclose([v for _, v in top],
                                   ref[[int(i) for i, _ in top]],
                                   rtol=0, atol=EXACT)


def test_explain_prints_plan_and_does_not_solve(capsys):
    assert main(["--scale", SCALE, "--explain"]) == 0
    out = capsys.readouterr().out
    assert "plan[rank]: backend=dense path=while-loop method=ita" in out, out
    assert "why:" in out and "cost source:" in out, out
    assert "top-5 vertices:" not in out and "iterations=" not in out, out


# ``--partition 1d`` is left out: ita_distributed_1d calls its shard_map
# step eagerly, about 1.7 s a round on one CPU device, 243 s for this graph.
def test_partition_2d_on_one_device(capsys, pi_ref):
    assert main(["--scale", SCALE, "--partition", "2d"]) == 0
    out = capsys.readouterr().out
    assert "method=ita_2d" in out and "converged=True" in out, out
    _check_top5(out, pi_ref, EXACT, True)
