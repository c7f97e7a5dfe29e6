"""The check fails what it must: the float32 control and planted faults.

Each test drives a whole run of a cell at a 3,000-vertex cut on the CPU,
with the platform check pointed at the CPU and the program broken
underneath, and sees ``correct`` come out false.
"""
import dataclasses
import importlib

import jax.numpy as jnp
import pytest

import harness
from conftest import SMALL

RANK, PPR = "web-google.rank", "web-google.ppr-zipf"


def _run(cell, dtype=None, seed=4242):
    return harness.run(cell, seed, 0.5, False, platform="cpu", dtype=dtype,
                       config_override=SMALL)


@pytest.mark.parametrize("cell", [RANK, PPR])
def test_sound_program_is_correct(cell):
    result, checks = _run(cell)
    assert result["correct"] and result["failed"] == 0, checks


@pytest.mark.parametrize("cell", [RANK, PPR])
def test_float32_control_is_not_correct(cell):
    result, checks = _run(cell, dtype="float32")
    assert not result["correct"], checks
    assert result["failed"] >= 1


def test_ranking_altered_where_produced(monkeypatch):
    from repro.core.engine import PageRankEngine

    exec_rank = PageRankEngine._exec_rank

    def swapped(self, ep):
        res = exec_rank(self, ep)
        top, low = int(jnp.argmax(res.pi)), int(jnp.argmin(res.pi))
        pi = res.pi.at[top].set(res.pi[low]).at[low].set(res.pi[top])
        return dataclasses.replace(res, pi=pi)

    monkeypatch.setattr(PageRankEngine, "_exec_rank", swapped)
    result, checks = _run(RANK)
    assert not result["correct"] and checks["l1"]["value"] > 1e-4


def test_ranking_loop_returning_its_state_unchanged(monkeypatch):
    ita = importlib.import_module("repro.core.ita")  # the module, not ita()

    def unchanged(g, h0, pi_bar0, **kw):
        return (h0, pi_bar0, jnp.asarray(0, jnp.int32),
                jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32))

    monkeypatch.setattr(ita, "run_ita_loop", unchanged)
    result, checks = _run(RANK)
    assert not result["correct"] and checks["l1"]["value"] > 1e-3


def test_served_answer_altered_where_produced(monkeypatch):
    from repro.core.engine import PageRankEngine

    exec_topk = PageRankEngine._exec_topk

    def reordered(self, q, ep):
        res = exec_topk(self, q, ep)
        return res._replace(indices=res.indices[:, ::-1])

    monkeypatch.setattr(PageRankEngine, "_exec_topk", reordered)
    result, checks = _run(PPR)
    assert not result["correct"] and checks["topk_err"]["value"] > 1e-4


def test_ppr_loop_returning_its_state_unchanged(monkeypatch):
    import repro.core.batch as batch

    def unchanged(g, ctx, H0, c, xi, max_iter, backend):
        return (H0, jnp.zeros_like(H0), jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32))

    monkeypatch.setattr(batch, "_ita_batch_loop", unchanged)
    result, checks = _run(PPR)
    assert not result["correct"] and checks["row_l1"]["value"] > 0.1


@pytest.mark.parametrize("seed", [4242, 2**33 + 5, 77])
def test_half_of_the_batch_left_out(seed, monkeypatch):
    from repro.core.engine import PageRankEngine

    exec_topk = PageRankEngine._exec_topk

    def half(self, q, ep):
        src = jnp.asarray(q.sources)
        b = src.shape[0] // 2
        return exec_topk(self, dataclasses.replace(
            q, sources=jnp.concatenate([src[:b], src[:b]])), ep)

    monkeypatch.setattr(PageRankEngine, "_exec_topk", half)
    result, checks = _run(PPR, seed=seed)
    assert not result["correct"] and result["failed"] >= 1
