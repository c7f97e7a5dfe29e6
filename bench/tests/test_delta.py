"""The ``delta`` driver and the ``web-google-live.rank-delta`` cell off the
chip.

The cell runs here through ``harness.run`` at a 3,000-vertex cut on the
CPU, its deltas cut with the graph (30 inserts and 30 deletes, 0.15% of
m, the cell's churn), with the platform check pointed at the CPU.  The
check must read ``correct`` false for the float32 control and for each
fault a refresh can have: the previous version's pi, a delta skipped, a
delta's correction applied twice.  Nothing here is a device measurement.
"""
import dataclasses

import numpy as np
import pytest

import graphgen
import harness
import plugins
import roofline
import trace_reduce
from conftest import SMALL

CELL = "web-google-live.rank-delta"
CUT = dict(inserts=30, deletes=30)
SEED = 2**33 + 17


@pytest.fixture
def small(monkeypatch):
    """The cell with its deltas cut to the 3,000-vertex graph."""
    load = harness.load_cell

    def load_cut(name):
        cell = load(name)
        cell.traffic = dict(cell.traffic, **CUT)
        return cell

    monkeypatch.setattr(harness, "load_cell", load_cut)


def _run(dtype=None, seed=SEED, trace=False):
    return harness.run(CELL, seed, 0.5, trace, platform="cpu", dtype=dtype,
                       config_override=SMALL)


def _stream(seed, stream_seed=1, deltas=4):
    config = dict(harness.load_cell(CELL).config, **SMALL)
    graph = graphgen.run_edges(config, seed)
    delta = plugins.load("drivers", "delta")
    return graph, delta.run_stream(graph, config["n"], stream_seed,
                                   deletes=30, inserts=30, deltas=deltas)


def _in_base(graph, stream):
    """The stream mapped back to the base graph's ids."""
    base = np.argsort(graph[2])
    return [(base[a], base[r]) for a, r in stream]


def test_stream_is_deterministic_and_maps_through_the_relabelling():
    g1, s1 = _stream(11)
    _, s1_again = _stream(11)
    g2, s2 = _stream(2**35 + 3)
    _, other = _stream(11, stream_seed=2)
    for x, y in zip(s1, s1_again):
        assert all(np.array_equal(a, b) for a, b in zip(x, y))
    for x, y in zip(_in_base(g1, s1), _in_base(g2, s2)):
        assert all(np.array_equal(a, b) for a, b in zip(x, y))
    assert not np.array_equal(s1[0][0], other[0][0])
    # each delta: exact sizes, no self-loop, adds absent, removes present
    src, dst, _ = g1
    n = SMALL["n"]
    keys = set((dst * n + src).tolist())
    for add, remove in s1:
        assert add.shape == remove.shape == (30, 2)
        rk = set((remove[:, 1] * n + remove[:, 0]).tolist())
        ak = set((add[:, 1] * n + add[:, 0]).tolist())
        assert len(rk) == len(ak) == 30 and rk <= keys and not ak & keys
        assert np.all(add[:, 0] != add[:, 1])
        keys = (keys - rk) | ak


def test_versions_follow_the_stream():
    delta = plugins.load("drivers", "delta")
    (src, dst, _), stream = _stream(5)
    n = SMALL["n"]
    keys = set((dst * n + src).tolist())
    for (add, remove), got in zip(stream, delta.versions(src, dst, n,
                                                         stream)):
        keys = (keys - set((remove[:, 1] * n + remove[:, 0]).tolist())) \
            | set((add[:, 1] * n + add[:, 0]).tolist())
        assert sorted(keys) == got.tolist()


def test_sound_run_is_correct(small):
    result, checks = _run()
    assert result["correct"] and result["failed"] == 0, checks
    assert result["attempted"] >= 2
    assert checks["window_compiles"]["value"] == 0
    assert checks["relayouts"]["value"] == 0
    assert set(result["metrics"]) == {"rank_solve_s", "setup_s"}


def test_float32_control_is_not_correct(small):
    result, checks = _run(dtype="float32")
    assert not result["correct"] and result["failed"] >= 1, checks
    assert checks["l1"]["value"] > 1e-8


def _faulty(monkeypatch, fault):
    """Plant ``fault(engine, query, original, call)`` in the engine's
    delta path; ``call`` counts from 0, and call 1 is the window's first
    refresh (the mix warms up with one)."""
    from repro.core.engine import PageRankEngine

    original = PageRankEngine._exec_delta
    calls = []

    def planted(self, q):
        calls.append(q)
        return fault(self, q, original, len(calls) - 1)

    monkeypatch.setattr(PageRankEngine, "_exec_delta", planted)


def test_previous_versions_pi_is_not_correct(small, monkeypatch):
    held = []

    def late(self, q, original, call):
        held.append(original(self, q))
        return held[-2] if call >= 1 else held[-1]

    _faulty(monkeypatch, late)
    result, checks = _run()
    assert not result["correct"] and checks["l1"]["value"] > 1e-6


def test_skipped_delta_is_not_correct(small, monkeypatch):
    from repro.core import DeltaQuery

    skipped = {}

    def skip(self, q, original, call):
        if call == 1:
            skipped["add"] = {tuple(e) for e in np.asarray(q.add).tolist()}
            skipped["remove"] = {tuple(e)
                                 for e in np.asarray(q.remove).tolist()}
            return original(self, DeltaQuery())
        if skipped:  # what the skipped delta would have made valid
            add = [e for e in np.asarray(q.add).tolist()
                   if tuple(e) not in skipped["remove"]]
            remove = [e for e in np.asarray(q.remove).tolist()
                      if tuple(e) not in skipped["add"]]
            q = DeltaQuery(add=tuple(map(tuple, add)),
                           remove=tuple(map(tuple, remove)))
        return original(self, q)

    _faulty(monkeypatch, skip)
    result, checks = _run()
    assert not result["correct"] and result["failed"] >= 2
    assert checks["l1"]["value"] > 1e-6


def test_delta_applied_twice_is_not_correct(small, monkeypatch):
    def twice(self, q, original, call):
        before = self._state[0] if self._state is not None else None
        res = original(self, q)
        if call != 1:
            return res
        pi_bar, h = self._state
        pi_bar = 2 * pi_bar - before  # the delta's correction, twice
        self._state = (pi_bar, h)
        folded = pi_bar + h
        return dataclasses.replace(res, pi=folded / folded.sum())

    _faulty(monkeypatch, twice)
    result, checks = _run()
    assert not result["correct"] and checks["l1"]["value"] > 1e-6


def test_program_without_a_delta_layout_fails_at_set_up(small, monkeypatch):
    from repro.core.engine import PageRankEngine

    describe = PageRankEngine.describe

    def parents(self, include_plan=True):
        d = describe(self, include_plan=include_plan)
        d.pop("delta_capacity")
        return d

    monkeypatch.setattr(PageRankEngine, "describe", parents)
    with pytest.raises(harness.SetupError, match="no layout for edge deltas"):
        _run()


def test_traced_run_reports_every_per_layer_metric(small, monkeypatch):
    # the CPU trace has no device plane: stand one in with a busy time of
    # 0.9 of a 10 s window and 0.2 s of host time in the delta span
    summary = trace_reduce.TraceSummary(
        window_s=10.0, busy_s=9.0, devices=1, span_device_s={},
        span_host_s={"engine.delta.apply": 0.2},
        device_ops=[["op", 9.0]], idle_gaps=[["gap", 1.0]])
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda *a: summary)
    monkeypatch.setattr(trace_reduce, "load", lambda path: None)
    monkeypatch.setitem(roofline.PEAKS, "cpu", dict(hbm_bytes_per_s=1e9))
    result, _ = _run(trace=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == {"delta_host_ms.delta", "rounds.delta",
                            "round_ms.delta", "round_roofline.delta",
                            "idle_share.delta", "active_edge_share.delta"}
    refreshes = result["window"]["calls"]
    rounds = sum(result["window"]["rounds"])
    assert metrics["delta_host_ms.delta"] == pytest.approx(200 / refreshes)
    assert metrics["rounds.delta"] == pytest.approx(rounds / refreshes)
    assert metrics["round_ms.delta"] == pytest.approx(9e3 / rounds)
    assert metrics["round_roofline.delta"] == pytest.approx(
        100 * roofline.round_bytes(SMALL["n"], SMALL["m"], 1, 8)
        / (9.0 / rounds * 1e9))
    assert metrics["idle_share.delta"] == pytest.approx(10.0)
    assert 0 < metrics["active_edge_share.delta"] <= 100


def test_delta_host_ms_reads_nothing_without_the_span():
    reader = harness.reader("delta_host_ms.delta")
    summary = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=1.0, devices=1, span_device_s={},
        span_host_s={}, device_ops=[], idle_gaps=[])
    run = harness.Run(cell=CELL, config={}, traffic={}, device_kind="cpu",
                      value_bytes=8, calls=[dict(iterations=3)],
                      trace=summary)
    assert reader(run) is None
