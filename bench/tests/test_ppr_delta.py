"""The ``ppr_delta`` driver and the ``web-google-live-ppr.ppr-delta`` cell
off the chip.

The cell runs here through ``harness.run`` at a 3,000-vertex cut on the
CPU, its writes cut with the graph (30 inserts and 30 deletes, 0.15% of
m, the cell's churn) and due every 0.25 s, so that each micro-batch after
the first follows a write, with the platform check pointed at the CPU.
The check must read ``correct`` false for the float32 control and for
each fault a write lane can have: a micro-batch answered on the previous
version, one whose halves ran on two versions, a write acknowledged but
never applied, a write acknowledged before its layout reached the device.
Nothing here is a device measurement.
"""
import dataclasses
import time

import numpy as np
import pytest

import harness
import roofline
import trace_reduce
from conftest import SMALL

CELL = "web-google-live-ppr.ppr-delta"
CUT = dict(inserts=30, deletes=30, write_every_s=0.25)
SEED = 2**33 + 29
SECONDS = 2.5


@pytest.fixture
def small(monkeypatch):
    """The cell with its writes cut to the 3,000-vertex graph."""
    load = harness.load_cell

    def load_cut(name):
        cell = load(name)
        cell.traffic = dict(cell.traffic, **CUT)
        return cell

    monkeypatch.setattr(harness, "load_cell", load_cut)


def _run(dtype=None, trace=False):
    return harness.run(CELL, SEED, SECONDS, trace, platform="cpu",
                       dtype=dtype, config_override=SMALL)


def _runs(monkeypatch):
    """The per-layer readers' view of each run, as it is made."""
    made, Run = [], harness.Run

    def record(**kw):
        made.append(Run(**kw))
        return made[-1]

    monkeypatch.setattr(harness, "Run", record)
    return made


def test_sound_run_is_correct(small):
    result, checks = _run()
    assert result["correct"] and result["failed"] == 0, checks
    assert result["window"]["calls"] >= 2
    for name in ("window_compiles", "stale_batches", "relayouts"):
        assert checks[name]["value"] == 0
    assert set(result["metrics"]) == {"ppr_qps", "ppr_p95_ms", "setup_s"}


def test_float32_control_is_not_correct(small):
    result, checks = _run(dtype="float32")
    assert not result["correct"] and result["failed"] >= 1, checks
    assert checks["row_l1"]["value"] > 1e-8


def _snapshots(monkeypatch):
    """Keep the layout and degrees each write replaces; the set-up write
    (the first) takes the layout and replaces none."""
    from repro.core.engine import PageRankEngine

    exec_delta, before = PageRankEngine._exec_delta, []

    def kept(self, q):
        if self._live is not None:
            before.append((self._ctx, self._live.degrees))
        return exec_delta(self, q)

    monkeypatch.setattr(PageRankEngine, "_exec_delta", kept)
    return before


def _on(engine, layout, run):
    """``run()`` with the engine's push and degrees set to ``layout``."""
    now = engine._ctx, engine._live.degrees
    engine._ctx, engine._live.degrees = layout
    try:
        return run()
    finally:
        engine._ctx, engine._live.degrees = now


def _previous_version(monkeypatch):
    from repro.core.engine import PageRankEngine

    before, exec_topk = _snapshots(monkeypatch), PageRankEngine._exec_topk

    def previous(self, q, ep):
        if not before:
            return exec_topk(self, q, ep)
        return _on(self, before[-1], lambda: exec_topk(self, q, ep))

    monkeypatch.setattr(PageRankEngine, "_exec_topk", previous)


def _mixed_versions(monkeypatch):
    from repro.core.engine import PageRankEngine

    before, exec_topk = _snapshots(monkeypatch), PageRankEngine._exec_topk

    def mixed(self, q, ep):
        res = exec_topk(self, q, ep)
        if not before:
            return res
        old = _on(self, before[-1], lambda: exec_topk(self, q, ep))
        b = res.indices.shape[0] // 2

        def halves(x, y):
            return x.at[b:].set(y[b:])

        return res._replace(
            indices=halves(res.indices, old.indices),
            scores=halves(res.scores, old.scores),
            result=dataclasses.replace(
                res.result, pi=halves(res.result.pi, old.result.pi)))

    monkeypatch.setattr(PageRankEngine, "_exec_topk", mixed)


def _skipped_write(monkeypatch):
    """The first window write is acknowledged and never applied; the later
    ones are cut to what stays valid without it."""
    from repro.core import DeltaQuery
    from repro.core.engine import PageRankEngine

    exec_delta, seen, skipped = PageRankEngine._exec_delta, [], {}

    def pairs(x):
        return {tuple(e) for e in np.asarray(x).reshape(-1, 2).tolist()}

    def skip(self, q):
        seen.append(q)
        if len(seen) == 2:
            skipped.update(add=pairs(q.add), remove=pairs(q.remove))
            return exec_delta(self, DeltaQuery(rank=False))
        if skipped:
            q = DeltaQuery(add=tuple(pairs(q.add) - skipped["remove"]),
                           remove=tuple(pairs(q.remove) - skipped["add"]),
                           rank=False)
        return exec_delta(self, q)

    monkeypatch.setattr(PageRankEngine, "_exec_delta", skip)


def _ack_before_apply(monkeypatch):
    """Each due write is acknowledged at once and reaches the engine only
    after the next micro-batch is dispatched."""
    import collections

    from repro.serve.service import PPRService, WriteAck

    apply, dispatch = PPRService._apply_writes, PPRService._dispatch
    held = []

    def early(self, pending, acks):
        now = self.clock.now()
        while pending and pending[0].t_arrival <= now:
            w = pending.popleft()
            held.append(w)
            acks.append(WriteAck(w.t_arrival, now, now,
                                 self.engine.graph_version + len(held)))

    def late(self, *args):
        dispatch(self, *args)
        if held:
            apply(self, collections.deque(held), [])
            held.clear()

    monkeypatch.setattr(PPRService, "_apply_writes", early)
    monkeypatch.setattr(PPRService, "_dispatch", late)


@pytest.mark.parametrize("fault, number", [
    (_previous_version, "row_l1"), (_mixed_versions, "row_l1"),
    (_skipped_write, "row_l1"), (_ack_before_apply, "stale_batches")],
    ids=["previous_version", "mixed_versions", "skipped_write",
         "ack_before_apply"])
def test_faulty_write_lane_is_not_correct(small, monkeypatch, fault, number):
    fault(monkeypatch)
    result, checks = _run()
    assert not result["correct"] and result["failed"] >= 1, checks
    assert checks[number]["value"] > (1e-6 if number == "row_l1" else 0)


@pytest.mark.parametrize("lacks", ["rank", "graph_version"])
def test_program_without_the_write_lane_fails_at_set_up(small, monkeypatch,
                                                        lacks):
    import repro.core
    import repro.serve.service

    if lacks == "rank":
        @dataclasses.dataclass(frozen=True)
        class DeltaQuery:
            add: tuple = ()
            remove: tuple = ()

        monkeypatch.setattr(repro.core, "DeltaQuery", DeltaQuery)
    else:
        @dataclasses.dataclass
        class Served:
            req: object = None

        monkeypatch.setattr(repro.serve.service, "Served", Served)
    with pytest.raises(harness.SetupError, match=f"has no '{lacks}'"):
        _run()


def test_traced_run_reports_every_per_layer_metric(small, monkeypatch):
    # the CPU trace has no device plane: stand one in with a busy time of
    # 0.9 of a 10 s window and 0.2 s of host time in the write span
    summary = trace_reduce.TraceSummary(
        window_s=10.0, busy_s=9.0, devices=1, span_device_s={},
        span_host_s={"serve.write": 0.2, "engine.delta.apply": 0.15},
        device_ops=[["op", 9.0]], idle_gaps=[["gap", 1.0]])
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda *a: summary)
    monkeypatch.setattr(trace_reduce, "load", lambda path: None)
    monkeypatch.setitem(roofline.PEAKS, "cpu", dict(hbm_bytes_per_s=1e9))
    made = _runs(monkeypatch)
    result, _ = _run(trace=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == {
        "rounds.ppr_live", "round_ms.ppr_live", "round_roofline.ppr_live",
        "idle_share.ppr_live", "write_host_ms.ppr_live",
        "write_visible_ms.ppr_live", "tier_ms_per_batch.ppr_live"}
    (run,) = made
    calls = run.calls
    rounds = sum(c["iterations"] for c in calls)
    writes = [w for c in calls for w in c["writes"]]
    assert writes and len(calls) >= 2

    def acked(c):
        return sum(w["t_ack"] <= c["t_dispatch"] for w in writes)

    # each micro-batch runs on the version of the writes acknowledged by
    # its dispatch; how many fall between two dispatches depends on the
    # host's speed
    base = calls[0]["graph_version"] - acked(calls[0])
    assert [c["graph_version"] - base for c in calls] == [
        acked(c) for c in calls]
    assert metrics["write_host_ms.ppr_live"] == pytest.approx(
        200 / len(writes))
    visible = [min(c["t_dispatch"] for c in calls
                   if c["t_dispatch"] >= w["t_ack"]) - w["t_arrival"]
               for w in writes if w["t_ack"] <= calls[-1]["t_dispatch"]]
    assert visible
    assert metrics["write_visible_ms.ppr_live"] == pytest.approx(
        1e3 * np.mean(visible))
    assert metrics["tier_ms_per_batch.ppr_live"] == pytest.approx(
        1e3 * run.tier_s / len(calls))
    assert metrics["rounds.ppr_live"] == pytest.approx(rounds / len(calls))
    assert metrics["round_ms.ppr_live"] == pytest.approx(9e3 / rounds)
    assert metrics["round_roofline.ppr_live"] == pytest.approx(
        100 * roofline.round_bytes(SMALL["n"], SMALL["m"], 16, 8)
        / (9.0 / rounds * 1e9))
    assert metrics["idle_share.ppr_live"] == pytest.approx(10.0)


def test_tier_time_leaves_out_the_write_lane(small, monkeypatch):
    """The serving tier's host time outside the engine holds neither the
    micro-batches nor the writes: each write is held here for ``SLOW``
    seconds on the host, which the tier's time must not show."""
    from repro.core.engine import PageRankEngine

    exec_delta, drivers, make = (PageRankEngine._exec_delta, [],
                                 harness.make_driver)
    slow = 0.4

    def held(self, q):
        time.sleep(slow)
        return exec_delta(self, q)

    def kept(*args):
        drivers.append(make(*args))
        return drivers[-1]

    monkeypatch.setattr(PageRankEngine, "_exec_delta", held)
    monkeypatch.setattr(harness, "make_driver", kept)
    result, checks = _run()
    assert result["correct"], checks
    ((driver, _),) = drivers
    lane = [a.t_ack - a.t_start for a in driver.window_acks]
    assert lane and min(lane) >= slow
    assert 0 <= driver.tier_s < slow


def test_write_readers_read_nothing_without_their_records():
    summary = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=1.0, devices=1, span_device_s={},
        span_host_s={}, device_ops=[], idle_gaps=[])

    def run(calls, trace=summary):
        return harness.Run(cell=CELL, config={}, traffic={},
                           device_kind="cpu", value_bytes=8, calls=calls,
                           trace=trace)

    host = harness.reader("write_host_ms.ppr_live")
    visible = harness.reader("write_visible_ms.ppr_live")
    write = dict(t_arrival=1.0, t_start=1.05, t_ack=1.1, graph_version=2,
                 relayouts=0)
    written = [dict(iterations=3, t_dispatch=1.2, graph_version=2,
                    writes=[write])]
    # the parent's records: no writes at all; a trace without the span
    for calls in ([dict(iterations=3)], []):
        assert host(run(calls)) is None and visible(run(calls)) is None
    assert host(run(written)) is None
    assert host(run(written, trace=None)) is None
    # a write no micro-batch followed on its version is not counted
    later = [dict(written[0], graph_version=1)]
    assert visible(run(later)) is None
    assert visible(run(written)) == pytest.approx(200.0)
    # a write applied after the last micro-batch's dispatch counts for the
    # host time, and no micro-batch made it visible
    after = [dict(written[0], writes=[write, dict(write, t_arrival=1.3,
                                                  t_ack=1.4, graph_version=3)])]
    spanned = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=1.0, devices=1, span_device_s={},
        span_host_s={"serve.write": 0.05}, device_ops=[], idle_gaps=[])
    assert visible(run(after)) == pytest.approx(200.0)
    assert host(run(after, trace=spanned)) == pytest.approx(25.0)
