"""The program-scope reduction, on a hand-made trace and on a recorded chip
trace, and the counters on a driver of a program that has none."""
import os
import types

import pytest
from google.protobuf import text_format

import program_trace
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")


def test_recorded_trace_scope_is_its_fusion():
    """On the chip trace, the loop body is one fusion, ``fusion.8``
    (``jit(work)/while/body/closed_call/dot_general``): the device time
    under ``while/body`` is that fusion's, and the decoder's times are
    those ``ProfileData`` reads."""
    pd = trace_reduce.load(SMALL)
    host = program_trace.host_events(pd)
    (window,) = [(s, e) for n, s, e in host if n == trace_reduce.WINDOW]
    ops = program_trace.device_ops(SMALL)
    assert list(ops) == ["/device:TPU:0"]
    fusion = [(a, b) for op, a, b in ops["/device:TPU:0"]
              if op == "jit(work)/while/body/closed_call/dot_general:"]
    assert len(fusion) > 100
    got = program_trace.scope_device_s(ops, window, ("while/body",
                                                     "closed_call"))
    expect = sum(min(b, window[1]) - max(a, window[0]) for a, b in fusion
                 if b > window[0] and a < window[1]) * 1e-9
    assert got["while/body"] == pytest.approx(expect, rel=1e-12)
    assert got["closed_call"] == pytest.approx(expect, rel=1e-12)
    summary = trace_reduce.reduce_trace(pd, ())
    assert got[None] == pytest.approx(summary.busy_s - expect, rel=1e-3)
    named = [(ev.start_ns, ev.duration_ns)
             for pl in pd.planes if pl.name == "/device:TPU:0"
             for ln in pl.lines if ln.name == "XLA Ops" for ev in ln.events
             if ev.name.startswith("%fusion.8 ")]
    assert len(named) == len(fusion)
    for (s, d), (a, b) in zip(sorted(named), sorted(fusion)):
        assert a == pytest.approx(s, abs=1) and b - a == pytest.approx(d, abs=1)


def _plane(name, line, events, tf_ops, ref=False):
    """A plane whose event ``i`` has the ``tf_op`` ``tf_ops[i]``, as a
    string or (``ref``) as a reference to a stat metadata name."""
    evs = " ".join(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                   f"duration_ps: {d * 1000} }}" for i, s, d in events)
    stats = [(1, "tf_op")] + [(10 + i, op) for i, op in tf_ops.items()]
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ name: "op{i}" stats {{ '
        f'metadata_id: 1 '
        + (f"ref_value: {10 + i}" if ref else f'str_value: "{op}"')
        + " } } }" for i, op in tf_ops.items())
    smeta = " ".join(f'stat_metadata {{ key: {k} value {{ name: "{v}" }} }}'
                     for k, v in stats)
    return (f'planes {{ name: "{name}" lines {{ name: "{line}" '
            f'timestamp_ns: 0 {evs} }} {meta} {smeta} }}')


# the round [0, 1000): the gather [100, 400), the scan [400, 700) with the
# while op over it all, the readout [700, 800); an op of the round outside
# the push [800, 900); the window cuts at 850
TF_OPS = {1: "jit(f)/while", 2: "jit(f)/while/body/ita_round/push/gather/gather:",
          3: "jit(f)/while/body/ita_round/push/scan/add:",
          4: "jit(f)/while/body/ita_round/push/readout/gather:",
          5: "jit(f)/while/body/ita_round/gt:"}
EVENTS = [(1, 0, 1000), (2, 100, 300), (3, 400, 300), (4, 700, 100),
          (5, 800, 100)]


@pytest.mark.parametrize("ref", [False, True])
def test_scopes_by_hand(tmp_path, ref):
    text = (_plane("/device:TPU:0", "XLA Ops", EVENTS, TF_OPS, ref)
            + _plane("/device:TPU:1", "XLA Ops", EVENTS[1:2], TF_OPS, ref)
            + _plane("/host:CPU", "python3", [(1, 0, 850)], {1: "-"}))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(text_format.Parse(text, program_trace._xspace()())
                     .SerializeToString())
    ops = program_trace.device_ops(str(path))
    assert sorted(ops) == ["/device:TPU:0", "/device:TPU:1"]
    got = program_trace.scope_device_s(ops, (0, 850))
    ns = 1e-9 / 2  # averaged over the two devices
    assert got["push/gather"] == pytest.approx((300 + 300) * ns)
    assert got["push/scan"] == pytest.approx(300 * ns)
    assert got["push/readout"] == pytest.approx(100 * ns)
    assert got["ita_round/push"] == pytest.approx((700 + 300) * ns)
    assert got["ita_round"] == pytest.approx((750 + 300) * ns)
    # the while op outside every scope: [0, 100)
    assert got[None] == pytest.approx(100 * ns)
    assert not program_trace.under("push/gather", "jit(f)/push/gatherer/x:")
    assert not program_trace.under("scan", "jit(f)/while/body/ita_round:")


def test_gap_label_names_program_spans():
    host = [("window", 0, 100), ("rank.solve", 0, 90), ("solve.wait", 50, 90),
            ("try_to_block", 60, 80), ("PjitFunction(f)", 10, 20)]
    spans = ("rank.solve",) + program_trace.PROGRAM_SPANS
    assert (program_trace.gap_label(host, spans, 70)
            == "rank.solve > solve.wait > try_to_block")
    assert program_trace.gap_label(host, spans, 95) == "no span"
    assert (program_trace.gap_label(host, spans, 15)
            == "rank.solve > PjitFunction(f)")


def _served(t_arrival, t_done, **extra):
    req = types.SimpleNamespace(t_arrival=t_arrival)
    return types.SimpleNamespace(req=req, indices=[0], t_done=t_done,
                                 latency_s=t_done - t_arrival, **extra)


def test_counters_absent_where_the_program_has_none():
    config = dict(m=100)
    old = types.SimpleNamespace(
        calls=[dict(iterations=4, rows=2, ops=None)],
        executor=types.SimpleNamespace(calls=[dict(env=types.SimpleNamespace(
            result=types.SimpleNamespace(result=types.SimpleNamespace())))]),
        report=types.SimpleNamespace(served=[_served(0.0, 3.0)]))
    assert program_trace.counters(old, config) == {}

    batch = types.SimpleNamespace(ops=200.0)
    new = types.SimpleNamespace(
        calls=[dict(iterations=4, rows=2, ops=None)],
        executor=types.SimpleNamespace(calls=[None, dict(
            env=types.SimpleNamespace(result=types.SimpleNamespace(
                result=batch)))]),
        report=types.SimpleNamespace(served=[
            _served(0.0, 2.0, t_dispatch=0.0),
            _served(0.0, 4.0, t_dispatch=2.0)]))
    got = program_trace.counters(new, config)
    assert got["active_edge_share"] == pytest.approx(100.0 * 200 / (4 * 2 * 100))
    assert got["queue_wait_ms"] == pytest.approx(1000.0)
    assert got["queue_wait_from_latency_ms"] == pytest.approx(1000.0)
