"""The trace reduction on a hand-made trace and on a recorded chip trace."""
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _plane(pid, name, line, events, names):
    evs = "\n".join(_event(*e) for e in events)
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')


def _profile(device_events, host_events):
    from jax.profiler import ProfileData

    dev_names = {1: "fusion.1", 2: "gather.2", 3: "copy.3", 4: "late.4"}
    host_names = {1: "window", 2: "rank.solve", 3: "PjitFunction(f)",
                  4: "serve.dispatch", 5: "$time sleep"}
    text = (_plane(1, "/device:TPU:0", "XLA Ops", device_events, dev_names)
            + _plane(2, "/host:CPU", "python3", host_events, host_names))
    return ProfileData.from_text_proto(text)


# device, in ns: fusion.1 [100, 400) with gather.2 [200, 300) nested in it,
# copy.3 [400, 500), fusion.1 again [700, 900), late.4 [1000, 1200) which
# the window [0, 1100) cuts to [1000, 1100)
DEVICE = [(1, 100, 300), (2, 200, 100), (3, 400, 100), (1, 700, 200),
          (4, 1000, 200)]
HOST = [(1, 0, 1100), (2, 50, 500), (3, 60, 30), (5, 550, 90), (4, 650, 400)]
SPANS = ("rank.solve", "serve.dispatch")


def test_busy_idle_spans_ops_and_gaps_by_hand():
    s = trace_reduce.reduce_trace(_profile(DEVICE, HOST), SPANS)
    assert s.window_s == pytest.approx(1100e-9)
    # union [100, 500) + [700, 900) + [1000, 1100)
    assert s.busy_s == pytest.approx(700e-9)
    assert s.idle_share == pytest.approx(400 / 1100)
    assert s.devices == 1
    assert s.span_device_s["rank.solve"] == pytest.approx(400e-9)
    # [700, 900) and [1000, 1050)
    assert s.span_device_s["serve.dispatch"] == pytest.approx(250e-9)
    assert s.span_host_s["serve.dispatch"] == pytest.approx(400e-9)
    ops = dict(s.device_ops)
    # self time: fusion.1 200 + 200, the nested gather's 100 taken out once
    assert ops == pytest.approx({"fusion.1": 400e-9, "gather.2": 100e-9,
                                 "copy.3": 100e-9, "late.4": 100e-9})
    assert s.device_ops[0][0] == "fusion.1"
    # gaps [500, 700), [900, 1000), [0, 100), longest first, named by the
    # innermost harness span and host event at their middle
    assert s.idle_gaps == [["no harness span > $time sleep", pytest.approx(200e-9)],
                           ["serve.dispatch", pytest.approx(100e-9)],
                           ["rank.solve", pytest.approx(100e-9)]]


def test_a_trace_without_window_or_device_work_is_refused():
    with pytest.raises(ValueError, match="host span 'window'"):
        trace_reduce.reduce_trace(_profile(DEVICE, HOST[1:]), SPANS)
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_trace(_profile([(4, 2000, 10)], HOST), SPANS)


def test_op_names_drop_layouts_and_called_computations():
    hlo = ("%fusion.121 = f32[5105039]{0:T(1024)S(1)} fusion(f32[875713]"
           "{0:T(1024)} %get-tuple-element.786), kind=kCustom, "
           "calls=%fused_computation.clone.clone")
    assert trace_reduce.op_name(hlo) == (
        "%fusion.121 = f32[5105039] fusion(f32[875713] "
        "%get-tuple-element.786), kind=kCustom")


def test_recorded_chip_trace():
    """``record_trace.py`` on a TPU v5e: three jitted loops, a 0.2 s sleep
    with the device idle, one loop more, all inside ``window``."""
    pd = trace_reduce.load(os.path.join(DATA, "small.xplane.pb"))
    s = trace_reduce.reduce_trace(pd, ("rank.solve", "host.sleep"))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.2058, abs=1e-3)
    assert 0 < s.busy_s < 0.01
    assert s.span_device_s["rank.solve"] > 0.5 * s.busy_s
    label, seconds = s.idle_gaps[0]
    assert label.startswith("host.sleep") and 0.2 <= seconds < 0.21
    assert s.device_ops[0][0].startswith("%fusion")
