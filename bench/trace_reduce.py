"""From a profiler trace to device busy time, idle share and the breakdown.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Device planes are those named
``/device:...``; on each, the operations are the events of the line named
``XLA Ops`` (every line of the plane when it has none).  Host spans are the
events of the ``/host:CPU`` plane, among them the harness's own
``jax.profiler.TraceAnnotation`` spans.

  * the window is the host span named ``window``;
  * busy time is the union of the operation intervals of one device,
    clipped to the window, averaged over the devices;
  * the device time of a host span is the busy time inside its intervals;
  * the top operations are ranked by self time (an event's duration less
    the events nested in it on the same line), summed by name;
  * an idle gap is a stretch of the window in which no operation runs on
    the device, named by what the host was doing at its middle: the
    innermost harness span there, and the innermost host event beneath it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

__all__ = ["TraceSummary", "find_xplane", "load", "reduce_trace"]

WINDOW = "window"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    span_device_s: dict     # harness span name -> device busy seconds in it
    span_host_s: dict       # harness span name -> host seconds spent in it
    device_ops: list        # [[op name, self seconds], ...], longest first
    idle_gaps: list         # [[what the host did, seconds], ...], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return ProfileData.from_file(path)


def _union(intervals):
    """Sorted, disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(union, s, e) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in union)


def _self_times(events, into: dict) -> None:
    """Add each event's duration less its nested children to ``into``."""
    stack = []  # [name, start, end, child time]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            into[top[0]] += (top[2] - top[1]) - top[3]
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        top = stack.pop()
        into[top[0]] += (top[2] - top[1]) - top[3]


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def op_name(hlo: str) -> str:
    """An operation's HLO text without layouts and called computations:
    ``%fusion.121 = f32[5105039] fusion(f32[875713] %gte.786, ...)``."""
    return re.sub(r"\{[^{}]*\}", "", hlo.split(", calls=")[0])


def reduce_trace(pd, span_names=()) -> TraceSummary:
    """Reduce a ``ProfileData`` to a :class:`TraceSummary`.

    ``span_names`` are the harness spans to account for by name.
    """
    host = [ev for pl in pd.planes if pl.name == HOST_PLANE
            for ln in pl.lines for ev in _events(ln)]
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one host span {WINDOW!r}, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    spans = defaultdict(list)
    for name, s, e in host:
        if name in span_names:
            spans[name].append((s, e))

    unions, self_ns = [], defaultdict(float)
    for pl in pd.planes:
        if not pl.name.startswith("/device:"):
            continue
        lines = [ln for ln in pl.lines if ln.name == OPS_LINE] or list(pl.lines)
        ops = []
        for ln in lines:
            evs = [(n, max(s, w0), min(e, w1)) for n, s, e in _events(ln)
                   if e > w0 and s < w1]
            _self_times(evs, self_ns)
            ops += evs
        if ops:
            unions.append(_union((s, e) for _, s, e in ops))
    if not unions:
        raise ValueError("the trace holds no device operation in the window")
    ndev = len(unions)
    busy = sum(b - a for u in unions for a, b in u) / ndev
    span_dev = {k: sum(_overlap(u, s, e) for u in unions for s, e in v) / ndev
                for k, v in spans.items()}
    span_host = {k: sum(e - s for s, e in v) for k, v in spans.items()}

    # gaps are read on the first device; only the longest are named
    edges = [w0] + [x for ab in unions[0] for x in ab] + [w1]
    gaps = sorted(((b - a, a) for a, b in zip(edges[::2], edges[1::2])
                   if b > a), reverse=True)[:TOP]
    gaps = [(d, _what_host_did(host, span_names, a + d / 2)) for d, a in gaps]
    ops_top = sorted(self_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9, devices=ndev,
        span_device_s={k: v * 1e-9 for k, v in span_dev.items()},
        span_host_s={k: v * 1e-9 for k, v in span_host.items()},
        device_ops=[[op_name(k), v * 1e-9 / ndev] for k, v in ops_top],
        idle_gaps=[[what, d * 1e-9] for d, what in gaps])


def _what_host_did(host, span_names, t) -> str:
    covering = [(e - s, name) for name, s, e in host
                if s <= t < e and name != WINDOW]
    spans = sorted(c for c in covering if c[1] in span_names)
    others = sorted(c for c in covering if c[1] not in span_names)
    label = spans[0][1] if spans else "no harness span"
    if others:
        label += " > " + others[0][1]
    return label
