"""The program's own spans, device scopes and counters over one window.

    python3 bench/program_trace.py --workload web-google.ppr-zipf --seed 7 --seconds 35 --trace 1

Runs one cell as ``bench/run.py`` does (the same graph from ``--seed``, the
same engine, warm-up and window) and prints one JSON object as its last
line: the window's end-to-end numbers, the program's counters and, under
``--trace 1``, what the profiler trace says of the program's layers.  It
does not check the answers; ``bench/run.py`` does.

What it reads of the program, and where the program sets it:

  * device scopes (``jax.named_scope``): ``ita_round`` around the round
    body, ``push`` inside it, and ``gather``, ``scan``, ``readout`` inside
    the dense push (``core/backends.py``, ``core/batch.py``);
  * host spans (``jax.profiler.TraceAnnotation``): :data:`PROGRAM_SPANS`;
  * counters: ``ops`` of each solve or micro-batch (``SolverResult.ops``,
    ``BatchSolverResult.ops``), ``Served.t_dispatch`` of each answer.

A device operation's scope is its ``tf_op`` stat: the ``op_name`` path of
the HLO instruction, ``jit(...)/while/body/ita_round/push/gather/gather``.
The stat lives in the device plane's event metadata, which
``jax.profiler.ProfileData`` does not expose, so :func:`device_ops` decodes
the ``.xplane.pb`` itself with a descriptor built from ``xplane.proto``'s
public field numbers.  A fused operation carries the ``tf_op`` of its
root: a fusion that swallows instructions of two scopes counts, whole,
under its root's.  A scope's device time is the union of the intervals of
the operations under it, clipped to the window and averaged over devices.
Where the program has no scope, span or counter (an older checkout), the
number is absent from the line, never 0.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import tempfile
import time

import numpy as np

import harness
import trace_reduce

# the program's host spans, outermost first as the layers call
PROGRAM_SPANS = ("serve.batch", "serve.ingest", "engine.plan", "engine.exec",
                 "solve.wait", "serve.assemble")
# the device scopes, as consecutive segments of an operation's path
SCOPES = ("ita_round", "ita_round/push", "push/gather", "push/scan",
          "push/readout")


@functools.lru_cache(maxsize=None)
def _xspace():
    """The ``XSpace`` message class, with only the fields read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, s = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING

    def sub(name):
        return (F.TYPE_MESSAGE, ".xplane." + name)

    messages = {  # name: [(field, number, label, type)]
        "XStat": [("metadata_id", 1, one, i64), ("str_value", 5, one, s),
                  ("ref_value", 7, one, u64)],
        "XEvent": [("metadata_id", 1, one, i64), ("offset_ps", 2, one, i64),
                   ("duration_ps", 3, one, i64)],
        "XLine": [("name", 2, one, s), ("timestamp_ns", 3, one, i64),
                  ("events", 4, many, sub("XEvent"))],
        "XEventMetadata": [("name", 2, one, s), ("display_name", 4, one, s),
                           ("stats", 5, many, sub("XStat"))],
        "XStatMetadata": [("name", 2, one, s)],
        # a proto map is on the wire a repeated entry of key 1, value 2
        "EventMetadataEntry": [("key", 1, one, i64),
                               ("value", 2, one, sub("XEventMetadata"))],
        "StatMetadataEntry": [("key", 1, one, i64),
                              ("value", 2, one, sub("XStatMetadata"))],
        "XPlane": [("name", 2, one, s), ("lines", 3, many, sub("XLine")),
                   ("event_metadata", 4, many, sub("EventMetadataEntry")),
                   ("stat_metadata", 5, many, sub("StatMetadataEntry"))],
        "XSpace": [("planes", 1, many, sub("XPlane"))],
    }
    fd = descriptor_pb2.FileDescriptorProto(name="xplane_subset.proto",
                                            package="xplane", syntax="proto3")
    for name, fields in messages.items():
        m = fd.message_type.add(name=name)
        for field, number, label, typ in fields:
            f = m.field.add(name=field, number=number, label=label)
            if isinstance(typ, tuple):
                f.type, f.type_name = typ
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xplane.XSpace"))


def device_ops(path: str) -> dict:
    """``{device plane: [(tf_op, start_ns, end_ns), ...]}`` of the
    operations on each device's ``XLA Ops`` line (every line of a plane
    that has none); ``tf_op`` is ``""`` where the event has none."""
    with open(path, "rb") as f:
        space = _xspace().FromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    tf_op[e.key] = st.str_value or stat_names.get(
                        st.ref_value, "")
        lines = [ln for ln in plane.lines
                 if ln.name == trace_reduce.OPS_LINE] or list(plane.lines)
        ops = [(tf_op.get(ev.metadata_id, ""),
                ln.timestamp_ns + ev.offset_ps / 1e3,
                ln.timestamp_ns + (ev.offset_ps + ev.duration_ps) / 1e3)
               for ln in lines for ev in ln.events]
        if ops:
            out[plane.name] = ops
    return out


def under(scope: str, tf_op: str) -> bool:
    """Whether ``tf_op``'s path holds ``scope``'s segments in a row."""
    return f"/{scope}/" in "/" + tf_op.split(":")[0] + "/"


def scope_device_s(ops: dict, window, scopes=SCOPES) -> dict:
    """Device seconds in ``window`` (ns) under each scope, and under
    ``None`` the busy seconds under no scope, averaged over devices."""
    w0, w1 = window
    out = {k: 0.0 for k in (*scopes, None)}
    for evs in ops.values():
        clipped = [(op, max(a, w0), min(b, w1)) for op, a, b in evs
                   if b > w0 and a < w1]
        busy = trace_reduce._union((a, b) for _, a, b in clipped)
        scoped = []
        for scope in scopes:
            u = trace_reduce._union((a, b) for op, a, b in clipped
                                    if under(scope, op))
            out[scope] += sum(b - a for a, b in u)
            scoped += u
        covered = sum(b - a for a, b in trace_reduce._union(scoped))
        out[None] += sum(b - a for a, b in busy) - covered
    return {k: v * 1e-9 / len(ops) for k, v in out.items()}


def host_events(pd) -> list:
    return [ev for pl in pd.planes if pl.name == trace_reduce.HOST_PLANE
            for ln in pl.lines for ev in trace_reduce._events(ln)]


def gap_label(host, span_names, t) -> str:
    """What the host did at ``t``: every named span over it, outermost
    first, then the innermost other host event."""
    over = sorted((s - e, name) for name, s, e in host
                  if s <= t < e and name != trace_reduce.WINDOW)
    named = [name for _, name in over if name in span_names]
    others = [name for _, name in over if name not in span_names]
    return " > ".join(named + others[-1:]) or "no span"


def trace_readings(log_dir: str, span_names, rounds: int, calls: int) -> dict:
    """The layers' numbers from the trace of one window."""
    path = trace_reduce.find_xplane(log_dir)
    pd = trace_reduce.load(path)
    summary = trace_reduce.reduce_trace(pd, span_names)
    host = host_events(pd)
    (window,) = [(s, e) for name, s, e in host if name == trace_reduce.WINDOW]
    ops = device_ops(path)
    scoped = scope_device_s(ops, window)
    out = dict(window_s=summary.window_s, busy_s=summary.busy_s,
               idle_share=100.0 * summary.idle_share,
               round_ms=1e3 * summary.busy_s / rounds,
               unscoped_share=100.0 * scoped.pop(None) / summary.busy_s,
               span_host_s=summary.span_host_s,
               span_device_s=summary.span_device_s,
               device_ops=summary.device_ops)
    for scope, seconds in scoped.items():
        if seconds:
            out[scope.split("/")[-1] + "_ms"] = 1e3 * seconds / rounds
    if "serve.assemble" in summary.span_host_s:
        out["assemble_ms"] = 1e3 * summary.span_host_s["serve.assemble"] / calls
    # gaps on the first device, as trace_reduce reads them
    w0, w1 = window
    busy = trace_reduce._union((a, b) for _, a, b in next(iter(ops.values()))
                               if b > w0 and a < w1)
    edges = [w0] + [min(max(x, w0), w1) for ab in busy for x in ab] + [w1]
    gaps = sorted(((b - a, a) for a, b in zip(edges[::2], edges[1::2])
                   if b > a), reverse=True)[:trace_reduce.TOP]
    out["idle_gaps"] = [[gap_label(host, span_names, a + d / 2), d * 1e-9]
                        for d, a in gaps]
    return out


def counters(driver, config: dict) -> dict:
    """The program's counters over the window's calls."""
    out = {}
    rounds = sum(c["iterations"] for c in driver.calls)
    rows = driver.calls[0]["rows"]
    ops = [c.get("ops") for c in driver.calls]
    executor = getattr(driver, "executor", None)
    if executor is not None:
        ops = [getattr(c["env"].result.result, "ops", None)
               for c in executor.calls if c is not None]
    if ops and all(o is not None for o in ops):
        out["active_edge_share"] = 100.0 * sum(ops) / (rounds * rows
                                                        * config["m"])
    report = getattr(driver, "report", None)
    if report is not None:
        done = [s for s in report.served if s.indices is not None]
        if done and getattr(done[0], "t_dispatch", None) is not None:
            waits = [s.t_dispatch - s.req.t_arrival for s in done]
            split = [s.latency_s - (s.t_done - s.t_dispatch) for s in done]
            out["queue_wait_ms"] = 1e3 * float(np.mean(waits))
            out["queue_wait_from_latency_ms"] = 1e3 * float(np.mean(split))
    return out


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        platform: str = "tpu", config_override: dict = None) -> dict:
    cell = harness.load_cell(cell_name)
    config = dict(cell.config, **(config_override or {}))
    harness.require_chip(platform, cell.workload["chips"])
    harness.import_program()

    import jax
    from jax.profiler import TraceAnnotation

    import graphgen
    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_enable_x64", True)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the cache key leaves out the op_name metadata the scopes live in, so
    # a program compiled from a source without them, or with other names,
    # would be served here; keyed with it, the trace names this source's
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    dtype = config["dtype"]
    src, dst, perm = graphgen.run_edges(config, int(seed))
    engine = harness.prepare_engine(config, src, dst, dtype,
                                    cell.workload["chips"])
    driver, mod = harness.make_driver(cell.traffic, engine, config, dtype,
                                      (src, dst, perm))
    del engine
    driver.warm_up(seconds)
    with tempfile.TemporaryDirectory(prefix="program_trace_") as log_dir:
        if trace:
            jax.profiler.start_trace(log_dir)
        with TraceAnnotation("window"):
            driver.window()
        if trace:
            jax.profiler.stop_trace()
        rounds = sum(c["iterations"] for c in driver.calls)
        out = dict(cell=cell_name, seed=int(seed), trace=bool(trace),
                   calls=len(driver.calls), rounds=rounds,
                   call_s=[c["t1"] - c["t0"] for c in driver.calls],
                   end_to_end=driver.end_to_end(),
                   counters=counters(driver, config))
        if trace:
            spans = harness.SPANS + mod.SPANS + PROGRAM_SPANS
            out["trace"] = trace_readings(log_dir, spans, rounds,
                                          len(driver.calls))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except harness.SetupError as e:
        print(e, file=sys.stderr)
        return 2
    out["run_s"] = time.perf_counter() - t0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
