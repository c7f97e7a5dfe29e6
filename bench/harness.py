"""One run of one cell: set up, warm up, measure a window, check, report.

Everything is found by name from ``BENCHMARK.json`` (``bench/plugins.py``):

  * the cell's configuration is the JSON file its ``configs`` entry names;
    its ``plan`` goes whole to ``repro.core.EnginePlan`` and its
    ``generator`` to ``bench/generators/<kind>.py``;
  * its traffic mix is ``bench/traffic/<traffic>.json``; the mix's
    ``query`` names its driver, ``bench/drivers/<query>.py``, which takes
    the mix's other keys as its parameters;
  * its correctness limits are ``bench/limits/<cell>.json``;
  * a per-layer metric ``<q>.<split>`` is read by ``bench/metrics/<q>.<split>.py``
    or, failing that, by the quantity's reader ``bench/metrics/<q>.py``.

A key that the part it goes to does not take is an error, never dropped.

The run measures with the profiler off (``trace=False``) and reports the
cell's end-to-end metrics, or traces its window (``trace=True``) and
reports the per-layer metrics.  Either way it checks what the window
produced against the float64 reference and prints each compared number
beside its limit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import plugins
from plugins import SetupError

BENCH = plugins.BENCH
ROOT = os.path.dirname(BENCH)
# a threshold no vertex reaches: a solve runs one round of the same
# compiled program, which is all a warm-up needs
WARM_XI = 1e30
# keys of a traffic mix that describe it and are no driver's parameter
MIX_NOTES = ("query", "why", "assumed")
# the harness's own host spans, by which the trace reduction names idle gaps
SPANS = ("setup.graph", "setup.prepare", "setup.warm_up")


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


@dataclasses.dataclass
class Run:
    """What a per-layer reader sees of one run."""

    cell: str
    config: dict
    traffic: dict
    device_kind: str
    value_bytes: int
    calls: list               # one dict per solve or micro-batch in the window
    trace: object = None      # trace_reduce.TraceSummary of the window
    tier_s: float = None      # serve-loop host time outside the engine


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Cell:
    bench = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, workload=w,
        config=_read_json(os.path.join(ROOT, conf["file"])),
        traffic=_read_json(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(BENCH, "limits", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """The ``read(run)`` function of a per-layer metric, found by name."""
    stem = metric if os.path.exists(plugins.path("metrics", metric)) \
        else metric.split(".")[0]
    return plugins.load("metrics", stem).read


def prepare_engine(config: dict, src, dst, dtype: str, chips: int):
    """The engine on the run's graph, planned as the configuration says.

    ``plan`` goes whole to ``EnginePlan`` beside ``c`` and ``dtype``; its
    ``mesh`` (``[R]`` or ``[R, C]``) has to use exactly the cell's chips,
    and a cell on more than one chip needs one.
    """
    import jax.numpy as jnp
    from repro.core import EnginePlan, PageRankEngine
    from repro.graph import graph_from_edges

    plan = dict(config.get("plan", {}))
    if plan.get("mesh") is not None:
        plan["mesh"] = tuple(plan["mesh"])
    used = math.prod(plan["mesh"]) if plan.get("mesh") else 1
    if used != chips:
        raise SetupError(f"configuration {config['name']!r} plans a mesh of "
                         f"{used} chips; the cell has {chips}")
    plan = plugins.call(EnginePlan, f"configuration {config['name']!r} plan",
                        c=config["c"], dtype=getattr(jnp, dtype), **plan)
    g = graph_from_edges(src, dst, config["n"], dedup=False)
    return PageRankEngine(g, plan)


def make_driver(traffic: dict, engine, config: dict, dtype: str, graph):
    """The mix's driver, ``bench/drivers/<query>.py``, and its module."""
    import jax.numpy as jnp

    mod = plugins.load("drivers", traffic["query"])
    params = {k: v for k, v in traffic.items() if k not in MIX_NOTES}
    driver = plugins.call(mod.make, f"traffic query {traffic['query']!r}",
                          engine, config, getattr(jnp, dtype), graph, WARM_XI,
                          **params)
    return driver, mod


class CompileWatch:
    """Counts backend compiles while ``armed``.

    JAX reports a program it loads from the persistent compile cache as a
    backend compile and a cache hit; ``compiles`` leaves those out, so it
    counts only programs the window had to compile.  ``loads`` counts the
    cache hits: programs the program traced again in the window (an eager
    ``lax.map`` re-traces on every call) and found compiled.
    """

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.events = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    @property
    def loads(self) -> int:
        return self.events.count(self.HIT)

    @property
    def compiles(self) -> int:
        return self.events.count(self.COMPILE) - self.loads

    def _seen(self, name):
        if self.armed and name in (self.COMPILE, self.HIT):
            self.events.append(name)

    def _event(self, name, **kw):
        self._seen(name)

    def _duration(self, name, duration, **kw):
        self._seen(name)


# libtpu maps a host buffer for transfers when it starts.  Without
# transparent huge pages on the host, mapping it at its default size took
# 5.1-11.2 s on one TPU v5e, varying by seconds from run to run; at 256 MiB
# the chip came up in 1.1-1.9 s and moved a 100 MB array in the same time.
PREMAPPED_BUFFER_BYTES = str(256 << 20)


def require_chip(platform: str, chips: int):
    """The devices of the cell, or :class:`SetupError` if JAX finds none."""
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", PREMAPPED_BUFFER_BYTES)
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise SetupError(f"no TPU: JAX found platform "
                         f"{devices[0].platform!r}; the benchmark does not "
                         f"fall back to it")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def import_program():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise SetupError(f"the program (src/repro) is not in this checkout: "
                         f"{e}") from None


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float = None, platform: str = "tpu", dtype: str = None,
        config_override: dict = None):
    """One run; returns ``(result, checks)``.

    ``dtype`` replaces the configuration's precision (the control runs the
    program in float32); ``config_override`` replaces configuration keys
    (the CPU tests shrink the graph); ``platform`` is the platform the run
    insists on.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(cell_name)
    config = dict(cell.config, **(config_override or {}))
    t_chip = time.perf_counter()
    devices = require_chip(platform, cell.workload["chips"])
    phases = dict(chip_s=time.perf_counter() - t_chip)
    import_program()

    import jax
    from jax.profiler import TraceAnnotation

    phases["start_s"] = time.perf_counter() - t_start
    jax.config.update("jax_enable_x64", True)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # every program, however quick to compile, goes to the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = CompileWatch()

    import graphgen
    import trace_reduce

    dtype = dtype or config["dtype"]
    chips = cell.workload["chips"]
    # the seed relabels the graph and draws the sample the check compares
    sample_rng = np.random.default_rng(int(seed))
    with _phase("setup.graph", phases):
        src, dst, perm = graphgen.run_edges(config, int(seed))
    with _phase("setup.prepare", phases):
        engine = prepare_engine(config, src, dst, dtype, chips)
        driver, mod = make_driver(cell.traffic, engine, config, dtype,
                                  (src, dst, perm))
        del engine
    with _phase("setup.warm_up", phases):
        driver.warm_up(seconds)

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir)
    setup_s = time.perf_counter() - t_start
    watch.armed = True
    with TraceAnnotation("window"):
        driver.window()
    watch.armed = False
    if trace:
        jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[:chips])

    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=memory_peak)
    metrics, breakdown = {}, None
    if trace:
        summary = trace_reduce.reduce_trace(trace_reduce.load(log_dir),
                                            SPANS + mod.SPANS)
        shutil.rmtree(log_dir, ignore_errors=True)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = dict(device_ops=summary.device_ops,
                         idle_gaps=summary.idle_gaps)
        rec = Run(cell=cell.name, config=config, traffic=cell.traffic,
                  device_kind=devices[0].device_kind,
                  value_bytes=np.dtype(dtype).itemsize, calls=driver.calls,
                  trace=summary, tier_s=driver.tier_s)
        for m in cell.per_layer:
            value = reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
    else:
        measured = dict(driver.end_to_end(), setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in measured:
                raise SetupError(f"the {cell.traffic['query']!r} driver does "
                                 f"not measure {m['name']!r}")
            metrics[m["name"]] = dict(value=float(measured[m["name"]]),
                                      unit=m["unit"])

    # what the window did, for the record (the driver reads none of it)
    window = dict(calls=len(driver.calls), end_s=driver.calls[-1]["t1"],
                  rounds=[c["iterations"] for c in driver.calls],
                  cache_loads=watch.loads)
    # the check: program state off the device first, then the reference
    attempted = driver.attempted()
    held = driver.collect(sample_rng)
    del driver
    with _phase("check", phases):
        answers = mod.check(config, src, dst, held)
    limits = dict(cell.limits, window_compiles=0.0)
    numbers = {k: max(a[k] for a in answers) for k in answers[0]}
    numbers["window_compiles"] = float(watch.compiles)
    checks = {}
    for name, value in numbers.items():
        if name not in limits:
            raise SetupError(f"no limit for {name!r} in "
                             f"bench/limits/{cell.name}.json")
        checks[name] = dict(value=float(value), limit=float(limits[name]))
    failed = sum(any(not v <= limits[k] for k, v in a.items())
                 for a in answers)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = dict(correct=bool(correct), attempted=int(attempted),
                  failed=int(failed), metrics=metrics, device=device,
                  window=window, phases_s=phases)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, checks


@contextlib.contextmanager
def _phase(name: str, into: dict):
    """Time a phase on the host clock, under a trace span of its name."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation(name):
        t0 = time.perf_counter()
        yield
        into[name] = time.perf_counter() - t0
