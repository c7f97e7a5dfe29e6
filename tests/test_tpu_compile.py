"""Compile the chip's main path for a described TPU v5e, at web-Google size.

Nothing here runs on a chip: each test lowers and compiles one program of
the engine's main path for a ``v5e:2x2`` topology that is described, not
attached, at the widths of the paper's Table-3 web-Google preset
(n = 875,713, m = 5,105,039) and in the engine's dtype (float64).  What the
TPU compiler refuses fails here, at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compile cache is off around these
compiles, since a compile for a described chip cannot be read back here.

The dense push's float64 gathers are held to one gather of two-word rows
each (``backends._split_gather``), read from the compiled program's gather
operand shapes; a float32 push keeps the plain gather.  Its ``[16, n]``
scan is held to its pass schedule (``backends.ScanPasses``): the float32
words its passes write add up to the prefixes the schedule walks, and no
pass copies a whole edge list; a single vector takes every pass.

The bucketed-ELL kernel is refused by Mosaic today; its test is a strict
xfail, so a change that makes it lower must flip it (and lift
``EllBackend.refused_on``).
"""
import math
import re
from functools import cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.backends import (DenseRuns, ScanPasses, _ita_loop_jit,
                                 get_step_impl)
from repro.core.batch import _ita_batch_loop_donated
from repro.core.distributed import _batch_2d_loop, _batch_dp_loop
from repro.core.dynamic import _warm_start_jit
from repro.core.live import SLACK, _ListUpdate, _relaid
from repro.graph import Degrees, Graph
from repro.graph.generators import TABLE3_PRESETS
from repro.kernels.spmv_ell.kernel import TPU_REFUSAL, spmv_ell_bucket

N = TABLE3_PRESETS["web-Google"]["n"]
M = TABLE3_PRESETS["web-Google"]["m"]
# the referenced core's out-edges on the benchmark's web-Google stand-in
M_CORE = 3_449_864
# the scan's schedule of each list of that stand-in (ScanPasses.of, its
# runs longest first; bench/generators/powerlaw_web.py, dataset_seed 0):
# 7.01 and 6.49 passes an edge, where every pass over the list took 23, 22
FULL_PASSES = ScanPasses((
    4874330, 4552006, 4055995, 3575935, 3155416, 2775058, 2429050, 2109952,
    1817822, 1552960, 1300255, 1069885, 855182, 661136, 490522, 332500,
    200773))
CORE_PASSES = ScanPasses((
    3197134, 2920934, 2571982, 2260091, 1987521, 1740229, 1517690, 1313162,
    1119678, 945972, 786636, 640593, 502517, 378463, 263018, 171734, 85076))
B = 16
DTYPE = jnp.float64
# insert slots of each edge list of the live layout (core/live.py)
C = int(np.ceil(M * SLACK))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def graph(one_chip):
    """Abstract web-Google graph on one described chip (shapes only)."""
    return Graph(src=_struct((M,), jnp.int32, one_chip),
                 dst=_struct((M,), jnp.int32, one_chip),
                 out_deg=_struct((N,), jnp.int32, one_chip),
                 in_deg=_struct((N,), jnp.int32, one_chip), n=N, m=M)


def _runs(sharding):
    """Abstract ``DenseBackend.prepare`` context of that graph: the full
    edge list and the referenced core's, so a push compiles both."""
    def edge_list(e, passes):
        return DenseRuns(src=_struct((e,), jnp.int32, sharding),
                         start=_struct((e,), jnp.bool_, sharding),
                         last=_struct((N,), jnp.int32, sharding),
                         passes=passes)
    return edge_list(M, FULL_PASSES)._replace(
        core=edge_list(M_CORE, CORE_PASSES),
        in_core=_struct((N,), jnp.bool_, sharding))


@pytest.fixture(scope="module")
def runs(one_chip):
    return _runs(one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    return compiled, mem


def _gathers(compiled):
    """The operand shape of each gather in a compiled program, sorted."""
    return sorted(re.findall(r"= (\w+\[[\d,]*\])\S* gather\(",
                             compiled.as_text()))


def _f32_elements(shapes: str) -> int:
    return sum(math.prod(int(d) for d in dims.split(",") if d)
               for dims in re.findall(r"f32\[([\d,]*)\]", shapes))


def _scan_writes(compiled):
    """Float32 words written in a compiled push by the scan's operations
    (op names under ``push/scan``): by its passes (the fusions rooted at
    their select), and by all of them, with the slices XLA materializes
    for a pass's shifted operand and for each finished piece.  The
    dynamic-update-slices that set the pieces side by side write in
    place and are left out.  Also the widths of the float32 copies
    anywhere in the program."""
    text = compiled.as_text()
    fused = set(re.findall(r"calls=%([\w.-]+)", text))
    passes, scan, copies, comp = 0, 0, [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        op = re.match(r"\s+(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(", line)
        if comp in fused or op is None:
            continue
        shapes, kind = op.groups()
        if kind in ("copy", "copy-start"):
            # a copy-start's shape names its source and its destination
            copies += [int(w) for w in re.findall(r"f32\[[\d,]*?(\d+)\]",
                                                  shapes)][:1]
        name = re.search(r'op_name="[^"]*/push/scan/([^"]*)"', line)
        if name is None or kind in ("dynamic-update-slice",
                                    "get-tuple-element", "bitcast"):
            continue
        scan += _f32_elements(shapes)
        if kind == "fusion" and name.group(1).endswith("select_n"):
            passes += _f32_elements(shapes)
    return passes, scan, copies


@pytest.fixture(scope="module")
def dense_push(graph, runs, one_chip):
    """The dense push compiled once per (rows, dtype): ``push`` for one
    row, ``push_batch`` for more."""
    dense = get_step_impl("dense")

    @cache
    def compile_push(batch, dtype=DTYPE):
        if batch == 1:
            return _compile(lambda g, r, w: dense.push(g, r, w), graph, runs,
                            _struct((N,), dtype, one_chip))
        return _compile(lambda g, r, W: dense.push_batch(g, r, W), graph,
                        runs, _struct((batch, N), dtype, one_chip))
    return compile_push


@pytest.mark.parametrize("batch", [1, B], ids=["push", "push_batch"])
def test_dense_push_compiles(dense_push, batch):
    compiled, mem = dense_push(batch)
    # the [B, m] gather is the largest buffer: it must fit one chip
    assert mem.temp_size_in_bytes < 16e9
    # no float64 scatter on TPU: XLA runs it one update at a time
    assert "scatter" not in compiled.as_text()
    # the full and the core edge list, chosen on the device
    assert "conditional" in compiled.as_text()


@pytest.mark.parametrize("batch", [1, B], ids=["push", "push_batch"])
def test_float64_push_gathers_both_words_with_one_index(dense_push, batch):
    """A TPU holds a float64 as two float32 words and would gather each on
    its own; the push stacks them as rows and gathers once per list walk
    (``_split_gather``): one edge gather per list, one readout each."""
    rows = 2 * batch
    assert _gathers(dense_push(batch)[0]) == sorted(
        [f"f32[{rows},{M}]", f"f32[{rows},{M_CORE}]"]
        + [f"f32[{rows},{N}]"] * 2)


def test_scan_walks_only_its_prefixes(dense_push):
    """Each pass of the ``[16, n]`` scan writes the prefix its schedule
    gives, both float32 words of each float64: Σ P_j words a row and
    list, where a pass over each whole list for every shift wrote
    ⌈log2 e⌉ · e (23 and 22).  With the slices XLA materializes, the scan
    writes under three times its passes' words (the full-pass scan wrote
    5.9 times these).  No copy in the push is an edge list wide, so no
    pass copies the suffix it leaves (one per pass would be 34 a list)."""
    passes, scan, copies = _scan_writes(dense_push(B)[0])
    walked = 2 * B * (sum(FULL_PASSES.prefix) + sum(CORE_PASSES.prefix))
    assert passes == walked
    assert walked < 0.31 * 2 * B * (23 * M + 22 * M_CORE)
    assert scan < 3 * walked
    assert not {M, M_CORE} & set(copies)


def test_one_vector_scan_takes_every_pass(dense_push):
    """A single vector's scan takes every pass over each whole list, as
    before the schedule, and copies no list."""
    passes, _, copies = _scan_writes(dense_push(1)[0])
    assert passes == 2 * (sum(ScanPasses.full(M).prefix)
                          + sum(ScanPasses.full(M_CORE).prefix))
    assert not {M, M_CORE} & set(copies)


@pytest.mark.parametrize("batch", [1, B], ids=["push", "push_batch"])
def test_float32_push_keeps_the_plain_gather(dense_push, batch):
    shape = "" if batch == 1 else f"{batch},"
    assert _gathers(dense_push(batch, jnp.float32)[0]) == sorted(
        [f"f32[{shape}{M}]", f"f32[{shape}{M_CORE}]"]
        + [f"f32[{shape}{N}]"] * 2)


def test_ita_solve_compiles(graph, runs, one_chip):
    """One whole device-resident ITA solve, as RankQuery runs it."""
    h = _struct((N,), DTYPE, one_chip)
    lowered = _ita_loop_jit.lower(graph, runs, h, h, 0.85, 1e-10,
                                  max_iter=10_000,
                                  backend=get_step_impl("dense"),
                                  signed=False)
    assert lowered.compile().memory_analysis() is not None


def test_donated_batch_loop_compiles(graph, runs, one_chip):
    """The accelerator serving path: the batched loop with H0 donated and
    the graph as an argument, so no edge array is a constant."""
    H0 = _struct((B, N), DTYPE, one_chip)
    lowered = _ita_batch_loop_donated.lower(
        graph, runs, H0, 0.85, 1e-10, max_iter=10_000,
        backend=get_step_impl("dense"))
    args = [(a.shape, a.donated) for a in jax.tree.leaves(lowered.args_info)]
    assert args[:2] == [((M,), False)] * 2  # src, dst are arguments
    assert ((B, N), True) in args           # the [B, n] buffer is donated
    assert "tf.aliasing_output" in lowered.as_text()
    assert lowered.compile().memory_analysis() is not None


def _live_runs(sharding):
    """Abstract live layout: both edge lists with their insert regions."""
    def edge_list(e, passes):
        return DenseRuns(src=_struct((e + C,), jnp.int32, sharding),
                         start=_struct((e + C,), jnp.bool_, sharding),
                         last=_struct((N,), jnp.int32, sharding),
                         passes=passes,
                         carry=_struct((C,), jnp.int32, sharding))
    return edge_list(M, FULL_PASSES)._replace(
        core=edge_list(M_CORE, CORE_PASSES),
        in_core=_struct((N,), jnp.bool_, sharding))


def _degrees(sharding):
    return Degrees(out_deg=_struct((N,), jnp.int32, sharding),
                   in_deg=_struct((N,), jnp.int32, sharding), n=N)


@pytest.fixture(scope="module")
def live_refresh(one_chip):
    """A DeltaQuery's device work on the live layout, compiled: the warm
    start's push and the signed cascade, over the degrees alone."""
    runs, degrees = _live_runs(one_chip), _degrees(one_chip)
    h = _struct((N,), DTYPE, one_chip)
    dense = get_step_impl("dense")
    warm = _warm_start_jit.lower(degrees, runs, h, h, 0.85, backend=dense)
    loop = _ita_loop_jit.lower(degrees, runs, h, h, 0.85, 1e-10,
                               max_iter=100_000, backend=dense, signed=True)
    return {"warm": warm.compile(), "loop": loop.compile()}


def test_live_refresh_compiles(live_refresh):
    assert "scatter" not in live_refresh["warm"].as_text()
    compiled = live_refresh["loop"]
    assert "scatter" not in compiled.as_text()
    assert "conditional" in compiled.as_text()


@pytest.mark.parametrize("program", ["warm", "loop"])
def test_live_refresh_gathers_both_words_with_one_index(live_refresh,
                                                        program):
    """On the live layout too, each float64 gather is one: the edge gather
    of each padded list, its carry gather over the insert region and its
    readout."""
    assert _gathers(live_refresh[program]) == sorted(
        [f"f32[2,{M + C}]", f"f32[2,{M_CORE + C}]"]
        + [f"f32[2,{C}]"] * 2 + [f"f32[2,{N}]"] * 2)


def test_live_donated_batch_loop_compiles(one_chip):
    """PPR served on the live layout: the donated batched loop reads the
    graph's degrees, so its only edge-sized arguments are the padded
    lists, whose shapes no delta changes."""
    H0 = _struct((B, N), DTYPE, one_chip)
    lowered = _ita_batch_loop_donated.lower(
        _degrees(one_chip), _live_runs(one_chip), H0, 0.85, 1e-10,
        max_iter=10_000, backend=get_step_impl("dense"))
    args = [(a.shape, a.donated) for a in jax.tree.leaves(lowered.args_info)]
    assert args[:2] == [((N,), False)] * 2  # out_deg, in_deg
    assert ((M,), False) not in args         # no Graph's src or dst
    assert ((B, N), True) in args
    assert lowered.compile().memory_analysis() is not None


def test_live_delta_update_compiles(one_chip):
    """The delta's update of the live layout, in its fixed shapes."""
    def ints(size):
        return _struct((size,), jnp.int32, one_chip)

    update = _ListUpdate(src=ints(C), dst=ints(C), kill=ints(C))
    lowered = _relaid.lower(_live_runs(one_chip), ints(N), ints(N), update,
                            update, ints(C), _degrees(one_chip), ints(2 * C),
                            ints(2 * C), ints(2 * C))
    assert lowered.compile().memory_analysis() is not None


def test_batch_parallel_loop_compiles_on_4x1(topo):
    """The (4, 1) batch-parallel loop: each chip runs the dense push_batch
    on its rows against replicated graph operands."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, P())
    g = Graph(src=_struct((M,), jnp.int32, rep),
              dst=_struct((M,), jnp.int32, rep),
              out_deg=_struct((N,), jnp.int32, rep),
              in_deg=_struct((N,), jnp.int32, rep), n=N, m=M)
    runs = _runs(rep)
    run = _batch_dp_loop(mesh, get_step_impl("dense"), 0.85, 1e-10, 10_000,
                         "data")
    compiled = run.lower(
        g, runs, _struct((B, N), DTYPE, NamedSharding(mesh, P("data", None)))
    ).compile()
    assert "scatter" not in compiled.as_text()


def test_sharded_dense_round_compiles_on_2x2(topo):
    """The (2, 2) vertex-sharded dense loop of ``ita_batch_distributed``."""
    R, C = 2, 2
    mesh = Mesh(np.asarray(topo.devices).reshape(R, C), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    n_pad = -(-N // C) * C
    e_pad = -(-int(M / C * 1.05) // 8) * 8
    run = _batch_2d_loop(mesh, n_pad, 0.85, 1e-10, 10_000, "data", "model")
    sh = partial(NamedSharding, mesh)
    args = (_struct((B, n_pad), DTYPE, sh(P("data", "model"))),
            _struct((C, e_pad), jnp.int32, sh(P("model", None))),
            _struct((C, e_pad), jnp.int32, sh(P("model", None))),
            _struct((n_pad,), DTYPE, sh(P("model"))),
            _struct((n_pad,), jnp.bool_, sh(P("model"))))
    compiled = run.lower(*args).compile()
    # TPU has no 64-bit reduce-scatter: the column exchange is an all-to-all
    assert "all-to-all" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason=f"Mosaic: 'Only 2D gather is supported' "
                          f"({TPU_REFUSAL})")
def test_ell_kernel_lowers_at_web_google_width(one_chip):
    rows, k = 1 << 19, 8
    w = _struct((N + 1,), jnp.float32, one_chip)
    idx = _struct((rows, k), jnp.int32, one_chip)
    jax.jit(partial(spmv_ell_bucket, interpret=False)).lower(w, idx).compile()
