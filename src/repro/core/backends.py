"""Pluggable solver backends — one push interface, many edge layouts.

The paper's hot op is a single push round: ``y[dst] += w[src]`` over every
edge, where ``w`` is the pre-scaled per-source value (``c·h·inv_deg`` for
ITA, ``pi·inv_deg`` for the power method).  Every solver in ``repro.core``
used to hard-code the dst-sorted ``segment_sum`` realisation of that op;
this module turns the realisation into a registry of interchangeable
backends so the solvers pick a layout/schedule without changing numerics
(the paper's §IV commutativity result is exactly the licence to do this —
same commutative sum, different grouping):

  * ``"dense"``    — masked SpMV over the dst-sorted COO edges, summed
                     per vertex by a segmented scan (paper-faithful
                     synchronous baseline); a round whose input is zero
                     off the referenced core (paper §III) walks only the
                     core's out-edges, with the same bits.
  * ``"frontier"`` — active-set compression: each round gathers only the
                     out-edges of currently-active vertices into a
                     power-of-two-padded bucket, so the per-iteration edge
                     working set shrinks with the frontier.  Host-driven
                     (data-dependent shapes), bounded recompiles.
  * ``"ell"``      — bucketed-ELL layout driven by the Pallas kernel
                     ``repro.kernels.spmv_ell`` (interpret-mode on CPU;
                     Mosaic refuses it on TPU, see ``refused_on``).
                     Conversion is cached on the :class:`Graph` via
                     ``Graph.ell()``.
  * ``"frontier_priority"`` — the frontier machinery with the D-Iteration
                     descending-residual emission order (arXiv 1501.06350)
                     and a declared cost discount on undirected graphs
                     (the ``choose_backend`` undirected-schedule rule).

Registry contract
-----------------
A backend is a :class:`SolverBackend` with

  ``prepare(g) -> ctx``           one-time per-graph context (a pytree);
  ``push(g, ctx, w) -> y``        y[dst] = Σ_{(src,dst)∈E} w[src], [n]→[n];
  ``push_batch(g, ctx, W) -> Y``  the same over a [B, n] batch;
  ``push_counted`` / ``push_batch_counted``  the same, and whether the
                                  push walked a prepared core edge list
                                  (None where the layout keeps none);
  ``core_edges(ctx)``             that list's length, or None;
  ``scan_edge_passes(ctx)``       the scan's passes per edge, per list,
                                  or None where the layout scans none;
  ``capabilities()``              a :class:`BackendCapabilities` record —
                                  what this layout can do (trace inside
                                  jit, batch, donate, mesh-shard, update);
  ``cost(stats, cfg) -> float``   rough per-solve cost estimate, used by
                                  the engine planner to pick a backend for
                                  ``step_impl="auto"`` and reported in
                                  ``ExecutionPlan.explain()``.

The planner (``core/query.py`` + ``PageRankEngine.plan``) consults the
declared capabilities instead of hard-coding per-name compatibility rules,
so a newly registered layout becomes plannable by declaration alone.
``jittable`` survives as a plain attribute (it doubles as the
``capabilities().jittable`` default) for the host-loop dispatch in
``run_ita_loop``.

``ita_step_impl`` / ``signed_ita_step_impl`` build the full ITA round on
top of ``push``; ``run_ita_loop`` runs either the jitted device-resident
``while_loop`` (jittable backends) or the host-driven loop (frontier) with
identical semantics.  New layouts register with
``@register_step_impl("name")``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.structure import Graph

__all__ = [
    "BackendCapabilities", "SolverBackend", "StepBackend", "STEP_IMPLS",
    "STEP_IMPL_CLASSES", "declared_capabilities",
    "register_step_impl", "get_step_impl", "available_step_impls",
    "resolve_step_impl", "choose_backend", "ita_step_impl",
    "signed_ita_step_impl", "run_ita_loop",
]


# ---------------------------------------------------------------------------
# Capabilities
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What one edge layout/schedule can do — the planner's vocabulary.

    Every field is a *declaration* the engine planner (``core/query.py``)
    reads when mapping a query onto an execution path; adding a layout
    means declaring its row here, not editing engine branches.

    Attributes
    ----------
    jittable : bool
        ``push`` may be traced inside ``jit`` / ``while_loop`` /
        ``shard_map`` (host-driven layouts like "frontier" may not).
    batched : bool
        has a [B, n] ``push_batch`` worth using (vs. B sequential pushes).
    donation : bool
        the compiled batched loop may donate the [B, n] information
        buffer (requires a device-resident jitted loop).
    dynamic_update : bool
        supports the signed incremental cascade of ``core/dynamic.py``
        (pushes of negative corrections).
    batch_parallel_mesh : bool
        can serve under ``shard_map`` with the batch axis on "data"
        (requires ``jittable``).
    vertex_sharded_mesh : bool
        implements the C-way column-sharded (C > 1) push schedule of
        ``core/distributed.py`` ("dense" via the ``partition_cols``
        segment-sum, "ell" via per-block bucketed tiles through the
        batched Pallas kernel).
    dtypes : tuple[str, ...]
        value dtypes the push is validated for.
    """

    jittable: bool = True
    batched: bool = True
    donation: bool = True
    dynamic_update: bool = True
    batch_parallel_mesh: bool = True
    vertex_sharded_mesh: bool = False
    dtypes: tuple = ("float32", "float64")

    def __post_init__(self):
        # declarations must be internally consistent, or the planner will
        # hand out plans the executor cannot drive (e.g. donating a buffer
        # into a loop that cannot be jitted) — fail at the declaration
        # site, not with a tracer error mid-query.
        if not self.jittable:
            for f in ("donation", "batch_parallel_mesh",
                      "vertex_sharded_mesh"):
                if getattr(self, f):
                    raise ValueError(
                        f"inconsistent BackendCapabilities: {f}=True "
                        f"requires jittable=True (a host-driven push "
                        f"cannot run inside jit/shard_map)")

    def summary(self) -> str:
        """Compact flag list for ``ExecutionPlan.explain()``."""
        flags = [f for f in ("jittable", "batched", "donation",
                             "dynamic_update", "batch_parallel_mesh",
                             "vertex_sharded_mesh") if getattr(self, f)]
        return ", ".join(flags) if flags else "none"


def _est_rounds(c: float = 0.85, tol: float = 1e-10) -> float:
    """Geometric-decay round estimate: residual ~ c^t ⇒ t ~ log tol / log c."""
    c = min(max(float(c), 1e-6), 1.0 - 1e-9)
    tol = min(max(float(tol), 1e-300), 1.0 - 1e-9)
    return max(1.0, math.log(tol) / math.log(c))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
class SolverBackend:
    """Base class: one edge-propagation layout/schedule.

    Subclasses implement the push pair and *declare* what they can do via
    the class-level ``capabilities_decl`` row (preferred — statically
    introspectable, see :func:`declared_capabilities`) or by overriding
    :meth:`capabilities`; the engine planner does the rest.
    """

    name: str = "?"
    jittable: bool = True
    # Class-level capability declaration.  Setting it here (rather than
    # constructing inside capabilities()) lets tools read the row without
    # instantiating the backend — the repro-lint AST layer checks the
    # declaration against the class body without importing this module.
    capabilities_decl: Optional[BackendCapabilities] = None
    # Declared cost discount on symmetric edge sets (Graph.is_undirected).
    # None means "no structural advantage"; a float f means cost() scales
    # by f when the planner's stats carry undirected=True, and
    # choose_backend names the undirected-schedule rule in its reason.
    undirected_cost_factor: Optional[float] = None

    def prepare(self, g: Graph):
        """Per-graph context (pytree), built once outside the loop."""
        return None

    def refused_on(self, platform: str) -> Optional[str]:
        """Why this backend's push cannot compile on ``platform``, or None.

        ``choose_backend`` drops a refused backend from the "auto" pool and
        :func:`resolve_step_impl` raises on an explicit request for it.
        """
        return None

    def push(self, g: Graph, ctx, w: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def push_batch(self, g: Graph, ctx, W: jnp.ndarray) -> jnp.ndarray:
        """[B, n] → [B, n]; default is a vmap of ``push``."""
        return jax.vmap(lambda w: self.push(g, ctx, w))(W)

    def push_counted(self, g: Graph, ctx, w: jnp.ndarray):
        """``push``, and whether it walked a prepared core edge list in
        place of all m edges: a traced bool, or None where ``ctx`` holds
        no such list (the solver loops sum it as ``core_rounds``)."""
        return self.push(g, ctx, w), None

    def push_batch_counted(self, g: Graph, ctx, W: jnp.ndarray):
        """``push_batch``, counted as :meth:`push_counted` counts."""
        return self.push_batch(g, ctx, W), None

    def core_edges(self, ctx) -> Optional[int]:
        """Edges in ``ctx``'s core edge list, or None where it has none."""
        return None

    def scan_edge_passes(self, ctx) -> Optional[dict]:
        """The scan passes per edge slot a batched push walks on each of
        ``ctx``'s edge lists (``"full"``, and ``"core"`` where it keeps
        one), or None where the layout scans none."""
        return None

    def capabilities(self) -> BackendCapabilities:
        """Declared capability row: the class-level ``capabilities_decl``
        when set, else a default deriving everything requiring a traced
        loop from ``jittable``."""
        if self.capabilities_decl is not None:
            return self.capabilities_decl
        return BackendCapabilities(
            jittable=self.jittable,
            donation=self.jittable,
            batch_parallel_mesh=self.jittable,
        )

    def cost(self, stats: Optional[dict] = None, cfg=None) -> float:
        """Rough per-solve cost estimate in edge-traversal units.

        ``stats`` is a ``dict(n=..., m=...)`` (``None`` ⇒ unit edge count,
        which still ranks backends relatively); ``cfg`` supplies ``c`` and
        the stopping threshold when available.  This is a *planning*
        number — only its ordering across backends matters.  The default
        charges one unit per edge per round (the dense baseline).
        """
        m = float((stats or {}).get("m", 1) or 1)
        rounds = _est_rounds(getattr(cfg, "c", 0.85),
                             getattr(cfg, "xi", None)
                             or getattr(cfg, "tol", None) or 1e-10)
        return m * rounds


# Back-compat alias: PR-1 code and tests subclass/import StepBackend.
StepBackend = SolverBackend

STEP_IMPLS: dict[str, SolverBackend] = {}

# name -> class, kept alongside the instances so capability declarations
# can be read without executing backend code (declared_capabilities).
STEP_IMPL_CLASSES: dict[str, type] = {}


def register_step_impl(name: str) -> Callable[[type], type]:
    """Class decorator: instantiate and register a backend under ``name``."""
    def deco(cls: type) -> type:
        inst = cls()
        inst.name = name
        STEP_IMPLS[name] = inst
        STEP_IMPL_CLASSES[name] = cls
        return cls
    return deco


def declared_capabilities(backend) -> BackendCapabilities:
    """Capability row for a backend name or class, without instantiation.

    Resolves the class-level ``capabilities_decl`` (the introspectable
    declaration every shipped backend sets); classes that leave it None get
    the same jittable-derived default :meth:`SolverBackend.capabilities`
    would build — so for every registered backend this is value-identical
    to ``get_step_impl(name).capabilities()``.
    """
    cls = STEP_IMPL_CLASSES[backend] if isinstance(backend, str) else backend
    decl = getattr(cls, "capabilities_decl", None)
    if decl is not None:
        return decl
    jittable = bool(getattr(cls, "jittable", True))
    return BackendCapabilities(
        jittable=jittable, donation=jittable, batch_parallel_mesh=jittable)


def get_step_impl(name: str) -> SolverBackend:
    if name not in STEP_IMPLS:
        raise KeyError(
            f"unknown step_impl {name!r}; available: {sorted(STEP_IMPLS)}")
    return STEP_IMPLS[name]


def available_step_impls(jittable_only: bool = False) -> list[str]:
    return sorted(n for n, b in STEP_IMPLS.items()
                  if b.capabilities().jittable or not jittable_only)


def choose_backend(stats: Optional[dict] = None, cfg=None, *,
                   jittable_only: bool = True,
                   require: tuple = ()) -> tuple[str, str]:
    """Cost-based backend selection over the declared capability rows.

    Returns ``(name, reason)`` — the registered backend with the lowest
    :meth:`SolverBackend.cost` estimate (ties broken toward "dense", then
    lexicographically, so an equal-cost custom registration never silently
    hijacks ``step_impl="auto"``).  ``jittable_only`` restricts the pool
    to backends whose push can live inside the device-resident loop —
    the "auto" contract, since a host-driven layout must be an explicit
    opt-in.  ``require`` names additional :class:`BackendCapabilities`
    flags every candidate must declare (e.g. ``("vertex_sharded_mesh",)``
    when the engine prepares an (R, C) mesh with C > 1), and ``stats`` may
    carry a ``"mesh"`` entry — the normalized (R, C) — that mesh-aware
    cost models read (plus ``"platform"`` / ``"dtype"`` overrides, and
    ``"undirected"`` — ``Graph.is_undirected`` — which backends declaring
    an ``undirected_cost_factor`` fold into their estimate; when such a
    backend wins on a symmetric edge set the reason names the
    undirected-schedule rule).  Backends refused on the deciding platform
    (:meth:`SolverBackend.refused_on`: the ELL kernel on TPU) never enter
    the pool.

    When the process-wide roofline cost table
    (``repro.roofline.planner_costs``) holds a measured sample for EVERY
    eligible candidate on the deciding platform, the measured estimated
    seconds re-rank the pool and the reason names the measured source;
    any coverage gap falls back to the declared constants (mixing
    measured seconds with declared units would compare incommensurable
    numbers).  See docs/ROOFLINE.md.
    """
    platform = (stats or {}).get("platform") or jax.default_backend()
    cands = []
    for name, b in STEP_IMPLS.items():
        caps = b.capabilities()
        if jittable_only and not caps.jittable:
            continue
        if any(not getattr(caps, r) for r in require):
            continue
        if b.refused_on(platform) is not None:
            continue
        cands.append((b.cost(stats, cfg), 0 if name == "dense" else 1, name))
    if not cands:
        raise RuntimeError(
            "no eligible backend registered"
            + (f" (require={list(require)})" if require else ""))
    mesh = (stats or {}).get("mesh")
    undirected = bool((stats or {}).get("undirected"))
    suffix = (f"platform={platform}"
              + (f"; mesh={tuple(mesh)}" if mesh else "")
              + ("; undirected=True" if undirected else "")
              + (f"; require={list(require)}" if require else "") + ")")
    from ..roofline.planner_costs import rank_measured

    # None when the table is empty or misses a candidate: declared costs.
    measured = rank_measured([n for _, _, n in cands], stats, cfg)
    if measured is not None:
        m_cands = [(measured[n], 0 if n == "dense" else 1, n)
                   for _, _, n in cands]
        _, _, name = min(m_cands)
        m_others = ", ".join(f"{n}~{s:.3g}s" for s, _, n in sorted(m_cands))
        reason = (f"lowest measured roofline cost among eligible "
                  f"backends ({m_others}; cost source: measured; "
                  + suffix)
    else:
        cost, _, name = min(cands)
        others = ", ".join(f"{n}={c:.3g}" for c, _, n in sorted(cands))
        reason = (f"lowest est. cost among eligible backends ({others}; "
                  + suffix)
    factor = getattr(STEP_IMPLS[name], "undirected_cost_factor", None)
    if undirected and factor is not None:
        reason += (f" + undirected-schedule rule: symmetric edge set, "
                   f"{name!r} declares a x{factor:g} schedule discount")
    return name, reason


def resolve_step_impl(name: Optional[str]) -> str:
    """Map ``None``/"auto" to the cost-chosen default, else validate ``name``.

    An explicit backend that declares itself refused on this platform
    (:meth:`SolverBackend.refused_on`) raises ``ValueError`` here, before
    any graph is prepared for it.
    """
    if name is None or name == "auto":
        return choose_backend()[0]
    refusal = get_step_impl(name).refused_on(jax.default_backend())
    if refusal is not None:
        raise ValueError(f"step_impl={name!r} cannot run on "
                         f"{jax.default_backend()}: {refusal}")
    return name


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------
@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class ScanPasses:
    """The pass schedule of an edge list's segmented scan, static in every
    program that walks the list (a jit cache key, never a traced array).

    ``prefix[j]`` is the length of the shortest prefix of the list that
    holds every edge whose run is longer than ``2**j``: the scan's pass of
    shift ``2**j`` walks only ``[0, prefix[j])``, as only those runs still
    change (:func:`_run_sums`).  Any run order is correct; listing the runs
    longest first makes each prefix as short as the run lengths allow.
    """

    prefix: tuple = ()

    @staticmethod
    def of(start: np.ndarray) -> "ScanPasses":
        """The schedule of a host list from its run-start flags."""
        if not start.size:
            return ScanPasses()
        first = np.flatnonzero(start)
        end = np.append(first[1:], start.size)
        length = end - first
        prefix, k = [], 1
        while (length > k).any():
            prefix.append(int(end[length > k].max()))
            k *= 2
        return ScanPasses(tuple(prefix))

    @staticmethod
    def full(e: int) -> "ScanPasses":
        """Every pass over all ``e`` slots: the schedule of a list whose
        run lengths are not known when it is laid out."""
        return ScanPasses((e,) * (e - 1).bit_length() if e else ())


class DenseRuns(NamedTuple):
    """An edge list as runs of each vertex's in-edges, longest run first:
    the dense backend's per-graph context.

    ``src[e]`` is edge e's source; ``start[e]`` marks the first edge of a
    run of equal destination; ``last[v]`` is the position of vertex v's
    last in-edge, -1 when it has none.  The runs are listed longest first,
    by destination within a length, so ``last`` is not monotone; the
    scan's ``passes`` (:class:`ScanPasses`) follow from that order.

    ``core`` is the referenced core's own edge list (paper §III, see
    ``Graph.reference_levels``): the out-edges of the vertices of no
    finite weak-unreferenced level, in the order of its own run lengths.
    Each run of the full list lists its edges from outside the core first,
    so its tail is the core list's run, edge for edge.  ``in_core`` marks
    those vertices.  Both are None when the core holds every edge.

    ``carry`` is set on a list laid out for edge deltas
    (``repro.core.live``): its last ``C = carry.size`` slots are an insert
    region of runs of its own, and source ``n`` is a pad vertex that always
    pushes zero (a deleted edge points there).  ``passes`` covers the main
    region only; the insert region takes every pass over its C slots.
    After the scan, an insert run's last slot adds the sum at position
    ``carry[j]`` (its vertex's run in the main list, -1 for none), and
    ``last`` points at that slot.
    """

    src: jnp.ndarray    # int32[e]
    start: jnp.ndarray  # bool[e]
    last: jnp.ndarray   # int32[n]
    passes: ScanPasses
    core: Optional["DenseRuns"] = None
    in_core: Optional[jnp.ndarray] = None  # bool[n]
    carry: Optional[jnp.ndarray] = None    # int32[C]


def _runs(src: np.ndarray, dst: np.ndarray, n: int,
          slack: Optional[int] = None) -> DenseRuns:
    """:class:`DenseRuns` of a host edge list whose runs of equal ``dst``
    are contiguous, in any order; with ``slack``, an empty insert region
    of that many slots after it."""
    start = np.ones(dst.shape, bool)
    start[1:] = dst[1:] != dst[:-1]
    end = np.ones(dst.shape, bool)
    end[:-1] = start[1:]
    last = np.full(n, -1, np.int32)
    last[dst[end]] = np.flatnonzero(end)
    passes = ScanPasses.of(start)
    carry = None
    if slack is not None:
        src = np.concatenate([src, np.full(slack, n, src.dtype)])
        start = np.concatenate([start, np.ones(slack, bool)])
        carry = jnp.full((slack,), -1, jnp.int32)
    return DenseRuns(src=jnp.asarray(src.astype(np.int32)),
                     start=jnp.asarray(start), last=jnp.asarray(last),
                     passes=passes, carry=carry)


def _longest_first(dst: np.ndarray, n: int,
                   tie: Optional[np.ndarray] = None) -> np.ndarray:
    """The stable order that lists ``dst``'s runs longest first, by
    destination within a length, and by ``tie`` within a run."""
    deg = np.bincount(dst, minlength=n)
    key = (deg.max(initial=0) - deg[dst]) * np.int64(n) + dst
    if tie is not None:
        key = 2 * key + tie
    return np.argsort(key, kind="stable")


def _core_order(g: Graph):
    """The dense push's two edge orders, and the referenced core.

    Returns ``(order, core, in_core)``: ``order`` lists the graph's edges
    run by run, longest run first, each run's edges from outside the core
    first; ``core`` lists the edges whose source is in the core, longest
    of their runs first, each run in the full list's order (its tail
    there); ``in_core`` marks the core's vertices.  ``core`` and
    ``in_core`` are None when the core holds every edge.
    """
    dst = np.asarray(g.dst)
    in_core = g.reference_levels < 0
    from_core = in_core[np.asarray(g.src)]
    if from_core.all():
        return _longest_first(dst, g.n), None, None
    core = np.flatnonzero(from_core)
    return (_longest_first(dst, g.n, from_core),
            core[_longest_first(dst[core], g.n)], in_core)


def _dense_runs(g: Graph) -> DenseRuns:
    """Host-side :class:`DenseRuns` of a concrete graph, with the
    referenced core's list beside the full one when the core leaves some
    edges out."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    order, core, in_core = _core_order(g)
    runs = _runs(src[order], dst[order], g.n)
    if core is None:
        return runs
    return runs._replace(core=_runs(src[core], dst[core], g.n),
                         in_core=jnp.asarray(in_core))


def _passes_per_slot(runs: DenseRuns) -> float:
    """The scan's passes per slot of ``runs``: the slots its schedule
    walks, summed over the passes, over the list's slots (a full scan
    walks ``ceil(log2 e)``)."""
    slots = runs.src.shape[-1]
    if not slots:
        return 0.0
    walked = sum(runs.passes.prefix)
    if runs.carry is not None:
        walked += sum(ScanPasses.full(runs.carry.shape[-1]).prefix)
    return walked / slots


def _split_gather(vals: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``vals[..., idx]`` for 64-bit floats, both float32 halves of each
    value fetched with one index.

    A TPU holds a float64 as a pair of float32 words, and its compiler
    gathers each word on its own: two 1-D gathers over the same indices.
    On a v5e one gather of two-word rows costs less than either of them
    (PERF.md, "Where the time goes").  Here each value is split by value
    into ``hi = f32(v)`` and ``lo = f32(v - hi)``, the halves stacked as
    rows (``[2, n]``, or ``[2B, n]`` for B rows), gathered once, and
    added back.  That is exact where a float64 is a float32 pair, as on
    the TPU; a true float64 loses the bits below ``lo``.  A zero ``lo``
    takes the sign of ``hi``, so ±0 and infinities come back as they went
    in.  (The TPU compiler refuses a bitcast of float64 to
    ``u32[..., 2]``, the other way to one index.)
    """
    lead, n = vals.shape[:-1], vals.shape[-1]
    hi = vals.astype(jnp.float32)
    lo = jnp.where(hi == vals, jnp.copysign(jnp.zeros_like(hi), hi),
                   (vals - hi.astype(vals.dtype)).astype(jnp.float32))
    rows = jnp.concatenate([hi.reshape(math.prod(lead), n),
                            lo.reshape(math.prod(lead), n)])
    got = rows[:, idx]
    k = got.shape[0] // 2
    out = got[:k].astype(vals.dtype) + got[k:].astype(vals.dtype)
    return out.reshape(lead + idx.shape)


def _gather(vals: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``vals[..., idx]``: one index for both halves of a float64 where it
    is compiled for the TPU (:func:`_split_gather`); the plain gather
    elsewhere, and for narrower values, so those keep their bits."""
    def plain(vals, idx):
        return vals[..., idx]
    if vals.dtype != jnp.float64:
        return plain(vals, idx)
    return jax.lax.platform_dependent(vals, idx, tpu=_split_gather,
                                      default=plain)


def _scan(x: jnp.ndarray, f: jnp.ndarray, passes: ScanPasses) -> list:
    """The segmented Hillis-Steele passes of ``x`` (edges on the last
    axis) with run-start flags ``f``, as pieces that concatenate to the
    scanned list.

    Before the pass of shift ``s``, ``f`` marks the edges less than ``s``
    from their run's start; the pass adds ``x[i - s]`` at every other edge.
    So a run of length r is final after the passes with ``s < r``, and the
    pass of shift ``2**j`` changes nothing past ``passes.prefix[j]``.  Each
    pass walks only its prefix; the suffix it leaves is final and set
    aside, never copied again until the one concatenation.
    """
    lead = [(0, 0)] * (x.ndim - 1)
    done, k = [], 1
    for p in passes.prefix:
        if p < x.shape[-1]:
            done.append(x[..., p:])
            x, f = x[..., :p], f[:p]
        x = jnp.where(f, x, x + jnp.pad(x[..., :-k], lead + [(k, 0)]))
        f = f | jnp.pad(f[:-k], (k, 0), constant_values=True)
        k *= 2
    return [x] + done[::-1]


def _run_sums(vals: jnp.ndarray, runs: DenseRuns) -> jnp.ndarray:
    """Sum ``vals`` (edges on the last axis) over each vertex's run.

    A segmented Hillis-Steele scan along the edge axis, then one gather
    at each run's last edge; it scatters nothing.  On more than one row,
    the pass of shift s walks only the prefix of the list that holds the
    runs longer than s (``runs.passes``, :func:`_scan`); a single vector
    takes every pass over the whole list.  With the runs listed longest
    first, the mean pass count per edge follows the run lengths (about 7
    on the web graphs) where a pass over the whole list for each shift
    below e took ``ceil(log2 e)`` (22 or 23).  Each run still gets every pass
    that changes it, with the same operands, so every sum keeps its bits.

    The sum at a run's last edge depends only on the run's values counted
    back from that edge, so exact zeros leading a run leave it bit for bit
    as the run without them gives it, and so does the run's place in the
    list.  The live layout's insert region is scanned as a list of its
    own, every pass over its C slots.  The readout gather, and the live
    layout's carry gather, go through :func:`_gather`: on a TPU one index
    fetches both float32 words of a float64 sum.
    """
    if vals.shape[-1] == 0:
        return jnp.zeros(vals.shape[:-1] + runs.last.shape, vals.dtype)
    e = vals.shape[-1] - (0 if runs.carry is None else runs.carry.shape[0])
    with jax.named_scope("scan"):
        if math.prod(vals.shape[:-1]) == 1:
            # one vector: every pass over the whole list.  Its passes are
            # cheap, and the pieces cost it more than the passes they save
            # (a rank push 0.3 ms slower, a live core push 4.8 ms, on a
            # v5e at web-Google size, where [16, n] gained 43 to 62 ms).
            pieces = _scan(vals, runs.start, ScanPasses.full(vals.shape[-1]))
        else:
            pieces = _scan(vals[..., :e], runs.start[:e], runs.passes)
            if runs.carry is not None:
                pieces += _scan(vals[..., e:], runs.start[e:],
                                ScanPasses.full(vals.shape[-1] - e))
        x = jnp.concatenate(pieces, -1)
    with jax.named_scope("readout"):
        if runs.carry is not None:
            # each insert run's last slot takes its vertex's main sum
            main = jnp.where(runs.carry >= 0,
                             _gather(x, jnp.maximum(runs.carry, 0)), 0)
            x = jax.lax.dynamic_update_slice_in_dim(
                x, x[..., e:] + main, e, axis=x.ndim - 1)
        return jnp.where(runs.last >= 0,
                         _gather(x, jnp.maximum(runs.last, 0)), 0)


def _walk(vals: jnp.ndarray, runs: DenseRuns) -> jnp.ndarray:
    """Push ``vals`` (vertices on the last axis) along one edge list.

    The edge gather ``vals[..., src]`` goes through :func:`_gather`: where
    float64 is a float32 pair (the TPU), both words of each value come
    with one index, in one gather in place of two.
    """
    with jax.named_scope("gather"):
        if runs.carry is not None:  # the pad vertex n pushes zero
            vals = jnp.concatenate(
                [vals, jnp.zeros(vals.shape[:-1] + (1,), vals.dtype)], -1)
        gathered = _gather(vals, runs.src)
    return _run_sums(gathered, runs)


def _walk_branch(runs: DenseRuns):
    # lax.cond files each branch's operations under "cond/branch_<i>_fun";
    # a "push" scope inside keeps push/gather, push/scan and push/readout
    # contiguous in the operations' names, where a profile reads them.
    def branch(vals):
        with jax.named_scope("push"):
            return _walk(vals, runs)
    return branch


def _dense_push(vals: jnp.ndarray, runs: DenseRuns):
    """The dense push of ``vals`` ([n] or [B, n]), and whether it walked
    the core list: a traced bool, or None where there is none.

    A vertex outside the core can hold information only in the first
    rounds of a solve; once ``vals`` is zero off the core, every edge
    outside the core list carries an exact zero.  The push checks that on
    ``vals`` itself and then walks the core list, whose sums equal the
    full list's bit for bit (:func:`_run_sums`).  Where it does not hold,
    it walks every edge, so the result never rests on the level theory.
    """
    if runs.core is None:
        return _walk(vals, runs), None
    off_core = jnp.any(jnp.logical_and(vals != 0,
                                       jnp.logical_not(runs.in_core)))
    y = jax.lax.cond(off_core, _walk_branch(runs), _walk_branch(runs.core),
                     vals)
    return y, jnp.logical_not(off_core)


@register_step_impl("dense")
class DenseBackend(StepBackend):
    """Sorted segment-sum over the COO edge list, grouped by destination.

    The segment-sum is a segmented scan over each vertex's run of
    in-edges (:func:`_run_sums`), not a scatter-add: XLA's float64
    scatter-add on a TPU v5e takes about 0.58 s per push of web-Google's
    5.06M edges, the scan about 0.09 s.  ``ctx`` is the graph's
    :class:`DenseRuns`; a push called with ``ctx=None`` builds it on the
    host from the (concrete) graph.  Each list lists its runs longest
    first, so the scan's pass of shift s walks only the prefix that holds
    the runs longer than s: on a power-law graph about 7 passes an edge
    in place of ``ceil(log2 e)``, with the same bits
    (``scan_edge_passes``).

    Each push walks either all m edges or, when its input is zero on
    every vertex outside the referenced core, only the core's out-edges
    (:func:`_dense_push`).  On a rank solve that holds from round K + 2
    for a deepest weak-unreferenced level K, and on a PPR row seeded in
    the core from round 1.  The result is the same bit for bit either way.

    Each float64 gather of the push (the edge gather, the readout and the
    live layout's carry) fetches both float32 words of a value with one
    index where it is compiled for a TPU, which holds a float64 as such a
    pair and would otherwise gather each word on its own
    (:func:`_split_gather`).  Only there: on a true float64, as on
    the CPU, the split would drop bits, so every other platform, and
    every narrower dtype, keeps the plain gather.

    In float64 both agree with a numpy sum to 1e-12 relative on a v5e.
    In float32 there, a ``[16, n]`` push_batch at web-Google size
    returned a few sums too large by up to 9 in 3e3, though one vector
    and ``[2, n]`` were right (PERF.md, "Findings"; cause open).
    """

    # the paper-faithful C>1 column-sharded schedule (partition_cols
    # COO blocks + segment-sum, core/distributed.py), hence
    # vertex_sharded_mesh.
    capabilities_decl = BackendCapabilities(vertex_sharded_mesh=True)

    def prepare(self, g: Graph) -> DenseRuns:
        return _dense_runs(g)

    def core_edges(self, ctx: DenseRuns) -> Optional[int]:
        return None if ctx.core is None else int(ctx.core.src.shape[-1])

    def scan_edge_passes(self, ctx: DenseRuns) -> dict:
        lists = {"full": ctx, "core": ctx.core}
        return {name: _passes_per_slot(runs) for name, runs in lists.items()
                if runs is not None}

    def push_counted(self, g: Graph, ctx, w: jnp.ndarray):
        return _dense_push(w, ctx if ctx is not None else _dense_runs(g))

    def push_batch_counted(self, g: Graph, ctx, W: jnp.ndarray):
        # one gather + one scan over the trailing axis beats B separate
        # scans: the edge index stream is read once per batch, and one
        # test over all B rows picks the list.
        return _dense_push(W, ctx if ctx is not None else _dense_runs(g))

    def push(self, g: Graph, ctx, w: jnp.ndarray) -> jnp.ndarray:
        return self.push_counted(g, ctx, w)[0]

    def push_batch(self, g: Graph, ctx, W: jnp.ndarray) -> jnp.ndarray:
        return self.push_batch_counted(g, ctx, W)[0]  # [B, n]


@register_step_impl("ell")
class EllBackend(StepBackend):
    """Bucketed-ELL layout, Pallas kernel on the push (repro.kernels)."""

    # the column-sharded (C > 1) push now has an ELL realisation —
    # Graph.ell_partitioned(C) blocks through _batch_2d_ell_loop in
    # core/distributed.py — so the layout serves every mesh shape.
    capabilities_decl = BackendCapabilities(vertex_sharded_mesh=True)

    def refused_on(self, platform: str) -> Optional[str]:
        from ..kernels.spmv_ell.kernel import TPU_REFUSAL
        return TPU_REFUSAL if platform == "tpu" else None

    def cost(self, stats: Optional[dict] = None, cfg=None) -> float:
        # Only ranked off-TPU (refused_on), where the kernel runs
        # interpret-mode (Python-slow): a large declared penalty keeps
        # "auto" away from it on one device.  On a C-way vertex-sharded
        # host mesh (stats carries the normalized (R, C)) the declared
        # x0.35 keeps the sharded-ELL schedule on the CPU suite's "auto"
        # path; no chip measurement backs it.
        mesh = (stats or {}).get("mesh")
        C = int(mesh[1]) if mesh is not None and len(tuple(mesh)) == 2 else 1
        factor = 0.35 if C > 1 else 50.0
        return super().cost(stats, cfg) * factor

    def prepare(self, g: Graph):
        return g.ell()

    def push(self, g: Graph, ctx, w: jnp.ndarray) -> jnp.ndarray:
        from ..kernels.spmv_ell import spmv_ell
        return spmv_ell(ctx, w)

    def push_batch(self, g: Graph, ctx, W: jnp.ndarray) -> jnp.ndarray:
        from ..kernels.spmv_ell import spmv_ell_batch
        return spmv_ell_batch(ctx, W)


class _FrontierPlan:
    """Host-side CSR-by-src view used to slice out the active frontier."""

    def __init__(self, g: Graph):
        from ..graph.structure import csr_from_graph

        self.offsets, self.dst_by_src = csr_from_graph(g, by="src")
        self.deg = np.asarray(g.out_deg).astype(np.int64)


@partial(jax.jit, static_argnames=("n",))
def _frontier_coo_push(w_pad: jnp.ndarray, src_e: jnp.ndarray,
                       dst_e: jnp.ndarray, n: int) -> jnp.ndarray:
    # sentinel slot n absorbs padding: w_pad[n] == 0 and dst n is dropped.
    contrib = w_pad[src_e]
    return jax.ops.segment_sum(contrib, dst_e, num_segments=n + 1)[:n]


@register_step_impl("frontier")
class FrontierBackend(StepBackend):
    """Active-set compression: push only the out-edges of the frontier.

    Each round the nonzero support of ``w`` (exactly the active,
    non-dangling set — dangling sources have ``inv_deg == 0``) is located
    on the host, its out-edges gathered from a CSR-by-src plan, and the
    resulting compressed COO padded to the next power of two so the jitted
    push sees at most log2(m) distinct shapes across the whole solve.
    Host-driven by construction — not traceable inside ``while_loop``.

    ``schedule`` names the order the host emits the frontier's edges in:

      * ``"fifo"``     — vertex-index order, exactly the historical
                         behaviour (nonzero scan order);
      * ``"priority"`` — descending |w|, the D-Iteration diffusion order
                         (arXiv 1501.06350): the largest residuals lead
                         each sweep.  Registered as the separate
                         ``"frontier_priority"`` backend below.

    Because the push is one commutative ``segment_sum`` over the gathered
    COO, the schedule changes *emission order only* — both schedules
    compute the same sum (the §IV commutativity licence every backend
    relies on), agreeing to segment-sum rounding, i.e. within the push
    contract tolerance like any other backend pair; the priority order is
    the one a future partial (top-K) sweep would consume, and is what the
    declared cost model of ``"frontier_priority"`` prices.
    """

    jittable = False
    schedule = "fifo"
    # host-driven: everything requiring a traced device-resident loop is
    # off; push_batch exists (sequential rows), so batched stays True.
    capabilities_decl = BackendCapabilities(
        jittable=False, donation=False, batch_parallel_mesh=False)

    def cost(self, stats: Optional[dict] = None, cfg=None) -> float:
        # compressed frontiers visit ~0.4x the edges over a solve, but the
        # host round-trip per iteration dominates — net ~1.2x dense, so
        # "frontier" is an explicit choice, never the "auto" pick (and the
        # jittable gate excludes it from "auto" anyway).
        return super().cost(stats, cfg) * 0.4 * 3.0

    def prepare(self, g: Graph) -> _FrontierPlan:
        return _FrontierPlan(g)

    def push(self, g: Graph, ctx: _FrontierPlan, w: jnp.ndarray) -> jnp.ndarray:
        w_host = np.asarray(w)
        vs = np.nonzero(w_host)[0]
        if self.schedule == "priority":
            # D-Iteration order: largest |residual| first.  Stable sort so
            # equal priorities keep vertex-index order (deterministic).
            vs = vs[np.argsort(-np.abs(w_host[vs]), kind="stable")]
        counts = ctx.deg[vs]
        total = int(counts.sum())
        if total == 0:
            return jnp.zeros((g.n,), w.dtype)
        # edge positions = concat of CSR ranges, vectorised
        starts = ctx.offsets[vs]
        shift = np.concatenate([[0], np.cumsum(counts)[:-1]])
        pos = np.arange(total, dtype=np.int64) + np.repeat(starts - shift, counts)
        src_e = np.repeat(vs, counts)
        dst_e = ctx.dst_by_src[pos]
        cap = 1 << int(total - 1).bit_length()  # next power of two
        src_p = np.full(cap, g.n, np.int32)
        dst_p = np.full(cap, g.n, np.int32)
        src_p[:total] = src_e
        dst_p[:total] = dst_e
        w_pad = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        return _frontier_coo_push(w_pad, jnp.asarray(src_p), jnp.asarray(dst_p),
                                  g.n)

    def push_batch(self, g: Graph, ctx, W: jnp.ndarray) -> jnp.ndarray:
        # host-driven push cannot be vmapped; each row has its own frontier.
        return jnp.stack([self.push(g, ctx, W[i]) for i in range(W.shape[0])])


@register_step_impl("frontier_priority")
class FrontierPriorityBackend(FrontierBackend):
    """Frontier compression with the D-Iteration priority schedule.

    Same gather/pad/push machinery as ``"frontier"`` (inherited), but the
    host emits the frontier in descending-|residual| order — the diffusion
    order of arXiv 1501.06350 — and declares a cost discount on symmetric
    edge sets (``Graph.is_undirected``): when every edge has its reverse,
    draining the largest residuals first returns their mass to the same
    neighbourhood within the sweep, so the compressed frontier shrinks
    faster than the fifo scan order.  The discount is a *declaration* the
    planner reads (the undirected-schedule rule in ``choose_backend``);
    the push itself equals ``"frontier"``'s by segment-sum commutativity
    (to summation-order rounding, within the push contract tolerance),
    so every conformance/oracle contract holds unchanged.
    Host-driven like its base — an explicit opt-in, never the "auto"
    pick (the jittable gate already excludes it).
    """

    jittable = False
    schedule = "priority"
    undirected_cost_factor = 0.6
    capabilities_decl = BackendCapabilities(
        jittable=False, donation=False, batch_parallel_mesh=False)

    def cost(self, stats: Optional[dict] = None, cfg=None) -> float:
        # fifo frontier constants (0.4 edge visits x 3.0 host round-trip)
        # times the declared undirected discount when the stats say the
        # edge set is symmetric; on directed graphs the priority queue
        # maintenance buys nothing over fifo, so the cost is identical.
        base = super().cost(stats, cfg)
        if (stats or {}).get("undirected"):
            base *= self.undirected_cost_factor
        return base


# ---------------------------------------------------------------------------
# The shared ITA round, generic over the push backend
# ---------------------------------------------------------------------------
def _ita_round(backend: StepBackend, g: Graph, ctx, h, pi_bar, c, xi,
               inv_deg, non_dangling, signed: bool):
    """The one ITA round body every solver shares.

    ``signed`` selects the |h| activity threshold (incremental updates push
    negative corrections); everything else — accumulate, push, Formula-15
    ops and the Management-thread CNT — is identical by construction, so a
    fix here reaches the plain, signed and batched solvers alike.  Returns
    ``(h', pi_bar', n_active, ops, core)``, ``core`` as
    :meth:`SolverBackend.push_counted` gives it.
    """
    with jax.named_scope("ita_round"):
        mag = jnp.abs(h) if signed else h
        active = jnp.logical_and(mag > xi, non_dangling)
        h_act = jnp.where(active, h, 0)
        pi_bar = pi_bar + h_act
        with jax.named_scope("push"):
            pushed, core = backend.push_counted(g, ctx, h_act * inv_deg * c)
        h = jnp.where(active, 0, h) + pushed
        n_active = jnp.sum(active, dtype=jnp.int32)
        ops = jnp.sum(jnp.where(active, g.out_deg, 0).astype(jnp.float32),
                      dtype=jnp.float32)
        return h, pi_bar, n_active, ops, core


def count_core(total, core):
    """``total`` plus one if a round's push walked the core list."""
    return total if core is None else total + core.astype(jnp.int32)


def ita_step_impl(backend: StepBackend, g: Graph, ctx, h, pi_bar, c, xi,
                  inv_deg, non_dangling):
    """One synchronous ITA round over any backend.

    Same contract as :func:`repro.core.ita.ita_step`:
    returns ``(h', pi_bar', n_active, ops)``.
    """
    return _ita_round(backend, g, ctx, h, pi_bar, c, xi, inv_deg,
                      non_dangling, signed=False)[:4]


def signed_ita_step_impl(backend: StepBackend, g: Graph, ctx, h, pi_bar, c,
                         xi, inv_deg, non_dangling):
    """Signed variant (|h| threshold) used by the incremental solver."""
    return _ita_round(backend, g, ctx, h, pi_bar, c, xi, inv_deg,
                      non_dangling, signed=True)[:4]


# NOTE: the backend INSTANCE is the static jit key (not its registry name):
# re-registering a different backend under the same name must invalidate
# cached traces, and instances are identity-hashed.
@partial(jax.jit, static_argnames=("max_iter", "backend", "signed"))
def _ita_loop_jit(g: Graph, ctx, h0, pi_bar0, c, xi, max_iter: int,
                  backend: StepBackend, signed: bool):
    inv_deg = g.inv_out_deg(h0.dtype)
    non_dangling = jnp.logical_not(g.dangling_mask)

    def cond(state):
        _, _, n_active, _, it, _ = state
        return jnp.logical_and(n_active > 0, it < max_iter)

    def body(state):
        h, pi_bar, _, ops_total, it, core_total = state
        h, pi_bar, n_active, ops, core = _ita_round(
            backend, g, ctx, h, pi_bar, c, xi, inv_deg, non_dangling, signed)
        return (h, pi_bar, n_active, ops_total + ops, it + 1,
                count_core(core_total, core))

    init = (h0, pi_bar0, jnp.asarray(1, jnp.int32),
            jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32))
    return jax.lax.while_loop(cond, body, init)


def run_ita_loop(g: Graph, h0, pi_bar0, *, c: float, xi: float,
                 max_iter: int, impl: str = "dense", signed: bool = False,
                 ctx=None):
    """Run ITA rounds to quiescence over the named backend.

    Jittable backends get the device-resident ``while_loop``; host-driven
    backends (frontier) run the same step in a python loop.  Returns
    ``(h, pi_bar, n_active, ops_total, iterations, core_rounds)``,
    ``core_rounds`` None where the backend's ctx holds no core list.
    """
    backend = get_step_impl(impl)
    if ctx is None:
        ctx = backend.prepare(g)
    if backend.capabilities().jittable:
        *out, core_rounds = _ita_loop_jit(g, ctx, h0, pi_bar0, float(c),
                                          float(xi), int(max_iter), backend,
                                          signed)
    else:
        inv_deg = g.inv_out_deg(h0.dtype)
        non_dangling = jnp.logical_not(g.dangling_mask)
        h, pi_bar = h0, pi_bar0
        ops_total, it, core_rounds = 0.0, 0, 0
        n_active = jnp.asarray(1, jnp.int32)
        while it < max_iter:
            h, pi_bar, n_active, ops, core = _ita_round(
                backend, g, ctx, h, pi_bar, c, xi, inv_deg, non_dangling,
                signed)
            ops_total += float(ops)
            core_rounds = count_core(core_rounds, core)
            it += 1
            if int(n_active) == 0:
                break
        out = [h, pi_bar, n_active, jnp.asarray(ops_total, jnp.float32),
               jnp.asarray(it, jnp.int32)]
    if backend.core_edges(ctx) is None:
        core_rounds = None
    return (*out, core_rounds)
