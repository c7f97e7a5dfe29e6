"""Measured planner costs — lower backend steps to HLO, price them on a roofline.

The planner's declared ``SolverBackend.cost`` constants rank backends from
hand-tuned factors.  This module replaces guessing with measurement where a
measurement exists: each backend's push step is lowered to optimized HLO for a
concrete (graph stats, batch, mesh, dtype) point, FLOPs and bytes are read
from ``compiled.cost_analysis()`` (the text parser in ``hlo_costs`` inflates
CPU scatter loops, but it is the only source of collective bytes, which
cost_analysis does not report), and the sample is priced in seconds against
the per-device-kind spec in ``hw.py``.

Samples live in a versioned :class:`CostTable` keyed by device kind (the
``platform`` field holds ``jax.devices()[0].device_kind``, "cpu" on the
host), persistable as JSON (``CostTable.save`` / ``CostTable.load``;
``REPRO_ROOFLINE_TABLE`` names a table to auto-load).  Consumers:

  * ``choose_backend`` (core/backends.py) re-ranks candidates by measured
    seconds when — and only when — every candidate has a sample for the
    deciding platform; any gap falls back to the declared constants, so an
    unmeasured backend is never penalized by someone else's measurement.
  * ``plan_query`` (core/query.py) calls :func:`plan_cost` per plan; the
    returned :class:`PlanCost` keeps ``cost`` in declared edge-traversal
    units (the serving tier's pricing unit) and carries the measured
    bytes/FLOPs/seconds + provenance that ``ExecutionPlan.explain()`` quotes.
  * ``tools/autotune_ell.py`` sweeps ELL ``block_rows`` / bucket widths
    against the same model.

See docs/ROOFLINE.md for the precedence rules and the on-disk format.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .hlo_costs import parse_hlo_costs
from .hw import device_kind, spec_for_device_kind

__all__ = [
    "TABLE_VERSION",
    "TABLE_ENV",
    "StepCostSample",
    "CostTable",
    "PlanCost",
    "measure_step",
    "sharded_round_step",
    "measure_sharded_step",
    "roofline_seconds",
    "get_cost_table",
    "set_cost_table",
    "plan_cost",
    "rank_measured",
]

TABLE_VERSION = 1
TABLE_ENV = "REPRO_ROOFLINE_TABLE"


def _est_rounds(cfg) -> float:
    """Geometric-decay round estimate (same model as ``SolverBackend.cost``)."""
    c = getattr(cfg, "c", 0.85)
    tol = getattr(cfg, "xi", None) or getattr(cfg, "tol", None) or 1e-10
    c = min(max(float(c), 1e-6), 1.0 - 1e-9)
    tol = min(max(float(tol), 1e-300), 1.0 - 1e-9)
    return max(1.0, math.log(tol) / math.log(c))


def roofline_seconds(
    flops: float,
    bytes_accessed: float,
    collective_bytes: float,
    platform: str,
) -> float:
    """Roofline time for one step: max(compute, memory) + interconnect."""
    spec = spec_for_device_kind(platform)
    compute_s = float(flops) / spec.peak_bf16_flops
    memory_s = float(bytes_accessed) / spec.hbm_bandwidth
    collective_s = float(collective_bytes) / spec.ici_link_bandwidth
    return max(compute_s, memory_s) + collective_s


@dataclasses.dataclass(frozen=True)
class StepCostSample:
    """One measured (backend, platform, shape) point: per-round HLO costs."""

    backend: str
    platform: str
    op: str  # "push" | "push_batch" | "sharded-round"
    n: int
    m: int
    batch: int
    dtype: str
    flops: float
    bytes_accessed: float
    collective_bytes: float
    seconds: float  # roofline-priced seconds per round
    mesh: Optional[tuple] = None  # normalized (R, C) for sharded samples

    def describe(self) -> str:
        mesh = f" mesh={tuple(self.mesh)}" if self.mesh else ""
        return (
            f"{self.backend}/{self.op} n={self.n} m={self.m} B={self.batch} "
            f"{self.dtype}@{self.platform}{mesh}"
        )

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["mesh"] = list(self.mesh) if self.mesh else None
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "StepCostSample":
        mesh = d.get("mesh")
        return cls(
            backend=str(d["backend"]),
            platform=str(d["platform"]),
            op=str(d["op"]),
            n=int(d["n"]),
            m=int(d["m"]),
            batch=int(d["batch"]),
            dtype=str(d["dtype"]),
            flops=float(d["flops"]),
            bytes_accessed=float(d["bytes_accessed"]),
            collective_bytes=float(d["collective_bytes"]),
            seconds=float(d["seconds"]),
            mesh=tuple(mesh) if mesh else None,
        )


def _cost_analysis(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns a per-partition list
        ca = ca[0] if ca else {}
    return dict(ca or {})


def _lower_costs(fn, args, platform: str) -> tuple:
    """(flops, bytes, collective_bytes) of ``jit(fn)`` lowered at ``args``."""
    compiled = jax.jit(fn).lower(*args).compile()
    ca = _cost_analysis(compiled)
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = parse_hlo_costs(compiled.as_text()).collective_bytes
    return flops, byts, coll


def measure_step(
    backend_name: str,
    g,
    *,
    batch: int = 1,
    dtype="float64",
    platform: Optional[str] = None,
) -> StepCostSample:
    """Lower one push round of ``backend_name`` over ``g`` and price it.

    ``batch=1`` measures ``push`` ([n] -> [n]); ``batch>1`` measures
    ``push_batch`` on a [batch, n] operand.  The host-driven "frontier"
    backend has no traceable push — its jitted inner op
    (``_frontier_coo_push``) is lowered at the worst-case full-frontier
    shape instead, scaled by ``batch`` (its batch is sequential rows).
    The sample's platform is always the lowering platform
    (``jax.devices()[0].device_kind``); ``platform`` only overrides the label/
    pricing spec for what-if tables and must be used knowingly.
    """
    from ..core.backends import get_step_impl

    backend = get_step_impl(backend_name)
    platform = platform or device_kind()
    dt = np.dtype(dtype).name
    batch = max(1, int(batch))
    if not backend.capabilities().jittable:
        from ..core.backends import _frontier_coo_push

        cap = 1 << max(0, int(g.m - 1)).bit_length()
        args = (
            jax.ShapeDtypeStruct((g.n + 1,), dt),
            jax.ShapeDtypeStruct((cap,), jnp.int32),
            jax.ShapeDtypeStruct((cap,), jnp.int32),
        )
        flops, byts, coll = _lower_costs(
            lambda w, s, d: _frontier_coo_push(w, s, d, g.n), args, platform
        )
        # push_batch is B sequential host-driven pushes
        flops, byts, coll = flops * batch, byts * batch, coll * batch
        op = "push_batch" if batch > 1 else "push"
    else:
        ctx = backend.prepare(g)
        if batch > 1:
            args = (jax.ShapeDtypeStruct((batch, g.n), dt),)
            flops, byts, coll = _lower_costs(
                lambda W: backend.push_batch(g, ctx, W), args, platform
            )
            op = "push_batch"
        else:
            args = (jax.ShapeDtypeStruct((g.n,), dt),)
            flops, byts, coll = _lower_costs(lambda w: backend.push(g, ctx, w), args, platform)
            op = "push"
    return StepCostSample(
        backend=backend_name,
        platform=platform,
        op=op,
        n=int(g.n),
        m=int(g.m),
        batch=batch,
        dtype=dt,
        flops=flops,
        bytes_accessed=byts,
        collective_bytes=coll,
        seconds=roofline_seconds(flops, byts, coll, platform),
    )


def sharded_round_step(
    backend_name: str,
    g,
    mesh,
    *,
    batch: int = 8,
    dtype="float64",
    c: float = 0.85,
    xi: float = 1e-10,
    ell_widths: tuple = (8, 32, 128),
    row_align: int = 8,
) -> tuple:
    """(step_fn, abstract_args, (R, C, B_pad)) for one sharded ITA round.

    The lowerable form of the mesh schedules in ``core/distributed.py``,
    shared by :func:`measure_sharded_step` (which prices the lowering) and
    the repro-lint trace layer (which checks the *collective schedule* of
    the same lowering against docs/SHARDING.md, rule RL104).  Needs R*C
    live devices (``resolve_mesh`` raises otherwise).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..core.backends import get_step_impl
    from ..core.batch import _batch_ita_step
    from ..core.distributed import (
        _batch_2d_operands_cached,
        _ell_cols_operands_cached,
        make_ita_batch_ell_step,
        make_ita_batch_step,
        resolve_mesh,
    )

    mesh = resolve_mesh(mesh)
    R = mesh.shape["data"]
    C = mesh.shape["model"] if "model" in mesh.axis_names else 1
    dt = np.dtype(dtype).name
    B_pad = max(R, ((int(batch) + R - 1) // R) * R)
    if C == 1:
        backend = get_step_impl(backend_name)
        if backend_name == "ell":
            bctx = g.ell(widths=tuple(ell_widths), row_align=int(row_align))
        else:
            bctx = backend.prepare(g)
        inv_deg = g.inv_out_deg(dt)
        nd = jnp.logical_not(g.dangling_mask)

        def local(H, PiBar):
            H2, PiBar2, n_loc, *_ = _batch_ita_step(
                backend, g, bctx, H, PiBar, float(c), float(xi), inv_deg, nd
            )
            return H2, PiBar2, jax.lax.psum(n_loc, "data")

        step = shard_map(
            local,
            mesh=mesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None), P()),
            check_vma=False,
        )
        state = jax.ShapeDtypeStruct((B_pad, g.n), dt)
        args = (state, state)
    elif backend_name == "ell":
        ellc, (leaves, ideg, nd) = _ell_cols_operands_cached(
            g, mesh, C, dt, "model", tuple(ell_widths), int(row_align)
        )
        n_pad = ellc.n_pad
        step = make_ita_batch_ell_step(mesh, ellc, float(c), float(xi))
        state = jax.ShapeDtypeStruct((B_pad, n_pad), dt)
        args = (state, state, ideg, nd, *leaves)
    else:
        part, (src_d, dst_d, ideg, nd) = _batch_2d_operands_cached(g, mesh, C, dt, "model")
        n_pad = part.n_pad
        step = make_ita_batch_step(mesh, {"nr": part.nr}, float(c), float(xi))
        state = jax.ShapeDtypeStruct((B_pad, n_pad), dt)
        args = (state, state, src_d, dst_d, ideg, nd)
    return step, args, (R, C, B_pad)


def measure_sharded_step(
    backend_name: str,
    g,
    mesh,
    *,
    batch: int = 8,
    dtype="float64",
    c: float = 0.85,
    xi: float = 1e-10,
    ell_widths: tuple = (8, 32, 128),
    row_align: int = 8,
) -> StepCostSample:
    """Lower one sharded batched ITA round on an (R, C) mesh and price it.

    Needs R*C live devices (``resolve_mesh`` raises otherwise).  For C > 1
    the parsed collective bytes are the per-device ``psum_scatter`` traffic
    the analytic table in docs/SHARDING.md predicts — the contract tests in
    tests/test_roofline.py hold the two within a stated tolerance.  For
    C == 1 the lowered round is the real batch-parallel schedule (each
    device runs the backend's own ``push_batch``; docs table: collective
    "none" beyond the scalar n_active psum).
    """
    platform = device_kind()
    dt = np.dtype(dtype).name
    step, args, (R, C, B_pad) = sharded_round_step(
        backend_name,
        g,
        mesh,
        batch=batch,
        dtype=dt,
        c=c,
        xi=xi,
        ell_widths=ell_widths,
        row_align=row_align,
    )
    flops, byts, coll = _lower_costs(step, args, platform)
    return StepCostSample(
        backend=backend_name,
        platform=platform,
        op="sharded-round",
        n=int(g.n),
        m=int(g.m),
        batch=B_pad,
        dtype=dt,
        flops=flops,
        bytes_accessed=byts,
        collective_bytes=coll,
        seconds=roofline_seconds(flops, byts, coll, platform),
        mesh=(R, C),
    )


class CostTable:
    """Versioned store of :class:`StepCostSample` points, per platform.

    Lookup picks the nearest sample in log-shape space for the same
    (backend, platform, op-family, dtype) and scales it linearly in the
    edge count and batch size — monotone by construction once a sample is
    chosen, and exact at the measured point.
    """

    def __init__(self, samples=(), version: int = TABLE_VERSION):
        self.version = int(version)
        self.samples: list[StepCostSample] = list(samples)

    def add(self, sample: StepCostSample) -> None:
        self.samples.append(sample)

    def __len__(self) -> int:
        return len(self.samples)

    def lookup(
        self,
        backend: str,
        platform: str,
        *,
        n: int,
        m: int,
        batch: int = 1,
        dtype: str = "float64",
        mesh: Optional[tuple] = None,
    ) -> Optional[StepCostSample]:
        """Nearest matching sample, or None when the family has no point.

        Batched requests prefer "push_batch"/"sharded-round" samples but
        fall back to a "push" point, and a batch of one prefers "push" but
        falls back to a "push_batch" point (either is scaled by B at
        estimate time); an (R, C) mesh with C > 1 prefers "sharded-round"
        samples.
        """
        dt = np.dtype(dtype).name
        C = int(mesh[1]) if mesh is not None and len(tuple(mesh)) == 2 else 1
        if C > 1:
            preferred = ("sharded-round", "push_batch", "push")
        elif batch > 1:
            preferred = ("push_batch", "push")
        else:
            preferred = ("push", "push_batch")
        cands = [
            s
            for s in self.samples
            if s.backend == backend and s.platform == platform and s.dtype == dt
        ]
        for op in preferred:
            pool = [s for s in cands if s.op == op]
            if pool:
                return min(
                    pool,
                    key=lambda s: (
                        abs(math.log(max(n, 1) / max(s.n, 1)))
                        + abs(math.log(max(m, 1) / max(s.m, 1)))
                        + abs(math.log(max(batch, 1) / max(s.batch, 1)))
                    ),
                )
        return None

    def estimate(
        self,
        backend: str,
        stats: Optional[dict],
        cfg=None,
        *,
        batch: int = 1,
        platform: Optional[str] = None,
    ) -> Optional[dict]:
        """Measured per-solve estimate for (backend, stats, cfg, batch).

        Returns None (→ declared fallback) when ``stats`` carries no shape
        or no sample family matches; otherwise a dict with the scaled
        per-round ``flops`` / ``bytes_accessed`` / ``collective_bytes``,
        ``rounds``, per-solve ``seconds``, and the deciding ``sample``.
        """
        if not stats or "m" not in stats or "n" not in stats:
            return None
        platform = platform or stats.get("platform") or device_kind()
        n, m = int(stats["n"]), int(stats["m"])
        dtype = str(stats.get("dtype", "float64"))
        mesh = stats.get("mesh")
        batch = max(1, int(batch))
        sample = self.lookup(backend, platform, n=n, m=m, batch=batch, dtype=dtype, mesh=mesh)
        if sample is None:
            return None
        scale = (m / max(sample.m, 1)) * (batch / max(sample.batch, 1))
        rounds = _est_rounds(cfg)
        flops = sample.flops * scale
        byts = sample.bytes_accessed * scale
        coll = sample.collective_bytes * scale
        per_round = roofline_seconds(flops, byts, coll, platform)
        return dict(
            flops=flops,
            bytes_accessed=byts,
            collective_bytes=coll,
            rounds=rounds,
            seconds=per_round * rounds,
            platform=platform,
            sample=sample.describe(),
            version=self.version,
        )

    # -- persistence -----------------------------------------------------
    def to_json(self) -> dict:
        return {
            "version": self.version,
            "samples": [s.to_dict() for s in self.samples],
        }

    @classmethod
    def from_json(cls, data: dict, *, strict: bool = True) -> "CostTable":
        version = int(data.get("version", -1))
        if version != TABLE_VERSION:
            if strict:
                raise ValueError(
                    f"cost table version {version} != supported {TABLE_VERSION}; "
                    f"re-measure (the sample schema changed)"
                )
            return cls()
        samples = [StepCostSample.from_dict(d) for d in data.get("samples", ())]
        return cls(samples, version=version)

    def save(self, path) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path, *, strict: bool = True) -> "CostTable":
        with open(path, encoding="utf-8") as f:
            return cls.from_json(json.load(f), strict=strict)


# -- module default table -------------------------------------------------
# None + not loaded => resolve from $REPRO_ROOFLINE_TABLE on first use; an
# explicit set_cost_table() pins it (tests; None re-enables env resolution).
_default_table: Optional[CostTable] = None
_default_loaded = False


def get_cost_table() -> CostTable:
    """The process-wide cost table (possibly empty — declared fallback)."""
    global _default_table, _default_loaded
    if _default_table is None and not _default_loaded:
        _default_loaded = True
        path = os.environ.get(TABLE_ENV)
        if path and os.path.exists(path):
            try:
                _default_table = CostTable.load(path, strict=False)
            except (OSError, ValueError, KeyError, json.JSONDecodeError):
                _default_table = CostTable()
    return _default_table if _default_table is not None else CostTable()


def set_cost_table(table: Optional[CostTable]) -> None:
    """Install (or with None: reset to env-resolution) the default table."""
    global _default_table, _default_loaded
    _default_table = table
    _default_loaded = table is not None


@dataclasses.dataclass(frozen=True)
class PlanCost:
    """What one planned execution is expected to cost, with provenance.

    ``cost`` stays in the declared edge-traversal units whatever the
    source — the serving tier's ``CostModel`` calibrates seconds-per-unit
    against exactly these units, so measurement must not change them.  The
    measured fields ride alongside for ``ExecutionPlan.explain()``.
    """

    cost: float  # declared edge-traversal units × batch
    source: str  # "measured" | "declared"
    reason: str  # provenance line explain() quotes
    seconds: Optional[float] = None  # est. seconds per solve (measured only)
    flops: Optional[float] = None  # per push round
    bytes_accessed: Optional[float] = None
    collective_bytes: Optional[float] = None
    rounds: Optional[float] = None
    sample: Optional[str] = None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def plan_cost(
    backend_name: str,
    stats: Optional[dict] = None,
    cfg=None,
    *,
    batch: int = 1,
    table: Optional[CostTable] = None,
) -> PlanCost:
    """Price one planned solve: measured table first, declared fallback.

    ``stats`` is the planner's ``dict(n=, m=, dtype=, mesh=, platform=)``
    (missing keys defaulted); ``batch`` multiplies the per-solve estimate
    the way ``plan_query`` charges [B, n] batches.
    """
    from ..core.backends import get_step_impl

    batch = max(1, int(batch))
    declared = get_step_impl(backend_name).cost(stats, cfg) * batch
    platform = (stats or {}).get("platform") or device_kind()
    table = table if table is not None else get_cost_table()
    est = table.estimate(backend_name, stats, cfg, batch=batch, platform=platform)
    if est is None:
        return PlanCost(
            cost=declared,
            source="declared",
            reason=(
                f"declared backend cost constants (no measured roofline "
                f"sample for backend={backend_name!r}, platform={platform!r})"
            ),
        )
    return PlanCost(
        cost=declared,
        source="measured",
        reason=(
            f"measured roofline sample [{est['sample']}] table "
            f"v{est['version']}: {est['bytes_accessed']:.4g} bytes, "
            f"{est['flops']:.4g} FLOPs per round x ~{est['rounds']:.0f} "
            f"rounds -> ~{est['seconds']:.3g} s/solve on {platform}"
        ),
        seconds=est["seconds"],
        flops=est["flops"],
        bytes_accessed=est["bytes_accessed"],
        collective_bytes=est["collective_bytes"],
        rounds=est["rounds"],
        sample=est["sample"],
    )


def rank_measured(
    names,
    stats: Optional[dict] = None,
    cfg=None,
    *,
    batch: int = 1,
    table: Optional[CostTable] = None,
) -> Optional[dict]:
    """Measured seconds per candidate, or None unless EVERY name is covered.

    ``choose_backend`` only trusts the measured ranking when the whole
    candidate pool has samples — mixing measured seconds with declared
    units would compare incommensurable numbers.
    """
    if not stats or "m" not in stats:
        return None
    table = table if table is not None else get_cost_table()
    if not len(table):
        return None
    platform = stats.get("platform") or device_kind()
    out = {}
    for name in names:
        est = table.estimate(name, stats, cfg, batch=batch, platform=platform)
        if est is None:
            return None
        out[name] = float(est["seconds"])
    return out
