"""Loop-aware HLO cost model.

``compiled.cost_analysis()`` counts every while-body ONCE — a loop of N
rounds or a scan of N steps is undercounted N-fold (DESIGN.md §5).  This
module re-derives costs from the optimized HLO text with loop-trip
multipliers:

  * computations are parsed into (name -> ops);
  * every ``while`` op publishes ``"known_trip_count":{"n":"N"}`` in its
    backend_config (XLA emits this for counted loops, which scan produces);
  * multipliers propagate through the call graph (entry=1; while body/cond
    x N; fusion/call/to_apply inherit the caller's multiplier);
  * FLOPs: dot ops (2 * prod(out_dims) * prod(contracting_dims)) and
    convolutions, wherever they appear (including inside fusion bodies);
  * bytes: per *executed* op — operands + outputs — counted only at
    fusion-call granularity (not inside fusion bodies), matching the
    "bytes accessed" semantics of cost_analysis;
  * collectives: operand bytes of all-gather / all-reduce / reduce-scatter
    / all-to-all / collective-permute, x multiplier.

All counts are PER DEVICE (the HLO module is the per-partition program
under SPMD), which is what the roofline terms want.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

__all__ = ["HloCosts", "CollectiveOp", "parse_hlo_costs", "parse_collectives"]

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1,
    "u4": 1,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "c64": 8,
    "c128": 16,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
    "token": 0,
}

_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\((.*)\)\s*->\s*(.*)\{\s*$")
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\))|(?:[\w]+\[[\d,]*\](?:\{[\d,]*\})?))\s*"
    r"([\w\-]+)\((.*)$",
)
_SHAPE_TOKEN = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND = re.compile(r"%([\w.\-]+)")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_BODY = re.compile(r"body=%?([\w.\-]+)")
_COND = re.compile(r"condition=%?([\w.\-]+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"to_apply=%?([\w.\-]+)")
_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LHS_BATCH = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_TOKEN.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str) -> list[int]:
    m = _SHAPE_TOKEN.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class _Op:
    name: str
    out_shape: str
    kind: str
    rest: str


@dataclasses.dataclass
class HloCosts:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    collective_by_kind: dict
    n_collective_ops: int
    loop_multipliers: dict
    flops_unscaled: float
    collective_msgs: list  # (kind, bytes_per_exec, multiplier)


def _split_computations(hlo: str) -> dict[str, list[_Op]]:
    comps: dict[str, list[_Op]] = {}
    lines = hlo.split("\n")
    cur: Optional[str] = None
    for ln in lines:
        h = _COMP_HEADER.match(ln)
        if h:
            cur = h.group(2)
            comps[cur] = []
            continue
        if cur is None:
            continue
        if ln.strip() == "}":
            cur = None
            continue
        m = _OP_LINE.match(ln)
        if m:
            comps[cur].append(_Op(m.group(1), m.group(2), m.group(3), m.group(4)))
    return comps


def _call_multipliers(hlo: str, comps: dict) -> dict:
    """{computation -> executed-times multiplier}, empty when no ENTRY.

    Fixpoint over call edges starting at the entry computation: while
    body/cond inherit caller x trip count, fusion/call/to_apply inherit the
    caller's multiplier unchanged.
    """
    mult: dict[str, float] = {}
    m_entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.MULTILINE)
    entry = m_entry.group(1) if m_entry else (list(comps)[-1] if comps else None)
    if entry is None:
        return mult
    mult[entry] = 1.0
    for _ in range(64):
        changed = False
        for cname, ops in comps.items():
            base = mult.get(cname)
            if base is None:
                continue
            for op in ops:
                if op.kind == "while":
                    n = 1.0
                    t = _TRIP.search(op.rest)
                    if t:
                        n = float(t.group(1))
                    for rx in (_BODY, _COND):
                        mm = rx.search(op.rest)
                        if mm:
                            callee = mm.group(1)
                            v = base * n
                            if mult.get(callee, 0) < v:
                                mult[callee] = v
                                changed = True
                else:
                    for rx in (_CALLS, _TO_APPLY, _BODY, _COND):
                        for mm in rx.finditer(op.rest):
                            callee = mm.group(1)
                            if mult.get(callee, 0) < base:
                                mult[callee] = base
                                changed = True
        if not changed:
            break
    return mult


def _fusion_callers(comps: dict) -> dict:
    """{fusion-body computation -> caller computation}."""
    out: dict[str, str] = {}
    for cname, ops in comps.items():
        for op in ops:
            if op.kind == "fusion":
                mm = _CALLS.search(op.rest)
                if mm:
                    out[mm.group(1)] = cname
    return out


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in the optimized HLO, with its loop multiplier.

    The shared vocabulary between the roofline cost model and the
    repro-lint trace layer (rule RL104): ``kind`` is the ``-start``-
    normalized HLO opcode, ``bytes_per_exec`` the operand bytes of one
    execution, ``multiplier`` how many times the surrounding loops run it.
    """

    kind: str  # "all-reduce" | "reduce-scatter" | ...
    bytes_per_exec: float
    multiplier: float
    computation: str  # computation the op appears in
    op_name: str  # the HLO value name

    @property
    def total_bytes(self) -> float:
        return self.bytes_per_exec * self.multiplier


def parse_collectives(hlo: str) -> list:
    """Every collective op in ``hlo`` as :class:`CollectiveOp` records.

    Same walk as :func:`parse_hlo_costs` (call-site granularity, loop
    multipliers applied, fusion bodies skipped), factored out so consumers
    that only need the collective *schedule* — which kinds move how many
    bytes — can ask for exactly that.
    """
    comps = _split_computations(hlo)
    shapes = {op.name: op.out_shape for ops in comps.values() for op in ops}
    mult = _call_multipliers(hlo, comps)
    fusion_bodies = set(_fusion_callers(comps))
    out: list[CollectiveOp] = []
    for cname, ops in comps.items():
        m = mult.get(cname)
        if m is None or cname in fusion_bodies:
            continue
        for op in ops:
            kind = op.kind.replace("-start", "")
            if kind not in _COLLECTIVES:
                continue
            operands = [mm.group(1) for mm in _OPERAND.finditer(op.rest.split(")", 1)[0])]
            ib = sum(_shape_bytes(shapes.get(o, "")) for o in operands)
            cb = ib if ib else _shape_bytes(op.out_shape)
            out.append(
                CollectiveOp(
                    kind=kind,
                    bytes_per_exec=float(cb),
                    multiplier=float(m),
                    computation=cname,
                    op_name=op.name,
                )
            )
    return out


def parse_hlo_costs(hlo: str) -> HloCosts:
    comps = _split_computations(hlo)
    shapes = {op.name: op.out_shape for ops in comps.values() for op in ops}

    # --- call-graph multipliers (shared with parse_collectives) -------
    mult = _call_multipliers(hlo, comps)
    if not mult:
        return HloCosts(0, 0, 0, {}, 0, {}, 0, [])

    # fusion bodies: count flops inside (they execute with the caller's
    # multiplier) but NOT bytes (fusion = one pass over caller operands).
    fusion_callers = _fusion_callers(comps)

    executed = {c: m for c, m in mult.items()}

    flops = 0.0
    flops_unscaled = 0.0
    byts = 0.0
    coll_bytes = 0.0
    coll_kind: dict[str, float] = {}
    coll_msgs: list = []
    n_coll = 0

    def dot_flops(op: _Op) -> float:
        out_dims = _shape_dims(op.out_shape)
        lhs_m = _OPERAND.search(op.rest)
        if not lhs_m:
            return 0.0
        lhs_shape = shapes.get(lhs_m.group(1), "")
        lhs_dims = _shape_dims(lhs_shape)
        con = _LHS_CONTRACT.search(op.rest)
        k = 1
        if con and lhs_dims:
            for d in con.group(1).split(","):
                if d and int(d) < len(lhs_dims):
                    k *= lhs_dims[int(d)]
        out_n = 1
        for d in out_dims:
            out_n *= d
        return 2.0 * out_n * k

    _SKIP = (
        "parameter",
        "constant",
        "get-tuple-element",
        "tuple",
        "bitcast",
        "while",
        "conditional",
        "call",
        "after-all",
        "copy-start",
        "copy-done",
        "iota",
        "partition-id",
        "replica-id",
    )
    # ops whose big operand is only *addressed*, not streamed in full
    _SLICY = ("dynamic-slice", "gather", "fusion")

    def op_bytes(op: _Op, comp_ops: dict) -> float:
        """Slice-aware byte estimate for one executed op."""
        ob = _shape_bytes(op.out_shape)
        operands = [mm.group(1) for mm in _OPERAND.finditer(op.rest.split(")", 1)[0])]
        if op.kind in ("dynamic-slice", "gather"):
            # reads ≈ output (the addressed slice) + indices
            return 2.0 * ob
        if op.kind == "dynamic-update-slice":
            upd = _shape_bytes(shapes.get(operands[1], "")) if len(operands) > 1 else ob
            return 3.0 * upd  # read update, read+write region
        if op.kind == "scatter":
            upd = _shape_bytes(shapes.get(operands[-1], "")) if operands else ob
            return 3.0 * upd
        if op.kind == "fusion":
            # charge each operand by how the body uses it: params consumed
            # only via dynamic-slice/gather are charged at slice size.
            body_name = None
            mm = _CALLS.search(op.rest)
            if mm:
                body_name = mm.group(1)
            body = comp_ops.get(body_name, [])
            sliced_params = set()
            param_order: list[str] = []
            for bop in body:
                if bop.kind == "parameter":
                    param_order.append(bop.name)
            for bop in body:
                if bop.kind in ("dynamic-slice", "gather"):
                    ops_in = [m2.group(1) for m2 in _OPERAND.finditer(bop.rest.split(")", 1)[0])]
                    if ops_in and ops_in[0] in param_order:
                        sliced_params.add(ops_in[0])
            total = ob
            for i, o in enumerate(operands):
                full = _shape_bytes(shapes.get(o, ""))
                if i < len(param_order) and param_order[i] in sliced_params:
                    # find the slice output size
                    sl = 0
                    for bop in body:
                        if bop.kind in ("dynamic-slice", "gather"):
                            ops_in = [
                                m2.group(1)
                                for m2 in _OPERAND.finditer(bop.rest.split(")", 1)[0])
                            ]
                            if ops_in and ops_in[0] == param_order[i]:
                                sl += _shape_bytes(bop.out_shape)
                    total += min(full, sl if sl else full)
                else:
                    total += full
            return total
        ib = sum(_shape_bytes(shapes.get(o, "")) for o in operands)
        return ob + ib

    for cname, ops in comps.items():
        m = executed.get(cname)
        is_fusion_body = cname in fusion_callers
        if m is None and is_fusion_body:
            m = executed.get(fusion_callers[cname])
        if m is None:
            continue
        for op in ops:
            if op.kind in ("dot", "convolution"):
                f = dot_flops(op)
                flops += f * m
                flops_unscaled += f
            if is_fusion_body:
                continue  # bytes & collectives only at call-site granularity
            if op.kind in _SKIP:
                continue
            byts += op_bytes(op, comps) * m
            kind = op.kind.replace("-start", "")
            if kind in _COLLECTIVES:
                operands = [mm.group(1) for mm in _OPERAND.finditer(op.rest.split(")", 1)[0])]
                ib = sum(_shape_bytes(shapes.get(o, "")) for o in operands)
                cb = ib if ib else _shape_bytes(op.out_shape)
                coll_bytes += cb * m
                coll_kind[kind] = coll_kind.get(kind, 0.0) + cb * m
                coll_msgs.append((kind, cb, m))
                n_coll += 1

    loop_mults = {k: v for k, v in mult.items() if v > 1}
    return HloCosts(
        flops=flops,
        bytes_accessed=byts,
        collective_bytes=coll_bytes,
        collective_by_kind=coll_kind,
        n_collective_ops=n_coll,
        loop_multipliers=loop_mults,
        flops_unscaled=flops_unscaled,
        collective_msgs=coll_msgs,
    )
