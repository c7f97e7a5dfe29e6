"""Planner golden-decision tests + cost-model property checks.

The backend decision table is committed as a golden file
(tests/golden/planner_golden.json): every row is a (graph stats, mesh,
platform, require, candidate pool) point with the backend
``choose_backend`` must pick and a substring its reason must contain —
including the undirected-schedule rows, where the reason must name the
rule (SOLVERS.md §frontier_priority).  Platform enters through the
``stats["platform"]`` override, so the TPU rows assert the production
decision from the CPU CI container.  Regenerate after an intentional
cost-model change with::

    REPRO_UPDATE_GOLDEN=1 python -m pytest tests/test_planner_golden.py

The suite also proves the measured-cost precedence contract (a full
roofline table re-ranks, any coverage gap falls back to declared — see
docs/ROOFLINE.md) with synthetic tables, and property-checks that every
backend's planned cost is monotone nondecreasing in n, m, and B
(tests/_propcheck.py: hypothesis when installed, seeded fallback
otherwise).
"""

from __future__ import annotations

import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest
from _propcheck import given, settings
from _propcheck import strategies as st

from repro.core.backends import STEP_IMPLS, choose_backend, get_step_impl
from repro.core.engine import EnginePlan, PageRankEngine
from repro.core.query import PPRQuery, RankQuery
from repro.graph import web_graph
from repro.roofline.hw import spec_for_device_kind
from repro.roofline.planner_costs import (
    CostTable,
    StepCostSample,
    plan_cost,
    rank_measured,
    set_cost_table,
)

GOLDEN_PATH = Path(__file__).parent / "golden" / "planner_golden.json"
UPDATE = os.environ.get("REPRO_UPDATE_GOLDEN") == "1"

# The committed decision table: (id, stats, require, opts).  ``opts`` may
# carry ``jittable_only`` (default True — the engine's serving pool) and
# ``reason_contains`` (default: the declared-cost tiebreak line).  Adding a
# case here and regenerating the golden extends coverage; editing a
# committed expectation requires the regeneration flag, which makes
# cost-model drift an explicit, reviewed act.
_DECLARED_REASON = "lowest est. cost among eligible backends"
DECISION_CASES = [
    ("cpu-small", dict(n=1_000, m=8_000, platform="cpu"), (), {}),
    ("cpu-large", dict(n=1_000_000, m=30_000_000, platform="cpu"), (), {}),
    ("tpu-small", dict(n=1_000, m=8_000, platform="tpu"), (), {}),
    ("tpu-large", dict(n=1_000_000, m=30_000_000, platform="tpu"), (), {}),
    (
        "cpu-mesh-R1",
        dict(n=100_000, m=2_000_000, platform="cpu", mesh=(4, 1)),
        ("batch_parallel_mesh",),
        {},
    ),
    (
        "cpu-mesh-C2",
        dict(n=100_000, m=2_000_000, platform="cpu", mesh=(4, 2)),
        ("batch_parallel_mesh", "vertex_sharded_mesh"),
        {},
    ),
    (
        "tpu-mesh-C2",
        dict(n=100_000, m=2_000_000, platform="tpu", mesh=(4, 2)),
        ("batch_parallel_mesh", "vertex_sharded_mesh"),
        {},
    ),
    # Undirected-schedule rule (SOLVERS.md §frontier_priority): on a
    # symmetric edge set a host-eligible pool prefers priority diffusion
    # via its declared undirected_cost_factor; the same stats without the
    # flag — or restricted to the jittable pool — still pick dense.
    (
        "cpu-hostpool-undirected",
        dict(n=50_000, m=400_000, platform="cpu", undirected=True),
        (),
        dict(jittable_only=False, reason_contains="undirected-schedule rule"),
    ),
    (
        "cpu-hostpool-directed",
        dict(n=50_000, m=400_000, platform="cpu"),
        (),
        dict(jittable_only=False),
    ),
    (
        "cpu-jitpool-undirected",
        dict(n=50_000, m=400_000, platform="cpu", undirected=True),
        (),
        {},
    ),
]


def _decide(stats, require, opts):
    name, reason = choose_backend(
        dict(stats), require=tuple(require), jittable_only=opts.get("jittable_only", True)
    )
    return name, reason


def _load_golden() -> dict:
    with open(GOLDEN_PATH, encoding="utf-8") as f:
        return json.load(f)


def test_golden_file_is_current():
    """Regeneration support: with REPRO_UPDATE_GOLDEN=1 rewrite the file."""
    set_cost_table(CostTable())  # decisions below are the declared ones
    try:
        decisions = []
        for case_id, stats, require, opts in DECISION_CASES:
            name, reason = _decide(stats, require, opts)
            decisions.append(
                dict(
                    id=case_id,
                    stats={k: (list(v) if isinstance(v, tuple) else v) for k, v in stats.items()},
                    require=list(require),
                    jittable_only=opts.get("jittable_only", True),
                    backend=name,
                    reason_contains=opts.get("reason_contains", _DECLARED_REASON),
                )
            )
    finally:
        set_cost_table(None)
    current = dict(version=1, decisions=decisions)
    if UPDATE:
        GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN_PATH.write_text(
            json.dumps(current, indent=2, sort_keys=True) + "\n", encoding="utf-8"
        )
    golden = _load_golden()
    assert golden == current, (
        "planner decisions drifted from tests/golden/planner_golden.json; "
        "if intentional, regenerate with REPRO_UPDATE_GOLDEN=1"
    )


@pytest.mark.parametrize(
    "case_id,stats,require,opts",
    DECISION_CASES,
    ids=[c[0] for c in DECISION_CASES],
)
def test_golden_decision(case_id, stats, require, opts):
    golden = {d["id"]: d for d in _load_golden()["decisions"]}[case_id]
    set_cost_table(CostTable())
    try:
        name, reason = _decide(stats, require, opts)
    finally:
        set_cost_table(None)
    assert name == golden["backend"], reason
    assert golden["reason_contains"] in reason


def test_explain_golden_head_lines():
    """Engine-level goldens: head line + declared cost source (CPU only —
    on an accelerator the prepared backend legitimately differs)."""
    if jax.default_backend() != "cpu":
        pytest.skip("explain goldens pinned for the CPU container")
    set_cost_table(CostTable())
    try:
        g = web_graph(400, 3200, dangling_frac=0.25, seed=17)
        eng = PageRankEngine(g, EnginePlan())
        rank = eng.plan(RankQuery())
        assert rank.explain().splitlines()[0] == (
            "plan[rank]: backend=dense path=while-loop method=ita "
            "mesh=none (single device)"
        )
        assert rank.cost_source == "declared"
        assert "cost source: declared" in rank.explain()
        P = np.zeros((3, g.n))
        P[0, 1] = P[1, 5] = P[2, 9] = 1.0
        ppr = eng.plan(PPRQuery(p_batch=P))
        assert ppr.explain().splitlines()[0] == (
            "plan[ppr]: backend=dense path=batched-while-loop "
            "method=ita_batch mesh=none (single device) micro_batch=3"
        )
        assert ppr.cost == pytest.approx(rank.cost * 3)
    finally:
        set_cost_table(None)


# ---------------------------------------------------------------------------
# Measured-cost precedence (synthetic tables — deterministic everywhere)
# ---------------------------------------------------------------------------
def _sample(backend, seconds, platform="cpu", **kw):
    # estimate() re-prices each lookup from bytes/FLOPs on the platform
    # roofline, so encode the intended per-round seconds as memory bytes
    # (per-round time = bytes / hbm_bandwidth when compute is negligible).
    spec = spec_for_device_kind(platform)
    base = dict(
        backend=backend,
        platform=platform,
        op="push",
        n=1_000,
        m=8_000,
        batch=1,
        dtype="float64",
        flops=0.0,
        bytes_accessed=seconds * spec.hbm_bandwidth,
        collective_bytes=0.0,
        seconds=seconds,
    )
    base.update(kw)
    return StepCostSample(**base)


def test_full_table_rerank_flips_decision():
    stats = dict(n=1_000, m=8_000, platform="cpu")
    table = CostTable()
    table.add(_sample("dense", 5e-4))
    table.add(_sample("ell", 1e-5))  # measured says ELL wins on CPU
    set_cost_table(table)
    try:
        name, reason = choose_backend(dict(stats))
        assert name == "ell"
        assert "measured" in reason
        pc = plan_cost("ell", stats)
        assert pc.source == "measured"
        assert "measured roofline sample" in pc.reason
        # cost UNITS stay declared even when the source is measured — the
        # serving tier's CostModel is calibrated against them.
        set_cost_table(CostTable())
        assert pc.cost == pytest.approx(plan_cost("ell", stats).cost)
    finally:
        set_cost_table(None)


def test_partial_table_falls_back_to_declared():
    stats = dict(n=1_000, m=8_000, platform="cpu")
    table = CostTable()
    table.add(_sample("ell", 1e-5))  # dense has no sample -> no re-rank
    set_cost_table(table)
    try:
        assert rank_measured(["dense", "ell"], stats) is None
        name, reason = choose_backend(dict(stats))
        assert name == "dense"
        assert "lowest est. cost among eligible backends" in reason
        pc = plan_cost("dense", stats)
        assert pc.source == "declared"
        assert "no measured roofline sample" in pc.reason
    finally:
        set_cost_table(None)


def test_version_mismatch_table_degrades_to_declared(tmp_path):
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps(dict(version=0, samples=[])), encoding="utf-8")
    with pytest.raises(ValueError, match="cost table version"):
        CostTable.load(stale)
    assert len(CostTable.load(stale, strict=False)) == 0


# ---------------------------------------------------------------------------
# Property: planned cost monotone nondecreasing in n, m, B
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=2, max_value=10_000_000),
    m=st.integers(min_value=1, max_value=100_000_000),
    b=st.integers(min_value=1, max_value=512),
    dn=st.integers(min_value=0, max_value=1_000_000),
    dm=st.integers(min_value=0, max_value=10_000_000),
    db=st.integers(min_value=0, max_value=64),
)
def test_declared_cost_monotone(n, m, b, dn, dm, db):
    set_cost_table(CostTable())
    try:
        for name in sorted(STEP_IMPLS):
            lo = plan_cost(name, dict(n=n, m=m, platform="cpu"), batch=b).cost
            hi = plan_cost(name, dict(n=n + dn, m=m + dm, platform="cpu"), batch=b + db).cost
            assert hi >= lo, name
    finally:
        set_cost_table(None)


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(min_value=1, max_value=100_000_000),
    b=st.integers(min_value=1, max_value=512),
    dm=st.integers(min_value=0, max_value=10_000_000),
    db=st.integers(min_value=0, max_value=64),
)
def test_measured_seconds_monotone(m, b, dm, db):
    for name in sorted(STEP_IMPLS):
        table = CostTable()
        table.add(_sample(name, 1e-4, op="push_batch", batch=8))
        stats = dict(n=1_000, platform="cpu")

        def sec(mm, bb):
            est = table.estimate(name, dict(stats, m=mm), batch=bb)
            assert est is not None
            return est["seconds"]

        assert sec(m + dm, b + db) >= sec(m, b), name
