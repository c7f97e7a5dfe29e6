"""Roofline analysis: hardware specs, HLO cost parsing, measured planner costs."""

from .analysis import RooflineReport, analyze_compiled
from .hlo_costs import HloCosts, parse_hlo_costs
from .hw import HW, CPULowering, TPUv5e, device_kind, spec_for_device_kind
from .planner_costs import (
    CostTable,
    PlanCost,
    StepCostSample,
    get_cost_table,
    measure_sharded_step,
    measure_step,
    plan_cost,
    rank_measured,
    roofline_seconds,
    set_cost_table,
)

__all__ = [
    "HW",
    "CPULowering",
    "CostTable",
    "HloCosts",
    "PlanCost",
    "RooflineReport",
    "StepCostSample",
    "TPUv5e",
    "analyze_compiled",
    "device_kind",
    "get_cost_table",
    "measure_sharded_step",
    "measure_step",
    "parse_hlo_costs",
    "plan_cost",
    "rank_measured",
    "roofline_seconds",
    "set_cost_table",
    "spec_for_device_kind",
]
