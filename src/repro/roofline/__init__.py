"""Roofline analysis: hardware specs, HLO cost parsing, measured planner costs."""

from .hlo_costs import HloCosts, parse_hlo_costs
from .hw import CPULowering, TPUv5e, device_kind, spec_for_device_kind
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
    "CPULowering",
    "CostTable",
    "HloCosts",
    "PlanCost",
    "StepCostSample",
    "TPUv5e",
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
