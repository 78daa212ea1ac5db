from repro.core.artifacts import ArtifactStore, set_disk_injector
from repro.core.isa import (
    Instr,
    Loc,
    OpKind,
    Program,
    annotate_locations,
    apply_policy,
    location_stats,
)
from repro.core.locator import JaxprAnnotation, annotate_fn, annotate_jaxpr
from repro.core.offload import (
    MatmulAnchor,
    OffloadPlan,
    OffloadStats,
    Segment,
    bwd_plan_stats,
    bwd_plans,
    clear_bwd_plans,
    mpu_offload,
    offload_explain,
    offload_report,
    plan_offload,
    rewrite_offload,
)
from repro.core.policy import (
    DEFAULT_POLICY,
    OFFLOAD_MODES,
    PLANNER_MODES,
    SIMULATOR_MODES,
    DecisionReport,
    OffloadPolicy,
    SegmentDecision,
    current_policy,
    offload_policy,
    resolve_policy,
    simulator_mode,
)
from repro.core.simulator import SimConfig, SimResult, end_to_end_time, simulate

__all__ = [
    "ArtifactStore", "set_disk_injector",
    "Instr", "Loc", "OpKind", "Program", "annotate_locations",
    "apply_policy", "location_stats", "JaxprAnnotation", "annotate_fn",
    "annotate_jaxpr", "MatmulAnchor", "OffloadPlan", "OffloadStats",
    "Segment",
    "bwd_plan_stats", "bwd_plans", "clear_bwd_plans",
    "mpu_offload", "offload_explain",
    "offload_report", "plan_offload", "rewrite_offload",
    "DEFAULT_POLICY", "OFFLOAD_MODES", "PLANNER_MODES", "SIMULATOR_MODES",
    "DecisionReport", "OffloadPolicy", "SegmentDecision",
    "current_policy", "offload_policy", "resolve_policy", "simulator_mode",
    "SimConfig", "SimResult", "end_to_end_time", "simulate",
]
