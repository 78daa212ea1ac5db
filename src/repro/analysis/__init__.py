"""Static verification of offload plans (the MPU §V verifying backend).

``verify_plan`` walks an ``OffloadPlan`` plus its rewritten jaxpr and
proves — without executing anything — alias safety, index-map
coverage/bounds, VMEM legality, and segment well-formedness.  Findings
are typed; ``python -m repro.analysis.lint`` sweeps every configs model
and MUST_FUSE chain (``repro.analysis.chains``).  See docs/analysis.md
for the rule catalog.
"""
from repro.analysis.verifier import (
    SEVERITIES,
    VMEM_CAPACITY_BYTES,
    Finding,
    PlanVerificationError,
    decision_statuses,
    has_errors,
    max_severity,
    verify_paged_decode,
    verify_plan,
)

__all__ = [
    "SEVERITIES",
    "VMEM_CAPACITY_BYTES",
    "Finding",
    "PlanVerificationError",
    "decision_statuses",
    "has_errors",
    "max_severity",
    "verify_paged_decode",
    "verify_plan",
]
