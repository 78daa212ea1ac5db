"""Offload planner: device milliseconds per decode step in the Pallas
calls the planner runs its near segments as (``fused_matmul``, its
``_dlhs``/``_drhs`` forms, ``fused_segment_grid``), over the window's
decode steps.  A program whose kernels carry no name, or a plan that
runs nothing near, gives nothing to read."""
from bench.scopes import NEAR_KERNELS, pallas_seconds

PROGRAM = "step_impl"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = pallas_seconds(t.ops, NEAR_KERNELS)
    steps = [s for s in run.steps if 0 <= s.start < run.seconds]
    if secs <= 0 or not steps or t.module_count(PROGRAM) != len(steps):
        return None
    return 1e3 * secs / len(steps)
