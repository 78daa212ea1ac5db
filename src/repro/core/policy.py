"""First-class offload policy: one object for the §IV-B1 decision.

The paper's backend optimization treats near-vs-far as a *modeled-cost
choice made once at compile time*, not a fixed rule.  This module is the
single source of truth for that choice across the whole stack:

* ``OffloadPolicy`` — a frozen, hashable configuration object carrying
  the decision mode, the planner thresholds (``bulk_threshold``,
  ``min_segment``), the runtime knobs (``impl``, ``max_plans``,
  ``vmem_budget``) and the machine model whose bandwidths the cost
  backend prices traffic with.  It is part of every plan-cache key, so
  the same avals under a different policy can never hit a stale plan.
* the **mode registry** — the planner's decision backends (``greedy``,
  ``cost``, ``all_near``, ``all_far``) and the instruction simulator's
  location policies (``annotated``, ``hw_default``, ``all_near``,
  ``all_far``) drawn from ONE vocabulary; ``simulator_mode`` maps any
  registry name (or a policy object) onto the simulator's subset, so
  ``repro.core.isa.apply_policy`` and the jaxpr planner cannot drift.
* ``offload_policy(p)`` — a context manager for scoped overrides: any
  ``mpu_offload``-wrapped function called under it re-resolves its
  effective policy (and re-keys its plan cache) for the duration.
* ``SegmentDecision`` / ``DecisionReport`` — the per-candidate decision
  record the planner emits (tier, anchor form, operand roles, io bytes,
  modeled near/far time, fuse/decline rationale) and the readable table
  behind ``wrapped.explain(*args)``.  Batched anchors render their
  outer grid axes in the ``batch`` column — a ``[B,H,S,D]`` einsum
  shows as ``form=fwd, batch=(B, H)`` (i.e. ``batch=2x4`` in the
  table) — and a planned flash-attention segment shows as
  ``form=flash`` with the same batch axes.

Decision backends
-----------------

``greedy``    today's behavior and the default: fuse whenever a segment
              is admissible and carries at least ``min_segment`` ALU
              eqns (anchored segments need >= 1 fused eqn — a bare
              contraction adds only rhs re-streaming).
``cost``      the paper's §IV-B1 decision: price the candidate both
              ways — fused near bytes (``Segment.io_bytes``, which
              counts the anchored rhs once per row block) against the
              far pipeline's per-eqn round-trips — at the machine
              model's near/far bandwidths, and decline whenever the far
              path is modeled no slower.  This subsumes both the
              ``min_segment`` floor (a 1-eqn segment moves the same
              bytes either way) and the bare-anchor special case (the
              re-streamed rhs makes near strictly worse).
``all_near``  fuse every admissible candidate (the Fig. 15 bound).
``all_far``   never fuse: every candidate declines, the far pipeline
              runs everything (PonB-like execution).
"""
from __future__ import annotations

import dataclasses
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Iterator

from repro.core.machine import V5E

# ---------------------------------------------------------------------------
# Mode registry: one vocabulary for planner and simulator.
# ---------------------------------------------------------------------------

#: decision backends of the jaxpr planner (repro.core.offload)
PLANNER_MODES: tuple[str, ...] = ("greedy", "cost", "all_near", "all_far")

#: location policies of the instruction simulator (repro.core.isa)
SIMULATOR_MODES: tuple[str, ...] = ("annotated", "hw_default",
                                    "all_near", "all_far")

#: the full shared vocabulary
OFFLOAD_MODES: tuple[str, ...] = tuple(dict.fromkeys(
    PLANNER_MODES + SIMULATOR_MODES))

# planner backends project onto the simulator's subset: greedy and cost
# are both Algorithm-1-annotated execution (cost only *refines* which
# annotated segments fuse; instruction locations are unchanged), while
# all_near/all_far mean the same thing on both sides.  hw_default and
# annotated are simulator-native and pass through.
_TO_SIMULATOR: dict[str, str] = {
    "greedy": "annotated",
    "cost": "annotated",
    "annotated": "annotated",
    "hw_default": "hw_default",
    "all_near": "all_near",
    "all_far": "all_far",
}


def simulator_mode(mode: "str | OffloadPolicy") -> str:
    """Project any registry mode (or a policy object) onto the
    simulator's ``apply_policy`` vocabulary.  Raises ``ValueError`` for
    names outside the registry — the drift guard both sides share."""
    if isinstance(mode, OffloadPolicy):
        mode = mode.mode
    try:
        return _TO_SIMULATOR[mode]
    except KeyError:
        raise ValueError(
            f"unknown offload mode {mode!r}: expected one of "
            f"{sorted(OFFLOAD_MODES)}") from None


# ---------------------------------------------------------------------------
# The policy object.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OffloadPolicy:
    """Every knob of the offload subsystem in one frozen, hashable value.

    ``mode``           decision backend (see module docstring)
    ``bulk_threshold`` minimum tensor size for a value to seed a near
                       segment (ld.global bulk gate)
    ``min_segment``    greedy mode's ALU-eqn floor per fused segment
    ``max_plans``      LRU bound of a wrapper's plan cache
    ``impl``           kernel dispatch: "auto" | "pallas" | "interpret"
                       | "ref"
    ``vmem_budget``    accumulator VMEM clamp for anchored kernels in
                       bytes (None: the kernels' built-in 4 MiB budget);
                       planner, kernel and roofline all honor the same
                       value
    ``machine``        the machine model whose ``offload_near_gbps`` /
                       ``offload_far_gbps`` price the cost decision
    """

    mode: str = "greedy"
    bulk_threshold: int = 1024
    min_segment: int = 2
    max_plans: int = 128
    impl: str = "auto"
    vmem_budget: int | None = None
    machine: Any = V5E

    def __post_init__(self):
        if self.mode not in PLANNER_MODES:
            raise ValueError(
                f"OffloadPolicy.mode {self.mode!r}: expected one of "
                f"{sorted(PLANNER_MODES)} (simulator-only modes "
                f"{sorted(set(SIMULATOR_MODES) - set(PLANNER_MODES))} "
                f"select instruction locations, not planner backends)")
        if self.max_plans < 1:
            raise ValueError("max_plans must be >= 1")
        if self.min_segment < 1:
            raise ValueError("min_segment must be >= 1")
        if self.vmem_budget is not None and self.vmem_budget < 4096:
            raise ValueError("vmem_budget must be >= 4096 bytes")

    def replace(self, **overrides) -> "OffloadPolicy":
        return dataclasses.replace(self, **overrides)

    # -- the cost model ----------------------------------------------------
    def check_cost_target(self, device) -> None:
        """Refuse ``mode="cost"`` on an accelerator the machine model's
        constants do not describe (its ``device_kinds``): the decision
        would price another chip's bandwidths.  A CPU host plans for the
        modelled machine itself (tests, offline planning)."""
        if device.platform == "cpu":
            return
        kinds = getattr(self.machine, "device_kinds", ())
        if device.device_kind not in kinds:
            raise ValueError(
                f"OffloadPolicy(mode='cost') prices with "
                f"{type(self.machine).__name__} constants, which describe "
                f"{list(kinds) or 'no real device'}, not this "
                f"{device.platform} device {device.device_kind!r}")

    @property
    def near_gbps(self) -> float:
        return float(self.machine.offload_near_gbps)

    @property
    def far_gbps(self) -> float:
        return float(self.machine.offload_far_gbps)

    def modeled_us(self, near_bytes: int, far_bytes: int
                   ) -> tuple[float, float]:
        """(near_us, far_us): the candidate priced both ways — fused
        near traffic at the near-bank stream bandwidth vs per-eqn
        round-trips at the far-path bandwidth (memory-bound segments:
        time == bytes / bandwidth)."""
        return (near_bytes / (self.near_gbps * 1e3),
                far_bytes / (self.far_gbps * 1e3))

    def decide(self, *, tier: str, n_compute: int, near_bytes: int,
               far_bytes: int, tiling: str | None = None
               ) -> "SegmentDecision":
        """The §IV-B1 decision for one candidate segment.

        ``tier`` is "anchor" for matmul-anchored candidates, else
        "elementwise"; ``n_compute`` counts fused ALU eqns (layout prims
        excluded); ``near_bytes`` is the fused kernel's modeled HBM
        traffic (``Segment.io_bytes``), ``far_bytes`` the same eqns'
        per-eqn round-trips on the far pipeline.  ``tiling`` names a
        block of the kernel that breaks the TPU tiling rule: such a
        kernel cannot compile, so the candidate declines in every
        mode."""
        near_us, far_us = self.modeled_us(near_bytes, far_bytes)
        if tiling is not None:
            fuse, reason = False, f"TPU block tiling: {tiling}"
        elif self.mode == "all_far":
            fuse, reason = False, "policy all_far: far pipeline only"
        elif self.mode == "all_near":
            fuse, reason = True, "policy all_near: fuse every admissible"
        elif self.mode == "cost":
            fuse = near_us < far_us
            ratio = far_us / max(near_us, 1e-12)
            reason = (f"modeled near {ratio:.2f}x faster" if fuse else
                      f"far path no slower ({near_us:.2f}us near vs "
                      f"{far_us:.2f}us far): fusing only adds "
                      f"re-streaming")
        elif tier == "anchor":
            fuse = n_compute >= 1
            reason = ("anchored: epilogue/prologue rides the accumulator"
                      if fuse else
                      "bare contraction: no fused ALU work, kernel would "
                      "only add rhs re-streaming")
        else:
            fuse = n_compute >= self.min_segment
            reason = (f"{n_compute} ALU eqns >= min_segment" if fuse else
                      f"{n_compute} ALU eqns < min_segment="
                      f"{self.min_segment}")
        return SegmentDecision(
            tier=tier, form=None, eqns=n_compute, rows=0, roles=(),
            near_bytes=near_bytes, far_bytes=far_bytes, near_us=near_us,
            far_us=far_us, fused=fuse, reason=reason)


#: the process-wide default policy (today's greedy behavior)
DEFAULT_POLICY = OffloadPolicy()

_tls = threading.local()


def current_policy() -> OffloadPolicy:
    """The effective policy at this point: the innermost active
    ``offload_policy(...)`` override, else ``DEFAULT_POLICY``."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else DEFAULT_POLICY


def active_policy_override() -> OffloadPolicy | None:
    """The innermost ``offload_policy(...)`` override, or None when no
    scope is active (wrappers then fall back to their pinned policy)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


@contextmanager
def offload_policy(policy: OffloadPolicy) -> Iterator[OffloadPolicy]:
    """Scoped policy override.  Inside the block every
    ``mpu_offload``-wrapped call (and every bare planning entry point
    not given an explicit policy) resolves to ``policy``; plan caches
    key on the policy, so leaving the scope restores the previous plans
    without recompilation."""
    if not isinstance(policy, OffloadPolicy):
        raise TypeError(f"expected OffloadPolicy, got {type(policy)!r}")
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(policy)
    try:
        yield policy
    finally:
        stack.pop()


def fold_legacy_kwargs(policy: OffloadPolicy | None, *, where: str,
                       target: str = "policy", stacklevel: int = 3,
                       **fields) -> OffloadPolicy | None:
    """The one deprecation shim for every pre-policy surface: fold
    non-None legacy kwargs (named by their ``OffloadPolicy`` field)
    into ``policy`` with a DeprecationWarning, or pass ``policy``
    through untouched when none were given."""
    given = {k: v for k, v in fields.items() if v is not None}
    if not given:
        return policy
    import warnings

    warnings.warn(
        f"{where}({', '.join(sorted(given))}) is deprecated: pass "
        f"{target}=OffloadPolicy("
        f"{', '.join(f'{k}=...' for k in sorted(given))}) instead",
        DeprecationWarning, stacklevel=stacklevel)
    return (policy or OffloadPolicy()).replace(**given)


def resolve_policy(policy: OffloadPolicy | None = None,
                   **legacy_overrides) -> OffloadPolicy:
    """The policy a planning entry point should use: the explicit
    ``policy`` argument, else the active scoped override, else the
    default — with any non-None legacy kwargs (``bulk_threshold``,
    ``min_segment``, ``impl``, ``max_plans``) folded on top."""
    base = policy if policy is not None else current_policy()
    overrides = {k: v for k, v in legacy_overrides.items() if v is not None}
    return base.replace(**overrides) if overrides else base


# ---------------------------------------------------------------------------
# Decision records: what explain() renders.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SegmentDecision:
    """One candidate segment's §IV-B1 verdict."""

    tier: str                    # "elementwise" | "anchor"
    form: str | None             # fwd/dlhs/drhs/flash for anchored candidates
    eqns: int                    # fused ALU eqns (n_compute)
    rows: int                    # shared row extent of the block views
    roles: tuple[str, ...]       # operand roles (bulk/param/rep/tile/...)
    near_bytes: int              # fused kernel traffic (Segment.io_bytes)
    far_bytes: int               # per-eqn round-trips on the far path
    near_us: float
    far_us: float
    fused: bool
    reason: str
    batch: tuple = ()            # batch grid axes of a batched anchor
    # the candidate's first eqn in the planned jaxpr (-1: not known)
    eqn: int = -1
    # filled by OffloadPlan.report() from the eqn's name stack, ending in
    # ``near/<kernel>`` when fused, with the call sites of the bodies
    # holding it in front: the name the candidate's ops carry in the
    # compiled program (a device trace's op names read the same string
    # under the program's and loops' own components)
    scope: str = ""
    # decision-vs-plan cross-check, filled by OffloadPlan.report():
    # "ok" when the emitted segment matches this row, "MISMATCH(...)"
    # when it disagrees (rows/form drift), "MISSING-SEGMENT" when a
    # fused verdict has no segment at all, None/"-" for declines.
    verified: str | None = None

    def _with(self, **kw) -> "SegmentDecision":
        return dataclasses.replace(self, **kw)


@dataclass
class DecisionReport:
    """The plan-inspection view ``wrapped.explain(*args)`` returns: one
    row per candidate segment (fused AND declined), nested reports for
    scan/jit bodies, and the plan's traffic accounting."""

    policy: OffloadPolicy
    decisions: list[SegmentDecision]
    naive_bytes: int
    fused_bytes: int
    inner: list["DecisionReport"] = field(default_factory=list)

    @property
    def n_fused(self) -> int:
        return sum(d.fused for d in self.decisions) + \
            sum(r.n_fused for r in self.inner)

    @property
    def n_declined(self) -> int:
        return sum(not d.fused for d in self.decisions) + \
            sum(r.n_declined for r in self.inner)

    @property
    def traffic_reduction(self) -> float:
        return self.naive_bytes / max(self.fused_bytes, 1)

    def all_decisions(self) -> list[SegmentDecision]:
        """Flattened decision rows, this program then nested bodies."""
        out = list(self.decisions)
        for r in self.inner:
            out.extend(r.all_decisions())
        return out

    def __str__(self) -> str:
        hdr = (f"OffloadPolicy(mode={self.policy.mode}, "
               f"bulk_threshold={self.policy.bulk_threshold}, "
               f"min_segment={self.policy.min_segment}, "
               f"machine={type(self.policy.machine).__name__}) — "
               f"{self.n_fused} fused / {self.n_declined} declined, "
               f"traffic {self.traffic_reduction:.2f}x "
               f"({self.naive_bytes / 1e6:.2f} -> "
               f"{self.fused_bytes / 1e6:.2f} MB)")
        cols = ("idx", "tier", "form", "batch", "eqns", "rows", "near_mb",
                "far_mb", "near_us", "far_us", "decision", "verified")
        rows = [cols]
        for i, d in enumerate(self.all_decisions()):
            rows.append((str(i), d.tier, d.form or "-",
                         "x".join(map(str, d.batch)) if d.batch else "-",
                         str(d.eqns),
                         str(d.rows), f"{d.near_bytes / 1e6:.2f}",
                         f"{d.far_bytes / 1e6:.2f}", f"{d.near_us:.2f}",
                         f"{d.far_us:.2f}",
                         "FUSE" if d.fused else "decline",
                         d.verified or "-"))
        widths = [max(len(r[c]) for r in rows) for c in range(len(cols))]
        lines = [hdr, "  ".join(c.ljust(w) for c, w in zip(rows[0], widths))]
        for r, d in zip(rows[1:], self.all_decisions()):
            line = "  ".join(c.ljust(w) for c, w in zip(r, widths))
            lines.append(f"{line}  {d.reason}")
            pad = " " * (sum(widths) + 2 * len(widths))
            if d.roles:
                lines.append(f"{pad}operands: {', '.join(d.roles)}")
            if d.scope:
                lines.append(f"{pad}scope: {d.scope}")
        return "\n".join(lines)
