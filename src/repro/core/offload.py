"""The instruction offload engine (§IV-B1) as a compile-time jaxpr
rewriter with a bounded plan cache.

The paper's backend decides offloading *once, at compile time* (§V): the
location annotator (Algorithm 1, repro.core.locator) marks each
instruction near/far, and the backend emits offload descriptors into the
compiled program.  This module mirrors that architecture for JAX:

  flatten once  trivially-inlinable call eqns (``jit``-wrapped
                elementwise helpers like ``jax.nn.silu``, and
                ``custom_jvp`` bodies, whose forward rule is what the
                post-grad trace wants anyway) are spliced into the
                caller so near chains are not cut at call boundaries.
                ``custom_vjp`` eqns are NOT inlined: their backward
                rules are numerically load-bearing, so they re-bind
                unchanged (preserving the user's rule under grad)
  trace once    ``jax.make_jaxpr(fn)`` on the call's avals
  plan once     ``plan_offload`` segments the jaxpr into maximal
                near-bank runs.  Segments are *cross-shape*: every
                operand carries its own 2-D block view ([rows, lanes])
                and an index-map role — ``bulk`` (tiled over rows),
                ``param`` (one broadcast block), ``rep``/``tile``
                (row-broadcast operands such as [B,1,D] against
                [B,S,D]) — and lane-axis layout prims
                (``broadcast_in_dim``/``reshape``/``slice``/
                ``concatenate``, see locator.LAYOUT_PRIMS) are absorbed
                instead of ending the segment.  Segments are also
                *matmul-anchored*: a qualifying ``dot_general``
                (locator.ANCHOR_PRIMS) OPENS a segment rather than
                ending it, absorbing its elementwise lhs prologue, a
                weight-side dequant-cast prologue, and its whole
                epilogue around an in-kernel contraction
                (``MatmulAnchor``).  THREE forms anchor — the forward
                x[M,K] @ w[K,N] and the grad-time dx = g @ wT
                (``dlhs``, weight read column-major) and dw = xT @ g
                (``drhs``, M-innermost into a [Kb,Nb] accumulator) —
                so backward passes fuse instead of falling far.  All
                three forms also admit leading, aligned BATCH dims
                ([B,H,S,D]-style contractions): the batch axes become
                outer grid axes of the kernels and the rhs re-streams
                per batch slice (``MatmulAnchor.batch``).  A SECOND
                anchor may ride a batched ``dlhs`` anchor: when the
                open run is exactly a scale/mask/row-softmax of the
                scores and the next eqn is the batched PV dot, the
                pair fuses as one flash-shaped segment
                (``MatmulAnchor.flash``) dispatched to the
                online-softmax flash kernel — the [S, T] score matrix
                never exists in HBM.
                Lane-axis reductions (locator.REDUCE_LANE_PRIMS) fuse
                as (rows, 1) row statistics so softmax/rmsnorm chains
                stay whole.
                Segment inputs that die at the segment are donated: the
                fused kernel is emitted with Pallas
                ``input_output_aliases`` so boundary buffers between
                consecutive segments are reused in place (§IV-B3's
                multiple-activated-row-buffers analogue).
  rewrite once  ``_build_runner`` bakes every decision into a list of
                step closures — each near segment becomes ONE fused
                Pallas launch (repro.kernels.ops.fused_segment_grid for
                elementwise segments; fused_matmul_segment /
                fused_matmul_dlhs_segment / fused_matmul_drhs_segment
                for anchored ones: one HBM read per operand, one write
                per output, intermediates and the matmul accumulator in
                VMEM), far eqns re-bind unchanged,
                ``scan``/``closed_call`` bodies are rewritten
                recursively *at rewrite time* (scan CARRIES that die at
                a body segment are donated into the body's kernel
                aliases), and non-trivial ``jit`` eqns are re-emitted
                as ``jax.jit`` calls so their fully-specified
                ``in_shardings``/``out_shardings`` and
                ``donated_invars`` survive the rewrite (partially
                specified sharding tuples are dropped — see ROADMAP)
  execute fast  the runner is staged through ``jax.jit`` — after the
                first call the near/far split lives inside one compiled
                XLA executable; no Python interpretation remains on the
                hot path
  grad ready    every fused-segment call carries a ``jax.custom_vjp``:
                ``grad(mpu_offload(f))`` differentiates THROUGH the
                rewritten program, and each segment's backward
                re-plans its cotangent jaxpr with this same rewriter
                (remat-style: residuals are the segment inputs, the
                recomputed forward re-anchors, and the grad-time
                contractions hit the dlhs/drhs kernels).  Backward
                plans cache under "bwd"-tagged keys — see
                ``bwd_plan_stats``/``bwd_plans`` — and never collide
                with the "fwd"-tagged plan cache.  The VJP forward
                path drops donation aliases (its residuals are the
                buffers donation would overwrite); the primal path
                keeps them.

Every fuse-or-decline verdict is an ``OffloadPolicy`` decision
(repro.core.policy): the planner finds candidate segments the same way
under every mode, then the policy's backend — ``greedy`` (default,
today's heuristics), ``cost`` (the paper's §IV-B1 modeled near-vs-far
time from ``Segment.io_bytes`` and the machine model's bandwidths),
``all_near``, or ``all_far`` — fuses or declines each candidate, and
both verdicts are recorded on the plan (``OffloadPlan.decisions``,
rendered by ``wrapped.explain(*args)`` / ``offload_explain``).

``mpu_offload(fn, policy=...)`` returns a drop-in replacement for ``fn``
that caches compiled runners keyed by (policy, aval signature) — the
same avals under a different policy (e.g. inside a
``with offload_policy(p):`` scope) compile a fresh plan rather than
hitting a stale one.  The cache is an LRU bounded by the policy's
``max_plans`` (serving with many shapes stays bounded); hits, misses,
evictions and traces are observable via ``wrapped.stats``.
``donate_argnums`` marks positional arguments whose buffers may be
reused by fused segments (same contract as ``jax.jit`` donation: pass
fresh buffers on subsequent calls).

``rewrite_offload`` exposes the rewritten ``ClosedJaxpr`` itself — the
compile-time artefact in which each near segment appears as a single
``pallas_call``-backed eqn carrying its ``input_output_aliases``.
``offload_report`` returns the plan with the paper's TSV-style traffic
accounting: naive per-eqn HBM bytes vs post-fusion bytes, plus the bytes
whose round-trip is eliminated by segment-boundary donation.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax.extend import source_info_util

from repro.core.isa import Loc
from repro.core.locator import (
    ELEMENTWISE_PRIMS,
    LAYOUT_PRIMS,
    JaxprAnnotation,
    annotate_jaxpr,
    eqn_tier,
)
from repro.core.policy import (
    DecisionReport,
    OffloadPolicy,
    SegmentDecision,
    active_policy_override,
    fold_legacy_kwargs,
    resolve_policy,
)
from repro.kernels import ops as kops
from repro.kernels.guard import kernel_guard


# ---------------------------------------------------------------------------
# 2-D block views: every segment value is a [rows, lanes] tile.
# ---------------------------------------------------------------------------

def _bulk_view(shape: Sequence[int]) -> tuple[int, int]:
    """[*, C] -> (prod(leading), C); rank-1 [N] is a column (N, 1)."""
    shape = tuple(shape)
    if len(shape) >= 2:
        r = 1
        for d in shape[:-1]:
            r *= d
        return r, shape[-1]
    if len(shape) == 1:
        return shape[0], 1
    return 1, 1


def _lane(shape: Sequence[int]) -> int:
    return shape[-1] if len(shape) else 1


def _is_param_shape(shape: Sequence[int]) -> bool:
    """Broadcastable to any row count: all leading dims are 1."""
    return all(d == 1 for d in tuple(shape)[:-1])


@dataclass(frozen=True)
class OperandSpec:
    """How one segment input is blocked by the fused kernel.

    role:
      * ``bulk``  — [rows, cols], tiled over the row grid
      * ``param`` — [1, cols], the same block broadcast to every step
      * ``rep``   — [op_rows, cols]; each row repeated rows/op_rows
                    times (suffix broadcast, e.g. [B,1,D] vs [B,S,D])
      * ``tile``  — [op_rows, cols]; rows cycle with period op_rows
                    (prefix broadcast, e.g. [1,S,D] vs [B,S,D])
      * ``bcast`` — [op_rows, cols] with an INTERIOR broadcast
                    (e.g. [B,1,S,1,D] vs [B,H,S,W,D]): no single
                    rep/tile remap exists, so the kernel decomposes the
                    row-block index over ``out_lead`` and strides only
                    the non-broadcast dims of ``lead`` — each distinct
                    operand row is still read once per visit

    ``lead``/``out_lead`` are only populated for ``bcast``: the
    operand's and the output's leading (row) dims.
    """

    var: Any
    role: str
    rows: int
    cols: int
    lead: tuple = ()
    out_lead: tuple = ()

    @property
    def meta(self) -> tuple:
        if self.role == "bcast":
            return (self.role, self.rows, self.cols, self.lead,
                    self.out_lead)
        return (self.role, self.rows, self.cols)


@dataclass(frozen=True)
class MatmulAnchor:
    """The dot_general a matmul-anchored segment is built around.

    The contraction itself runs on the MXU inside the fused kernel
    (contraction grid + f32 accumulator scratch); ``pro_eqns`` is the
    elementwise prologue chain producing the dot's lhs (applied per
    [rows_block, k_block] tile before each partial product),
    ``rhs_pro_eqns`` the weight-side prologue (a bf16/int8 dequant cast
    applied per [k_block, N] weight block instead of materializing the
    cast tensor), and the segment's ordinary ``eqn_idx`` holds the
    epilogue applied to the accumulator in-registers before the single
    store.

    ``form`` selects the contraction layout (see locator.ANCHOR_PRIMS):
      * ``fwd``  — x[M,K] @ w[K,N]; rhs streamed once per row block
      * ``dlhs`` — dx = g[M,N] @ w[K,N]^T; the [K,N] weight read
                   column-major via its block index map (rhs avals are
                   [n, k] here — n output lanes, k contraction)
      * ``drhs`` — dw = x[M,K]^T @ g[M,N]; both operands stream
                   contraction-major, M innermost into a [Kb, Nb]
                   scratch.  ``lhs_specs[0]`` is the row-source
                   (``bulk_m``), ``rhs`` the column-source; an adjacent
                   ``transpose`` of the product (jax's grad emission
                   order) is absorbed via ``extra_eqns``.

    All three forms admit leading, aligned batch dims ([B,...] on both
    operands): ``batch`` is their product (1 when unbatched),
    ``batch_shape`` the dims themselves, ``k``/``n`` stay PER-BATCH
    extents and ``Segment.rows`` folds the batch into the row axis.
    The kernels turn the batch into outer grid positions via their
    block index maps and the rhs re-streams per batch slice.

    ``flash`` (a dict, set by the second-anchor admission) marks a
    flash-shaped segment: this anchor's row-softmaxed scores feed a
    second batched PV contraction, and the whole QK^T -> softmax -> PV
    chain dispatches to the online-softmax flash kernel.  Keys:
    ``eqn_idx`` (the PV dot), ``v_var``/``p_var``, ``softmax_eqns``
    (the absorbed chain, replayed verbatim on the ref path), ``scale``,
    ``scores_var``/``scores_shape``/``scores_dtype`` and ``t_dim`` (the
    per-batch KV length).  For flash segments ``k`` is the head dim and
    ``n`` the value lane width.
    """

    eqn_idx: int                  # the dot_general eqn
    lhs_var: Any                  # the (possibly prologue-produced) lhs
    lhs_specs: list[OperandSpec]  # prologue inputs: roles bulk_k/param_k
    rhs: Any                      # the var feeding the dot's rhs
    pro_eqns: list[int]           # lhs prologue chain (inside the kernel)
    k: int                        # contraction extent (per batch slice)
    n: int                        # lane width of the segment product
    out_var: Any                  # the product var (kernel accumulator)
    out_dtype: Any
    form: str = "fwd"             # "fwd" | "dlhs" | "drhs"
    rhs_specs: list[OperandSpec] = field(default_factory=list)
    rhs_pro_eqns: list[int] = field(default_factory=list)
    extra_eqns: list[int] = field(default_factory=list)
    batch: int = 1                # product of the leading batch dims
    batch_shape: tuple = ()       # the leading batch dims themselves
    flash: Any = None             # flash-shaped second-anchor record


@dataclass
class Segment:
    """A maximal near-bank subgraph with per-operand block views."""

    eqn_idx: list[int]            # eqns fused into the kernel
    rows: int                     # shared row count of the 2-D views
    bulk_shape: tuple[int, ...]   # anchor shape (first bulk output)
    operand_specs: list[OperandSpec]
    outputs: list[Any]            # vars needed outside the segment
    out_cols: list[int]
    donations: list[tuple[int, int]]  # (operand idx, output idx) aliases
    pre_eqns: list[int]           # ejected layout eqns run before the call
    n_compute: int                # ALU eqns (layout prims excluded)
    span_start: int
    span_end: int
    matmul: MatmulAnchor | None = None   # set for matmul-anchored segments
    vmem_bytes: int | None = None        # OffloadPolicy.vmem_budget

    @property
    def n_eqns(self) -> int:
        return len(self.eqn_idx)

    @property
    def all_eqn_idx(self) -> list[int]:
        """Every eqn the fused kernel absorbs, including the anchor
        contraction, its prologue chains, and any absorbed transpose."""
        if self.matmul is None:
            return list(self.eqn_idx)
        return sorted({*self.matmul.pro_eqns, *self.matmul.rhs_pro_eqns,
                       *self.matmul.extra_eqns, self.matmul.eqn_idx,
                       *self.eqn_idx})

    @property
    def bulk_inputs(self) -> list[Any]:
        bulk = [s.var for s in self.operand_specs if s.role != "param"]
        if self.matmul is not None:
            bulk += [s.var for s in self.matmul.lhs_specs
                     if s.role != "param_k"]
            bulk += [s.var for s in self.matmul.rhs_specs
                     if s.role != "param_w"]
        return bulk

    @property
    def param_inputs(self) -> list[Any]:
        params = [s.var for s in self.operand_specs if s.role == "param"]
        if self.matmul is not None:
            params += [s.var for s in self.matmul.lhs_specs
                       if s.role == "param_k"]
            params += [s.var for s in self.matmul.rhs_specs
                       if s.role == "param_w"]
        return params

    def io_bytes(self) -> int:
        """Fused HBM bytes this segment moves: one read per operand —
        with the contraction re-streaming accounted per form (fwd/dlhs:
        the weight once per PER-BATCH row block; drhs: the activation
        once per lane block and the cotangent once per row block,
        matching the (k_rows, n_blocks, m_blocks) grid; flash: k and v
        once per q block while the [S, T] score matrix contributes ZERO
        bytes — it lives and dies in VMEM scratch) — and one write per
        output.  The single source of truth for both the plan's traffic
        accounting and the roofline model."""
        from repro.kernels.fused_matmul import matmul_row_blocks
        from repro.kernels.fused_matmul_bwd import drhs_grid_blocks

        total = sum(_dtype_size(sp.var.aval) for sp in self.operand_specs)
        total += sum(_dtype_size(v.aval) for v in self.outputs)
        if self.matmul is not None:
            mm = self.matmul
            lhs_b = sum(_dtype_size(sp.var.aval) for sp in mm.lhs_specs)
            rhs_bulk = sum(_dtype_size(sp.var.aval) for sp in mm.rhs_specs
                           if sp.role != "param_w")
            rhs_par = sum(_dtype_size(sp.var.aval) for sp in mm.rhs_specs
                          if sp.role == "param_w")
            if mm.flash is not None:
                q_pb = max(self.rows // mm.batch, 1)
                q_blocks = -(-q_pb // min(256, q_pb))   # flash q_block
                total += lhs_b + rhs_par + rhs_bulk * q_blocks
            elif mm.form == "drhs":
                row_blocks, n_blocks = drhs_grid_blocks(
                    self.rows, mm.n, batch=mm.batch,
                    vmem_bytes=self.vmem_bytes)
                total += lhs_b * n_blocks + rhs_bulk * row_blocks + rhs_par
            else:
                total += lhs_b + rhs_par
                total += rhs_bulk * matmul_row_blocks(
                    self.rows, [sp.meta for sp in self.operand_specs],
                    mm.n, batch=mm.batch, vmem_bytes=self.vmem_bytes)
        return total

    def tiling_violations(self) -> list[str]:
        """Breaches of the TPU block tiling rule (``repro.kernels.tiling``)
        by the blocks this segment's kernel would launch, computed with
        the kernel's own layout helpers; empty when every block lowers.
        Flash segments run the flash kernel's fixed blocks."""
        from repro.kernels.fused_elementwise import segment_grid_layout
        from repro.kernels.fused_matmul import matmul_layout
        from repro.kernels.fused_matmul_bwd import dlhs_layout, drhs_layout
        from repro.kernels.tiling import violations

        epi = [sp.meta for sp in self.operand_specs]
        arrays = [sp.var for sp in self.operand_specs] + list(self.outputs)
        mm = self.matmul
        if mm is None:
            *_, ins, outs = segment_grid_layout(
                self.rows, epi, self.out_cols, donate=bool(self.donations))
        elif mm.flash is not None:
            return []
        else:
            geo = dict(batch=mm.batch, vmem_bytes=self.vmem_bytes)
            lhs = [sp.meta for sp in mm.lhs_specs]
            if mm.form == "drhs":
                *_, ins, outs = drhs_layout(mm.k, self.rows, mm.n, epi,
                                            self.out_cols, **geo)
                arrays = [mm.lhs_specs[0].var, mm.rhs_specs[0].var] + arrays
            elif mm.form == "dlhs":
                *_, ins, outs = dlhs_layout(self.rows, mm.k, mm.n, lhs, epi,
                                            self.out_cols, **geo)
                arrays = [sp.var for sp in mm.lhs_specs] + \
                    [mm.rhs_specs[0].var] + arrays
            else:
                *_, ins, outs = matmul_layout(
                    self.rows, mm.k, mm.n, lhs,
                    [sp.meta for sp in mm.rhs_specs], epi, self.out_cols,
                    **geo)
                arrays = [sp.var for sp in (*mm.lhs_specs, *mm.rhs_specs)] \
                    + arrays
        return violations((*ins, *outs), [v.aval.dtype.itemsize
                                          for v in arrays])


@dataclass
class OffloadPlan:
    annotation: JaxprAnnotation
    segments: list[Segment]
    naive_hbm_bytes: int
    fused_hbm_bytes: int
    donated_hbm_bytes: int = 0
    inner_plans: list["OffloadPlan"] = field(default_factory=list)
    # every candidate's §IV-B1 verdict (fused AND declined), in program
    # order — what explain() renders; the policy the planner decided
    # under rides along so a plan is self-describing.
    decisions: list[SegmentDecision] = field(default_factory=list)
    policy: OffloadPolicy | None = None
    # name stack of the eqn (scan, jit) whose body this plan covers,
    # outer ones first; set by the runner, "" at the top level
    scope: str = ""

    def report(self) -> DecisionReport:
        """The per-segment decision report (see ``DecisionReport``),
        nested reports covering scan/jit bodies.  Every fused decision
        row is cross-checked against its emitted segment and rendered
        with a ``verified`` status ("ok" / "MISMATCH(...)" /
        "MISSING-SEGMENT") so decision/plan drift is visible instead of
        silently unreported."""
        from repro.analysis.verifier import decision_statuses
        from repro.core.policy import DEFAULT_POLICY

        statuses = decision_statuses(self)
        eqns = self.annotation.jaxpr.jaxpr.eqns
        return DecisionReport(
            policy=self.policy or DEFAULT_POLICY,
            decisions=[d._with(verified=s, scope="/".join(
                           x for x in (self.scope, _decision_scope(eqns, d))
                           if x))
                       for d, s in zip(self.decisions, statuses)],
            naive_bytes=self.naive_hbm_bytes,
            fused_bytes=self.fused_hbm_bytes,
            inner=[p.report() for p in self.inner_plans])

    def verify(self, closed=None) -> list:
        """Statically verify this plan (alias safety, index-map
        coverage/bounds, VMEM legality, well-formedness); returns the
        list of ``repro.analysis.Finding``.  See docs/analysis.md."""
        from repro.analysis import verify_plan

        return verify_plan(self, closed)

    @property
    def traffic_reduction(self) -> float:
        return self.naive_hbm_bytes / max(self.fused_hbm_bytes, 1)

    @property
    def effective_hbm_bytes(self) -> int:
        """Fused traffic minus boundary buffers donated in place.
        Modeled assuming the kernel grid tiles each segment's rows
        exactly; the launcher drops aliases when it must pad."""
        return max(self.fused_hbm_bytes - self.donated_hbm_bytes, 0)

    @property
    def total_segments(self) -> int:
        """Segments including those planned inside scan/jit bodies."""
        return len(self.segments) + sum(p.total_segments
                                        for p in self.inner_plans)


@dataclass
class OffloadStats:
    """Observability for the plan cache and the staged executable.

    The ``disk_*`` counters cover the persistent plan cache
    (``mpu_offload(persist_dir=...)`` / ``MPU_PLAN_CACHE``): a disk hit
    reconstructs the plan from the durable store instead of re-planning
    (and is NOT a ``plan_miss``); a corrupt/skewed entry is counted,
    quarantined on disk, and falls back to a fresh plan."""

    plan_hits: int = 0
    plan_misses: int = 0
    traces: int = 0
    evictions: int = 0
    plan_invalidations: int = 0  # cached plans dropped on kernel quarantine
    disk_hits: int = 0           # plans reconstructed from the durable store
    disk_misses: int = 0         # store consulted, no usable entry
    disk_corrupt: int = 0        # checksum/version/structure failures
    disk_evictions: int = 0      # on-disk LRU entries this wrapper evicted

    @property
    def hit_rate(self) -> float:
        """Fraction of calls served straight from the plan cache (0.0
        before the first call)."""
        total = self.plan_hits + self.plan_misses + self.disk_hits
        return (self.plan_hits + self.disk_hits) / total if total else 0.0

    def as_dict(self) -> dict[str, float]:
        return {**dataclasses.asdict(self), "hit_rate": self.hit_rate}

    def reset(self) -> None:
        self.plan_hits = self.plan_misses = self.traces = 0
        self.evictions = self.plan_invalidations = 0
        self.disk_hits = self.disk_misses = 0
        self.disk_corrupt = self.disk_evictions = 0

    def __repr__(self) -> str:
        disk = ""
        if self.disk_hits or self.disk_misses or self.disk_corrupt \
                or self.disk_evictions:
            disk = (f", disk_hits={self.disk_hits}, "
                    f"disk_misses={self.disk_misses}, "
                    f"disk_corrupt={self.disk_corrupt}, "
                    f"disk_evictions={self.disk_evictions}")
        return (f"OffloadStats(plan_hits={self.plan_hits}, "
                f"plan_misses={self.plan_misses}, traces={self.traces}, "
                f"plan_evictions={self.evictions}, "
                f"plan_invalidations={self.plan_invalidations}, "
                f"hit_rate={self.hit_rate:.3f}{disk})")


def _dtype_size(aval) -> int:
    return aval.size * aval.dtype.itemsize


def _eqn_io_bytes(eqn) -> int:
    """One eqn's naive HBM round-trip: every operand read, every output
    written (the paper's TSV-style per-eqn traffic accounting)."""
    return sum(_dtype_size(v.aval) for v in (*eqn.invars, *eqn.outvars)
               if not isinstance(v, jcore.Literal))


# eqns XLA executes as free layout folds on the far pipeline: they move
# no bytes of their own (a transpose after a dot is an output-layout
# choice, a broadcast/reshape feeds its consumer's read), so the cost
# decision must not credit a fusion for "eliminating" them.
_FAR_FREE_PRIMS = frozenset(LAYOUT_PRIMS) | {"transpose", "squeeze"}


def _far_decision_bytes(eqns: Sequence, idxs: Sequence[int]) -> int:
    """The far side of the §IV-B1 cost decision: what these eqns would
    actually stream on the far pipeline.  Tighter than the naive
    accounting — layout eqns are free (XLA folds them), a value read
    through a fold streams its *source* bytes (a scalar broadcast to
    [R, C] streams one scalar, not R*C), and an operand read twice by
    one eqn streams once — so the decision never credits fusion for
    savings XLA would realize anyway."""
    folded: dict[Any, int] = {}   # layout output -> folded source bytes

    def read_bytes(v) -> int:
        return folded.get(v, _dtype_size(v.aval))

    total = 0
    for j in idxs:
        eqn = eqns[j]
        if eqn.primitive.name in _FAR_FREE_PRIMS:
            folded[eqn.outvars[0]] = sum(
                read_bytes(v) for v in eqn.invars
                if not isinstance(v, jcore.Literal))
            continue
        seen: set[int] = set()
        for v in (*eqn.invars, *eqn.outvars):
            if isinstance(v, jcore.Literal) or id(v) in seen:
                continue
            seen.add(id(v))
            total += read_bytes(v)
    return total


# ---------------------------------------------------------------------------
# Call flattening: splice trivially-inlinable call bodies into the caller
# so near chains are not cut at jit boundaries (jax.nn.silu & friends).
# ---------------------------------------------------------------------------

# NOTE: no custom_vjp entry.  Inlining a ``custom_vjp_call`` body would
# silently discard the user's backward rule (the inlined forward would
# differentiate by autodiff instead); those eqns re-bind unchanged (via
# ``_bind_eqn``) so the rule rides through the rewrite intact.
_CALL_BODY_PARAM = {
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "custom_jvp_call": "call_jaxpr",
}


def _unspecified(s) -> bool:
    return type(s).__name__ == "UnspecifiedValue"


def _inline_body(eqn) -> Any | None:
    """The ClosedJaxpr to splice in place of ``eqn``, or None.

    ``custom_jvp_call``/``closed_call`` bodies are always inlined: the
    offload trace is post-grad, so the jvp body's forward rule is
    exactly what the trace wants.  ``custom_vjp`` eqns are NEVER inlined
    — their backward rules are numerically load-bearing and inlining
    would drop them — they re-bind unchanged instead.  A ``jit`` is
    inlined only when it carries no shardings or donation AND its body
    is purely elementwise/layout eqns — anything else keeps its call
    boundary (jit fidelity is preserved separately by the runner's
    re-emitted ``jax.jit``)."""
    name = eqn.primitive.name
    if name not in _CALL_BODY_PARAM:
        return None
    body = eqn.params.get(_CALL_BODY_PARAM[name])
    if body is None:
        return None
    if name in ("custom_jvp_call", "closed_call"):
        return body
    if name == "jit":
        if any(not _unspecified(s) for s in eqn.params.get("in_shardings", ())):
            return None
        if any(not _unspecified(s)
               for s in eqn.params.get("out_shardings", ())):
            return None
        if any(eqn.params.get("donated_invars", ())):
            return None
    for e in body.jaxpr.eqns:
        n = e.primitive.name
        if n in ELEMENTWISE_PRIMS or n in LAYOUT_PRIMS:
            continue
        if _inline_body(e) is not None:
            continue
        return None
    return body


def _eqn_scope(eqn, *extra: str):
    """Trace under ``eqn``'s own source info, as ``jax.core.eval_jaxpr``
    re-binds an eqn: its name stack (the model's ``jax.named_scope``s)
    extends the current one, so what the re-bind emits keeps those names
    in the compiled program's op metadata.  ``extra`` scopes go on top."""
    stack = source_info_util.current_name_stack() + eqn.source_info.name_stack
    for name in extra:
        stack = stack.extend(name)
    return source_info_util.user_context(eqn.source_info.traceback,
                                         name_stack=stack)


def _bind_eqn(eqn, invals) -> tuple:
    """Re-bind ``eqn``'s primitive on ``invals`` under its own source
    info; always a tuple of outs.  ``get_bind_params`` turns jaxpr-valued
    params (a ``custom_vjp_call``'s rules, a ``jit``'s body) back into
    what ``bind`` expects."""
    subfuns, params = eqn.primitive.get_bind_params(eqn.params)
    with _eqn_scope(eqn):
        out = eqn.primitive.bind(*subfuns, *invals, **params)
    return tuple(out) if eqn.primitive.multiple_results else (out,)


def _flatten_calls(closed: jcore.ClosedJaxpr) -> jcore.ClosedJaxpr:
    """jaxpr -> jaxpr with inlinable call eqns spliced into the caller.

    Implemented as a functional re-trace (eqn-by-eqn re-bind under
    ``make_jaxpr``) so no JaxprEqn surgery is needed; runs once per plan
    compile.  Invar order and avals are preserved."""
    if not any(_inline_body(e) is not None for e in closed.jaxpr.eqns):
        return closed

    def ev(c, args):
        env: dict[Any, Any] = {}

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]

        for var, val in zip(c.jaxpr.constvars, c.consts):
            env[var] = val
        for var, val in zip(c.jaxpr.invars, args):
            env[var] = val
        for eqn in c.jaxpr.eqns:
            body = _inline_body(eqn)
            if body is not None:
                with _eqn_scope(eqn):
                    outs = ev(body, [read(v) for v in eqn.invars])
            else:
                outs = _bind_eqn(eqn, [read(v) for v in eqn.invars])
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return tuple(read(v) for v in c.jaxpr.outvars)

    avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
             for v in closed.jaxpr.invars]
    return jax.make_jaxpr(lambda *a: ev(closed, a))(*avals)


# ---------------------------------------------------------------------------
# Planning: maximal cross-shape near segments over 2-D block views.
# ---------------------------------------------------------------------------

def _classify_operand(shape: tuple[int, ...], out_shape: tuple[int, ...],
                      rows: int) -> tuple | None:
    """Block view of an elementwise operand vs its eqn's output, or None
    if the broadcast pattern is not expressible as a 2-D index map.
    Returns a ``(role, rows, cols)`` triple, or a 5-tuple
    ``("bcast", rows, cols, lead, out_lead)`` for interior broadcasts."""
    if shape == out_shape:
        r, c = _bulk_view(shape)
        return ("bulk", r, c)
    n = len(out_shape)
    if len(shape) == n and n >= 1:
        if any(d not in (1, od) for d, od in zip(shape, out_shape)):
            return None
        lead = shape[:-1]
        if all(d == 1 for d in lead):
            return ("param", 1, shape[-1])
        r_op = 1
        for d in lead:
            r_op *= d
        cols = shape[-1]
        if r_op == rows:
            return ("bulk", rows, cols)      # lane broadcast [..., 1]
        k = len(lead)
        while k > 0 and lead[k - 1] == 1:
            k -= 1
        if lead[:k] == out_shape[:k]:        # [B, 1, D]-style suffix bcast
            return ("rep", r_op, cols)
        j = 0
        while j < len(lead) and lead[j] == 1:
            j += 1
        if lead[j:] == out_shape[j:n - 1]:   # [1, S, D]-style prefix bcast
            return ("tile", r_op, cols)
        # interior broadcast ([B,1,S,1,D] vs [B,H,S,W,D]): no single
        # rep/tile remap, but every dim is 1-or-matching, so the kernel
        # can decompose the row-block index over the output's leading
        # dims and stride only the non-broadcast ones
        return ("bcast", r_op, cols, lead, tuple(out_shape[:-1]))
    if _is_param_shape(shape):
        return ("param", 1, _lane(shape))
    return None


def plan_offload(closed: jcore.ClosedJaxpr, *,
                 policy: OffloadPolicy | None = None,
                 bulk_threshold: int | None = None,
                 min_segment: int | None = None,
                 donate_invars: frozenset = frozenset()) -> OffloadPlan:
    """Algorithm-1 annotation + maximal cross-shape segment extraction,
    gated by the policy's decision backend (§IV-B1).

    Pure planning on the given (already-flattened) jaxpr: no execution,
    no recursion into call bodies.  Candidate segments are found the
    same way under every mode; each candidate is then priced and either
    fused or declined by ``policy.decide`` — both verdicts land in
    ``OffloadPlan.decisions``.  ``policy`` defaults to the active
    ``offload_policy(...)`` scope (else ``DEFAULT_POLICY``);
    ``bulk_threshold``/``min_segment`` are legacy per-call overrides
    folded into it.  ``donate_invars`` marks jaxpr invars whose buffers
    may be aliased into segment outputs (from the wrapper's
    ``donate_argnums``); intermediates that die at a segment are always
    donation candidates."""
    policy = resolve_policy(policy, bulk_threshold=bulk_threshold,
                            min_segment=min_segment)
    if policy.mode == "cost":
        policy.check_cost_target(jax.devices()[0])
    bulk_threshold = policy.bulk_threshold
    min_segment = policy.min_segment
    ann = annotate_jaxpr(closed, bulk_threshold=bulk_threshold)
    jaxpr = closed.jaxpr
    eqns = jaxpr.eqns

    consumers: dict[Any, list[int]] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not isinstance(v, jcore.Literal):
                consumers.setdefault(v, []).append(i)
    outvar_set = {v for v in jaxpr.outvars if not isinstance(v, jcore.Literal)}
    constvar_set = set(jaxpr.constvars)
    invar_set = set(jaxpr.invars)

    # plan-time scalar resolution: attention's sqrt(head_dim) scale is
    # traced as a scalar eqn chain over consts/literals; the flash
    # matcher folds it into the kernel's static scale
    producer_idx: dict[Any, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            producer_idx[v] = i
    scalar_consts: dict[Any, Any] = {
        v: c for v, c in zip(jaxpr.constvars, closed.consts)
        if getattr(v.aval, "size", 0) == 1}
    scalar_cache: dict[Any, Any] = {}

    def resolve_scalar(v):
        """Concrete value of a scalar var derived only from literals and
        consts (None otherwise), evaluated once at plan time."""
        if getattr(v.aval, "size", 0) != 1:
            return None
        if v in scalar_cache:
            return scalar_cache[v]
        scalar_cache[v] = None           # cycle guard
        val = scalar_consts.get(v)
        if val is None and v in producer_idx:
            e = eqns[producer_idx[v]]
            if len(e.outvars) == 1:
                ins = []
                for u in e.invars:
                    r = u.val if isinstance(u, jcore.Literal) \
                        else resolve_scalar(u)
                    if r is None:
                        ins = None
                        break
                    ins.append(r)
                if ins is not None:
                    try:
                        val = e.primitive.bind(*ins, **e.params)
                    except Exception:
                        val = None
        scalar_cache[v] = val
        return val

    segments: list[Segment] = []
    decisions: list[SegmentDecision] = []
    # mutable run state
    current: list[int] = []
    cur_rows: int | None = None
    n_compute = 0
    anchor: tuple[int, ...] | None = None
    specs: dict[Any, tuple[str, int, int]] = {}   # external operand views
    produced: dict[Any, tuple[str, int]] = {}     # var -> (kind, cols)
    param_out_set: set[int] = set()
    reduced_vars: set[Any] = set()   # rank-reduced row stats: view (rows, 1)
    mm: dict[str, Any] | None = None  # open matmul-anchor state
    hoisted: list[int] = []   # independent scalar eqns passed over the
    #                           segment; they run unfused ahead of it

    def reset():
        nonlocal current, cur_rows, n_compute, anchor, specs, produced, \
            param_out_set, reduced_vars, mm, hoisted
        current, cur_rows, n_compute, anchor = [], None, 0, None
        specs, produced, param_out_set = {}, {}, set()
        reduced_vars, mm = set(), None
        hoisted = []

    def _merge_spec(new_specs, v, cls) -> bool:
        old = specs.get(v) or new_specs.get(v)
        if old is not None and old != cls:
            return False
        new_specs[v] = cls
        return True

    def try_admit_elementwise(i, eqn) -> bool:
        nonlocal cur_rows, n_compute, anchor
        if len(eqn.outvars) != 1:
            return False
        out = eqn.outvars[0]
        nonlit = [v for v in eqn.invars if not isinstance(v, jcore.Literal)]
        # continuation eqns extend a value chain already in the segment:
        # the bulk/eqn-loc gates only guard segment *entry*
        continuation = any(v in produced for v in nonlit)
        if ann.eqn_loc[i] not in (Loc.N, Loc.B) and not continuation:
            return False
        if out.aval.size < bulk_threshold and not continuation:
            return False
        oshape = tuple(out.aval.shape)

        if mm is not None and mm["form"] == "drhs":
            # drhs epilogues run on [Kb, Nb] lane-blocked tiles, so only
            # pure elementwise eqns that keep the full output width are
            # admissible, over full-width / column / param operands (no
            # rep/tile remaps, no row statistics)
            if any(v in reduced_vars for v in nonlit):
                return False
            r_out, c_out = _bulk_view(oshape)
            if r_out != cur_rows or c_out != mm["n"]:
                return False
            new_specs: dict[Any, tuple[str, int, int]] = {}
            for v in nonlit:
                if v in produced:
                    continue
                cls = _classify_operand(tuple(v.aval.shape), oshape,
                                        cur_rows)
                if cls is None or cls[0] not in ("bulk", "param") or \
                        cls[2] not in (1, mm["n"]):
                    return False
                if not _merge_spec(new_specs, v, cls):
                    return False
            specs.update(new_specs)
            produced[out] = ("bulk", c_out)
            current.append(i)
            n_compute += 1
            return True

        if any(v in reduced_vars for v in nonlit):
            # reduced space: rank-reduced row statistics ([B,S] against a
            # [B,S,D] segment) — every value is one element per row, so
            # the whole eqn is a (rows, 1) column op
            rows = cur_rows
            r_out = 1
            for d in oshape:
                r_out *= d
            if rows is None or r_out != rows:
                return False
            new_specs: dict[Any, tuple[str, int, int]] = {}
            for v in nonlit:
                if v in produced:
                    if produced[v][1] != 1:
                        return False
                    continue
                vshape = tuple(v.aval.shape)
                sz = 1
                for d in vshape:
                    sz *= d
                if sz == rows:
                    cls = ("bulk", rows, 1)
                elif sz == 1:
                    cls = ("param", 1, 1)
                else:
                    return False
                if not _merge_spec(new_specs, v, cls):
                    return False
            specs.update(new_specs)
            produced[out] = ("bulk", 1)
            reduced_vars.add(out)
            current.append(i)
            n_compute += 1
            return True

        r_out, c_out = _bulk_view(oshape)
        rows = r_out if cur_rows is None else cur_rows
        if r_out != rows:
            return False
        new_specs = {}
        for v in eqn.invars:
            if isinstance(v, jcore.Literal) or v in produced:
                continue
            cls = _classify_operand(tuple(v.aval.shape), oshape, rows)
            if cls is None or not _merge_spec(new_specs, v, cls):
                return False
        specs.update(new_specs)
        produced[out] = ("bulk", c_out)
        cur_rows = rows
        if anchor is None:
            anchor = oshape
        current.append(i)
        n_compute += 1
        return True

    def try_admit_reduce(i, eqn) -> bool:
        """Lane-axis reduce_sum/reduce_max: the row statistic completes
        inside one [block_rows, cols] tile, so it fuses into the segment
        as a (rows, 1) column (softmax/rmsnorm row stats)."""
        nonlocal cur_rows, n_compute, anchor
        if len(eqn.outvars) != 1:
            return False
        if mm is not None and mm["form"] == "drhs":
            return False     # lane extent is blocked: no row statistics
        v = eqn.invars[0]
        if isinstance(v, jcore.Literal) or v in reduced_vars:
            return False
        vshape = tuple(v.aval.shape)
        if tuple(eqn.params.get("axes", ())) != (len(vshape) - 1,):
            return False                 # only the lane axis reduces near
        if not jnp.issubdtype(eqn.outvars[0].aval.dtype, jnp.floating):
            return False
        r_op = 1
        for d in vshape[:-1]:
            r_op *= d
        cols = vshape[-1]
        rows = r_op if cur_rows is None else cur_rows
        if r_op != rows:
            return False
        new_specs: dict[Any, tuple[str, int, int]] = {}
        if v in produced:
            if produced[v] != ("bulk", cols):
                return False
        else:
            if len(vshape) < 2 or v.aval.size < bulk_threshold:
                return False
            if not _merge_spec(new_specs, v, ("bulk", rows, cols)):
                return False
        specs.update(new_specs)
        out = eqn.outvars[0]
        produced[out] = ("bulk", 1)
        reduced_vars.add(out)
        cur_rows = rows
        if anchor is None:
            anchor = vshape
        current.append(i)
        n_compute += 1
        return True

    def _full_leading_slice(eqn, ishape) -> bool:
        start = eqn.params["start_indices"]
        limit = eqn.params["limit_indices"]
        strides = eqn.params.get("strides") or (1,) * len(start)
        return all(start[d] == 0 and limit[d] == ishape[d]
                   and strides[d] == 1 for d in range(len(ishape) - 1))

    def try_admit_layout(i, eqn) -> bool:
        nonlocal cur_rows, n_compute, anchor
        if mm is not None and mm["form"] == "drhs":
            return False     # lane-blocked tiles: no block-column remaps
        name = eqn.primitive.name
        out = eqn.outvars[0]
        if not jnp.issubdtype(out.aval.dtype, jnp.floating):
            return False
        oshape = tuple(out.aval.shape)
        # rank-1 [N] is a bulk column view (N, 1), not a param: the
        # all-leading-dims-1 test is vacuously true for rank 1, so gate
        # it out explicitly (e.g. jnp.full-style scalar->[N] broadcasts)
        param_out = (_is_param_shape(oshape) and cur_rows != 1
                     and not (len(oshape) == 1 and oshape[0] > 1))

        if param_out:
            # tiny layout eqn over broadcast params ([C] -> [1,C] etc);
            # operands must be external so the eqn can be ejected and run
            # ahead of the kernel if its output escapes the segment.
            new_specs: dict[Any, tuple[str, int, int]] = {}
            for v in eqn.invars:
                if isinstance(v, jcore.Literal):
                    continue
                if v in produced:
                    return False
                vshape = tuple(v.aval.shape)
                if not _is_param_shape(vshape):
                    return False
                if not _merge_spec(new_specs, v, ("param", 1, _lane(vshape))):
                    return False
            if name == "broadcast_in_dim":
                ishape = tuple(eqn.invars[0].aval.shape)
                bdims = eqn.params["broadcast_dimensions"]
                if _lane(ishape) > 1 and (
                        not bdims or bdims[-1] != len(oshape) - 1
                        or oshape[-1] != ishape[-1]):
                    return False
            elif name in ("reshape", "squeeze"):
                if name == "reshape" and eqn.params.get("dimensions"):
                    return False
                if _lane(tuple(eqn.invars[0].aval.shape)) != _lane(oshape):
                    return False
            elif name == "slice":
                ishape = tuple(eqn.invars[0].aval.shape)
                if not _full_leading_slice(eqn, ishape):
                    return False
            elif name == "concatenate":
                if eqn.params["dimension"] != len(oshape) - 1:
                    return False
            else:
                return False
            specs.update(new_specs)
            produced[out] = ("param", _lane(oshape))
            param_out_set.add(i)
            current.append(i)
            return True

        # bulk-out layout eqn
        continuation = any(v in produced for v in eqn.invars
                           if not isinstance(v, jcore.Literal))
        if out.aval.size < bulk_threshold and not continuation:
            return False
        r_out, c_out = _bulk_view(oshape)
        rows = r_out if cur_rows is None else cur_rows
        if r_out != rows:
            return False
        if len(oshape) < 2 and name in ("slice", "concatenate"):
            return False                  # rank-1 lane == row axis
        new_specs = {}

        def external_bulk(v, want_cols=None) -> bool:
            vshape = tuple(v.aval.shape)
            r_in, c_in = _bulk_view(vshape)
            if r_in != rows or (want_cols is not None and c_in != want_cols):
                return False
            return _merge_spec(new_specs, v, ("bulk", rows, c_in))

        if name == "broadcast_in_dim":
            v = eqn.invars[0]
            ishape = tuple(v.aval.shape)
            bdims = tuple(eqn.params["broadcast_dimensions"])
            if (not isinstance(v, jcore.Literal) and v in produced
                    and bdims == tuple(range(len(ishape)))
                    and oshape[:len(ishape)] == ishape
                    and all(d == 1 for d in oshape[len(ishape):])):
                # pure rank expansion appending trailing singleton dims
                # (a [B,S] row stat re-expanding to [B,S,1]): the 2-D
                # view is unchanged
                if produced[v] != ("bulk", c_out):
                    return False
            elif isinstance(v, jcore.Literal):
                if not _is_param_shape(ishape):
                    return False
            elif _is_param_shape(ishape):
                if _lane(ishape) > 1 and (
                        not bdims or bdims[-1] != len(oshape) - 1
                        or oshape[-1] != ishape[-1]):
                    return False
                if v in produced:
                    if produced[v][0] != "param":
                        return False
                elif not _merge_spec(
                        new_specs, v, ("param", 1, _lane(ishape))):
                    return False
            else:
                if bdims != tuple(range(len(oshape) - len(ishape),
                                        len(oshape))):
                    return False
                if v in produced:
                    if produced[v][0] != "bulk":
                        return False
                elif not external_bulk(v):
                    # not a same-rows bulk view: classify the padded
                    # shape the way elementwise operands are — this is
                    # where rep/tile and interior-broadcast ("bcast")
                    # operands enter a segment, since jnp broadcasting
                    # always routes them through an explicit
                    # broadcast_in_dim eqn
                    vshape = (1,) * (len(oshape) - len(ishape)) + ishape
                    cls = _classify_operand(vshape, oshape, rows)
                    if cls is None or cls[0] == "param":
                        return False
                    if not _merge_spec(new_specs, v, cls):
                        return False
        elif name in ("reshape", "squeeze"):
            if name == "reshape" and eqn.params.get("dimensions"):
                return False
            v = eqn.invars[0]
            if isinstance(v, jcore.Literal):
                return False
            if _bulk_view(tuple(v.aval.shape)) != (rows, c_out):
                return False
            if v in produced:
                if produced[v] != ("bulk", c_out):
                    return False
            elif not external_bulk(v, want_cols=c_out):
                return False
        elif name == "slice":
            v = eqn.invars[0]
            ishape = tuple(v.aval.shape)
            if isinstance(v, jcore.Literal):
                return False
            if len(ishape) != len(oshape) or not _full_leading_slice(
                    eqn, ishape):
                return False
            if v in produced:
                if produced[v][0] != "bulk":
                    return False
            elif not external_bulk(v):
                return False
        elif name == "concatenate":
            if eqn.params["dimension"] != len(oshape) - 1:
                return False
            for v in eqn.invars:
                if isinstance(v, jcore.Literal):
                    return False
                vshape = tuple(v.aval.shape)
                if vshape[:-1] != oshape[:-1]:
                    return False
                if v in produced:
                    if produced[v][0] != "bulk":
                        return False
                elif not external_bulk(v):
                    return False
        else:
            return False

        specs.update(new_specs)
        produced[out] = ("bulk", c_out)
        cur_rows = rows
        if anchor is None:
            anchor = oshape
        current.append(i)
        return True

    def _prologue_convertible(anchor_i, lhs_v, m_rows, k_dim):
        """Whether the open elementwise run can be absorbed as the dot's
        lhs prologue (applied per [rows_block, k_block] tile inside the
        kernel).  Returns (pro_eqns, lhs_specs) or None."""
        if lhs_v not in produced or param_out_set or reduced_vars:
            return None
        cur_set = set(current)
        for j in current:
            e = eqns[j]
            if e.primitive.name not in ELEMENTWISE_PRIMS:
                return None
            ov = e.outvars[0]
            if _bulk_view(tuple(ov.aval.shape)) != (m_rows, k_dim):
                return None
            if ov in outvar_set:
                return None
            cons = consumers.get(ov, [])
            if any(c not in cur_set and c != anchor_i for c in cons):
                return None              # chain value escapes: keep split
            if ov is not lhs_v and anchor_i in cons:
                return None              # only the lhs may feed the dot
        seen: set[Any] = set()
        lhs_specs: list[OperandSpec] = []
        for j in current:
            for v in eqns[j].invars:
                if isinstance(v, jcore.Literal) or v in produced or \
                        v in seen:
                    continue
                seen.add(v)
                cls = specs.get(v)
                if cls is None:
                    return None
                role, r, c = cls[0], cls[1], cls[2]
                if role == "bulk" and (r, c) == (m_rows, k_dim):
                    lhs_specs.append(OperandSpec(v, "bulk_k", m_rows, k_dim))
                elif role == "param" and c in (1, k_dim):
                    lhs_specs.append(OperandSpec(v, "param_k", 1, c))
                else:
                    return None     # rep/tile/bcast prologues stay split
        return list(current), lhs_specs

    def _rhs_prologue_convertible(anchor_i, rhs_v, k_dim, n_cols):
        """Whether the open elementwise run can be absorbed as the dot's
        WEIGHT-side prologue (a bf16/int8 dequant cast applied per
        [k_block, N] weight block inside the kernel).  Returns
        (rhs_pro_eqns, rhs_specs) or None."""
        if rhs_v not in produced or reduced_vars:
            return None
        cur_set = set(current)
        for j in current:
            e = eqns[j]
            name = e.primitive.name
            ov = e.outvars[0]
            oshape = tuple(ov.aval.shape)
            param_view = _is_param_shape(oshape) and \
                _lane(oshape) in (1, n_cols)
            if name == "broadcast_in_dim":
                # a [N]/scalar per-channel scale lifted to a [1, N]
                # param view (jax's trace of `w * s`): replayed as a
                # [1, lane] block in the kernel — jnp broadcasting
                # against the [k_block, N] weight block does the rest.
                # It cannot itself BE the dot's rhs.
                v = e.invars[0]
                if isinstance(v, jcore.Literal) or v in produced or \
                        ov is rhs_v:
                    return None
                ishape = tuple(v.aval.shape)
                bdims = tuple(e.params["broadcast_dimensions"])
                if not _is_param_shape(ishape):
                    return None
                if _lane(ishape) > 1 and (
                        not bdims or bdims[-1] != len(oshape) - 1
                        or oshape[-1] != ishape[-1]):
                    return None
            elif name not in ELEMENTWISE_PRIMS:
                return None
            if not param_view and \
                    _bulk_view(oshape) != (k_dim, n_cols):
                return None
            if param_view and ov is rhs_v:
                return None              # the dot's rhs must be [K, N]
            if ov in outvar_set:
                return None
            cons = consumers.get(ov, [])
            if any(c not in cur_set and c != anchor_i for c in cons):
                return None              # chain value escapes: keep split
            if ov is not rhs_v and anchor_i in cons:
                return None              # only the rhs may feed the dot
        seen: set[Any] = set()
        rhs_specs: list[OperandSpec] = []
        for j in current:
            for v in eqns[j].invars:
                if isinstance(v, jcore.Literal) or v in produced or \
                        v in seen:
                    continue
                seen.add(v)
                cls = specs.get(v)
                if cls is None:
                    return None
                role, r, c = cls[0], cls[1], cls[2]
                if role == "bulk" and (r, c) == (k_dim, n_cols):
                    rhs_specs.append(
                        OperandSpec(v, "bulk_w", k_dim, n_cols))
                elif role == "param" and c in (1, n_cols):
                    rhs_specs.append(OperandSpec(v, "param_w", 1, c))
                else:
                    return None
        return list(current), rhs_specs

    def _admit_drhs(i, eqn, lhs_v, rhs_v, lshape, rshape, nb, batch,
                    batch_shape):
        """dw = xT @ g: both operands contract all their (per-batch)
        leading (row) dims, M runs innermost in the kernel into a
        [Kb, Nb] f32 scratch.  jax's transpose rule emits this as
        ``dot_general(g, x, contract-rows)`` followed by a transpose of
        the two trailing dims — when that transpose is the product's
        only consumer and directly adjacent, it is absorbed (the kernel
        writes the [.., K, N] layout directly, no transposed copy).
        With ``nb`` batch dims the grid gains a per-batch row axis and
        the contraction extent ``k`` stays the PER-BATCH m extent."""
        nonlocal mm, cur_rows, n_compute, anchor, current, specs, produced
        if current or lhs_v in produced or rhs_v in produced:
            return False     # a shared cotangent chain escapes: split
        if lshape[:-1] != rshape[:-1]:
            return False
        out = eqn.outvars[0]
        m_ext = 1
        for d in lshape[nb:-1]:
            m_ext *= d
        prod_var = out
        row_src, col_src = lhs_v, rhs_v
        extra: list[int] = []
        cons = consumers.get(out, [])
        want_perm = tuple(range(nb)) + (nb + 1, nb)
        if out not in outvar_set and cons == [i + 1]:
            nxt = eqns[cons[0]]
            if nxt.primitive.name == "transpose" and \
                    tuple(nxt.params["permutation"]) == want_perm:
                prod_var = nxt.outvars[0]
                row_src, col_src = rhs_v, lhs_v
                extra = [cons[0]]
        p_rows = tuple(row_src.aval.shape)[-1]
        n_cols = tuple(col_src.aval.shape)[-1]
        mm = dict(form="drhs", eqn_idx=i, lhs_var=row_src,
                  lhs_specs=[OperandSpec(row_src, "bulk_m",
                                         batch * m_ext, p_rows)],
                  rhs=col_src,
                  rhs_specs=[OperandSpec(col_src, "bulk_w",
                                         batch * m_ext, n_cols)],
                  pro_eqns=[], rhs_pro_eqns=[], extra_eqns=extra,
                  k=m_ext, n=n_cols, out_var=prod_var,
                  out_dtype=prod_var.aval.dtype, span_start=i,
                  batch=batch, batch_shape=batch_shape, flash=None)
        current, specs = [], {}
        produced = {prod_var: ("bulk", n_cols)}
        cur_rows, n_compute = batch * p_rows, 0
        anchor = tuple(prod_var.aval.shape)
        return True

    def _try_admit_flash(i, eqn) -> bool:
        """Second-anchor admission: ride a batched ``dlhs`` anchor whose
        open epilogue run is EXACTLY a scale/mask/row-softmax of the
        scores when the incoming eqn is the batched PV dot.  The pair
        fuses as one flash-shaped segment: anchor 1's row-softmaxed
        accumulator becomes anchor 2's streamed lhs, dispatched to the
        online-softmax flash kernel — the [S, T] score matrix never
        exists in HBM.  Anything that fails the pattern falls back to
        ordinary flush-then-readmit (still correct, just two
        segments)."""
        nonlocal mm, cur_rows, n_compute, anchor, current, specs, \
            produced
        nb = len(mm.get("batch_shape", ()))
        if (mm["form"] != "dlhs" or mm.get("flash") is not None
                or mm["pro_eqns"] or mm["rhs_pro_eqns"] or param_out_set
                or nb == 0):
            return False
        # external operands admitted so far must all be resolvable
        # scalar consts (the sqrt(head_dim) scale) — anything else means
        # the epilogue is not a pure scale/softmax of the scores
        if any(resolve_scalar(v) is None for v in specs):
            return False
        if eqn.primitive.name != "dot_general":
            return False
        (lc, rc), (lbatch, rbatch) = eqn.params["dimension_numbers"]
        if tuple(lbatch) != tuple(range(nb)) or \
                tuple(rbatch) != tuple(range(nb)):
            return False
        if tuple(lc) != (nb + 1,) or tuple(rc) != (nb,):
            return False                 # p[..,S,T] @ v[..,T,Dv]
        lhs_v, rhs_v = eqn.invars
        if isinstance(lhs_v, jcore.Literal) or \
                isinstance(rhs_v, jcore.Literal):
            return False
        if lhs_v not in produced or rhs_v in produced:
            return False
        lshape = tuple(lhs_v.aval.shape)
        rshape = tuple(rhs_v.aval.shape)
        out = eqn.outvars[0]
        t_dim = mm["n"]
        if lshape[:nb] != mm["batch_shape"] or \
                rshape[:nb] != mm["batch_shape"]:
            return False
        if _bulk_view(lshape) != (cur_rows, t_dim):
            return False
        if len(rshape) != nb + 2 or rshape[nb] != t_dim:
            return False
        n2 = rshape[-1]
        # the flash kernel's accumulator/PV tile assumes the value lane
        # width equals the q head dim; other widths fall back to two
        # ordinary anchored segments
        if n2 != mm["k"]:
            return False
        if not jnp.issubdtype(out.aval.dtype, jnp.floating) or any(
                jnp.dtype(v.aval.dtype).itemsize > 4 for v in (rhs_v, out)):
            return False

        # --- match the open run as scale -> row-softmax of the scores
        chain = list(current)
        pos = 0
        x = mm["out_var"]
        scale = 1.0

        def _lit_scalar(v):
            if isinstance(v, jcore.Literal) and \
                    getattr(v.aval, "size", 0) == 1:
                return float(jnp.asarray(v.val).reshape(()))
            return None

        ext_env: dict[Any, Any] = {}     # resolved scale consts, bound
        #                                  into the softmax replay

        def _scale_val(v):
            if isinstance(v, jcore.Literal):
                return _lit_scalar(v)
            c = resolve_scalar(v)
            if c is None:
                return None
            ext_env[v] = c
            return float(jnp.asarray(c).reshape(()))

        while pos < len(chain):          # leading scalar scale eqns
            e = eqns[chain[pos]]
            nm = e.primitive.name
            if nm not in ("mul", "div") or len(e.invars) != 2:
                break
            a, b = e.invars
            if nm == "mul" and a is x:
                s = _scale_val(b)
            elif nm == "mul" and b is x:
                s = _scale_val(a)
            elif nm == "div" and a is x:
                s = _scale_val(b)
                s = None if s == 0.0 else s
            else:
                break
            if s is None:
                break
            scale = scale / s if nm == "div" else scale * s
            x = e.outvars[0]
            pos += 1
        if pos >= len(chain) or \
                eqns[chain[pos]].primitive.name != "reduce_max" or \
                eqns[chain[pos]].invars[0] is not x:
            return False
        stat = eqns[chain[pos]].outvars[0]
        pos += 1
        massage = ("max", "stop_gradient", "broadcast_in_dim", "reshape",
                   "convert_element_type")
        while pos < len(chain):          # keepdims/guard massage of stat
            e = eqns[chain[pos]]
            nm = e.primitive.name
            nonlit = [v for v in e.invars
                      if not isinstance(v, jcore.Literal)]
            if nm not in massage or nonlit != [stat]:
                break
            if nm == "max":
                other = [v for v in e.invars if v is not stat]
                if len(other) != 1 or _lit_scalar(other[0]) is None or \
                        _lit_scalar(other[0]) > -1e9:
                    return False         # a real mask: not plain softmax
            stat = e.outvars[0]
            pos += 1
        if pos >= len(chain):
            return False
        e = eqns[chain[pos]]
        if e.primitive.name != "sub" or e.invars[0] is not x or \
                e.invars[1] is not stat:
            return False
        xs = e.outvars[0]
        pos += 1
        if pos >= len(chain) or eqns[chain[pos]].primitive.name != "exp" \
                or eqns[chain[pos]].invars[0] is not xs:
            return False
        ex = eqns[chain[pos]].outvars[0]
        pos += 1
        if pos >= len(chain) or \
                eqns[chain[pos]].primitive.name != "reduce_sum" or \
                eqns[chain[pos]].invars[0] is not ex:
            return False
        den = eqns[chain[pos]].outvars[0]
        pos += 1
        while pos < len(chain):          # keepdims massage of the denom
            e = eqns[chain[pos]]
            nonlit = [v for v in e.invars
                      if not isinstance(v, jcore.Literal)]
            if e.primitive.name not in ("broadcast_in_dim", "reshape",
                                        "convert_element_type") or \
                    nonlit != [den]:
                break
            den = e.outvars[0]
            pos += 1
        if pos >= len(chain):
            return False
        e = eqns[chain[pos]]
        if e.primitive.name != "div" or e.invars[0] is not ex or \
                e.invars[1] is not den or e.outvars[0] is not lhs_v:
            return False
        pos += 1
        if pos != len(chain):
            return False                 # extra eqns: not a pure softmax

        # no chain value (scores included) may escape the fused pair
        chain_set = set(chain)
        for v in [mm["out_var"]] + [eqns[j].outvars[0] for j in chain]:
            if v in outvar_set or any(
                    c not in chain_set and c != i
                    for c in consumers.get(v, [])):
                return False

        scores = mm["out_var"]
        mm["flash"] = dict(
            eqn_idx=i, v_var=rhs_v, p_var=lhs_v,
            softmax_eqns=tuple(chain), scale=scale, scores_var=scores,
            scores_shape=tuple(scores.aval.shape),
            scores_dtype=scores.aval.dtype, t_dim=t_dim,
            const_env=ext_env)
        mm["extra_eqns"] = list(mm["extra_eqns"]) + chain + [i]
        mm["rhs_specs"] = list(mm["rhs_specs"]) + [
            OperandSpec(rhs_v, "bulk_v", mm["batch"] * t_dim, n2)]
        mm["n"] = n2
        mm["out_var"] = out
        mm["out_dtype"] = out.aval.dtype
        current, specs = [], {}
        produced = {out: ("bulk", n2)}
        reduced_vars.clear()
        anchor = tuple(out.aval.shape)
        return True

    def try_admit_anchor(i, eqn) -> bool:
        """A qualifying dot_general OPENS a matmul-anchored segment: the
        contraction runs inside the fused kernel (contraction grid +
        accumulator scratch) and subsequent elementwise/layout/reduce
        eqns fuse as its epilogue, so the product never round-trips HBM.
        Three forms qualify — the forward x[M,K] @ w[K,N] and the two
        grad-time layouts dx = g @ wT (``dlhs``) and dw = xT @ g
        (``drhs``); see locator.ANCHOR_PRIMS.  All three also admit
        leading, aligned batch dims ([B,H,S,D]-style contractions): the
        batch axes become outer grid axes and the rhs re-streams per
        batch slice.  A second dot arriving on an open batched dlhs
        anchor may fuse the pair flash-shaped (``_try_admit_flash``)."""
        nonlocal mm, cur_rows, n_compute, anchor, current, specs, \
            produced, param_out_set
        if mm is not None:
            return _try_admit_flash(i, eqn)   # one anchor per segment,
            #                                   except the flash pair
        (lc, rc), (lbatch, rbatch) = eqn.params["dimension_numbers"]
        lhs_v, rhs_v = eqn.invars
        if isinstance(lhs_v, jcore.Literal) or isinstance(rhs_v, jcore.Literal):
            return False
        lshape = tuple(lhs_v.aval.shape)
        rshape = tuple(rhs_v.aval.shape)
        nb = len(lbatch)
        if tuple(lbatch) != tuple(range(nb)) or \
                tuple(rbatch) != tuple(range(nb)):
            return False                 # only leading, aligned batches
        if lshape[:nb] != rshape[:nb]:
            return False
        batch_shape = lshape[:nb]
        batch = 1
        for d in batch_shape:
            batch *= d
        out = eqn.outvars[0]
        oshape = tuple(out.aval.shape)
        if not jnp.issubdtype(out.aval.dtype, jnp.floating):
            return False
        # the kernels accumulate in f32: wider dtypes (f64 under x64)
        # would silently lose precision vs the unfused XLA dot
        if any(jnp.dtype(v.aval.dtype).itemsize > 4
               for v in (lhs_v, rhs_v, out)):
            return False
        # nor do they honour a precision the dot asks for (a float32
        # router at the highest precision would run at the kernel's)
        if any(p not in (None, jax.lax.Precision.DEFAULT)
               for p in eqn.params.get("precision") or ()):
            return False
        if out.aval.size < bulk_threshold:
            return False
        form = None
        if len(rshape) == nb + 2 and len(lshape) >= nb + 2 \
                and tuple(lc) == (len(lshape) - 1,):
            if tuple(rc) == (nb,):
                form = "fwd"             # x[..,M,K] @ w[..,K,N]
            elif tuple(rc) == (nb + 1,):
                form = "dlhs"            # g[..,M,N] @ w[..,K,N]^T
        if form is None and len(lshape) == len(rshape) >= nb + 2 \
                and tuple(lc) == tuple(range(nb, len(lshape) - 1)) \
                and tuple(rc) == tuple(range(nb, len(rshape) - 1)):
            form = "drhs"                # xT[..,K,M] @ g[..,M,N]
        if form is None:
            return False
        if form == "drhs":
            return _admit_drhs(i, eqn, lhs_v, rhs_v, lshape, rshape,
                               nb, batch, batch_shape)

        m_rows, n_cols = _bulk_view(oshape)
        k_dim = lshape[-1]
        if _bulk_view(lshape) != (m_rows, k_dim):
            return False
        want_rshape = batch_shape + (
            (k_dim, n_cols) if form == "fwd" else (n_cols, k_dim))
        if rshape != want_rshape:
            return False
        rhs_pro_eqns: list[int] = []
        rhs_specs = [OperandSpec(rhs_v, "bulk_w", *_bulk_view(rshape))]
        if rhs_v in produced:
            # weight-side prologue (unbatched fwd only): the open run
            # must be a dequant-cast chain producing the rhs; the dlhs
            # kernel reads its weight column-major, where a per-block
            # prologue would re-apply per (i, k) step in a different
            # layout
            if form != "fwd" or nb > 0 or lhs_v in produced:
                return False
            conv = _rhs_prologue_convertible(i, rhs_v, k_dim, n_cols)
            if conv is None:
                return False
            rhs_pro_eqns, rhs_specs = conv
            pro_eqns = []
            lhs_specs = [OperandSpec(lhs_v, "bulk_k", m_rows, k_dim)]
            span0, n_pro = current[0], n_compute
            # param-view scale lifts ([N] -> [1, N]) ride inside the
            # weight prologue — they must not be ejected at flush
            param_out_set = set()
        elif current:
            conv = _prologue_convertible(i, lhs_v, m_rows, k_dim)
            if conv is None:
                return False
            pro_eqns, lhs_specs = conv
            span0, n_pro = current[0], n_compute
        else:
            pro_eqns = []
            lhs_specs = [OperandSpec(lhs_v, "bulk_k", m_rows, k_dim)]
            span0, n_pro = i, 0
        mm = dict(form=form, eqn_idx=i, lhs_var=lhs_v, lhs_specs=lhs_specs,
                  rhs=rhs_v, rhs_specs=rhs_specs,
                  rhs_pro_eqns=rhs_pro_eqns, extra_eqns=[],
                  pro_eqns=pro_eqns, k=k_dim, n=n_cols,
                  out_var=out, out_dtype=out.aval.dtype, span_start=span0,
                  batch=batch, batch_shape=batch_shape, flash=None)
        # fresh elementwise state for the epilogue; the product is the
        # segment's root value
        current, specs = [], {}
        produced = {out: ("bulk", n_cols)}
        cur_rows, anchor, n_compute = m_rows, oshape, n_pro
        return True

    def try_admit(i, eqn) -> bool:
        if mm is not None and i in mm["extra_eqns"]:
            return True      # already absorbed at anchor admission
        if mm is not None and mm.get("flash") is not None:
            return False     # the PV dot closes a flash-shaped segment
        tier = eqn_tier(eqn.primitive.name)
        if tier == "near":
            return try_admit_elementwise(i, eqn)
        if tier == "layout":
            return try_admit_layout(i, eqn)
        if tier == "reduce":
            return try_admit_reduce(i, eqn)
        if tier == "anchor":
            return try_admit_anchor(i, eqn)
        return False

    def hoistable(i, eqn) -> bool:
        """A small eqn the open segment can pass over without flushing:
        it consumes nothing the segment produces (so it can run unfused
        just ahead of the kernel via ``pre_eqns``) and its output is
        param-shaped.  The canonical case is attention's
        ``sqrt(head_dim)`` scale constant traced as a scalar eqn chain
        between the QK^T anchor and its epilogue — without hoisting,
        that chain would flush the anchor bare."""
        if mm is None and not current:
            return False                 # no open segment to protect
        if len(eqn.outvars) != 1:
            return False
        if eqn.outvars[0].aval.size >= bulk_threshold:
            return False
        if eqn_tier(eqn.primitive.name) not in ("near", "layout"):
            return False
        return not any(v in produced for v in eqn.invars
                       if not isinstance(v, jcore.Literal))

    def flush():
        if mm is None and n_compute < 1:
            reset()                  # no ALU work at all: not a candidate
            return
        seg_idx = list(current)
        seg_set = set(seg_idx)
        if mm is None:
            span_start, span_end = seg_idx[0], seg_idx[-1]
        else:
            span_start = mm["span_start"]
            span_end = max([mm["eqn_idx"], *mm["extra_eqns"], *seg_idx])

        # eject param-out layout eqns whose output escapes the segment:
        # they run unfused just ahead of the kernel (their operands are
        # external by construction), and their output becomes a plain
        # segment input where consumed inside.  Hoisted scalar eqns
        # (passed over the segment without flushing) join them — the
        # runner jumps the whole span, so anything inside it that is not
        # absorbed by the kernel must run in ``pre_eqns``.
        pre: list[int] = [i for i in hoisted if i < span_end]
        for i in sorted(param_out_set):
            ov = eqns[i].outvars[0]
            if ov in outvar_set or any(ci not in seg_set
                                       for ci in consumers.get(ov, [])):
                seg_set.discard(i)
                pre.append(i)
        pre.sort()
        seg_idx = [i for i in seg_idx if i in seg_set]

        produced_f: dict[Any, tuple[str, int]] = {}
        out_candidates: list[Any] = []
        if mm is not None:
            produced_f[mm["out_var"]] = ("bulk", mm["n"])
            out_candidates.append(mm["out_var"])
        for i in seg_idx:
            out = eqns[i].outvars[0]
            produced_f[out] = produced[out]
            out_candidates.append(out)

        operand_specs: list[OperandSpec] = []
        seen: set[Any] = set()
        for i in seg_idx:
            for v in eqns[i].invars:
                if isinstance(v, jcore.Literal) or v in produced_f or \
                        v in seen:
                    continue
                seen.add(v)
                cls = specs.get(v)
                if cls is None:         # output of an ejected layout eqn
                    cls = ("param", 1, _lane(tuple(v.aval.shape)))
                operand_specs.append(OperandSpec(v, *cls))

        # escape analysis runs over every eqn the kernel absorbs
        member_set = set(seg_set)
        if mm is not None:
            member_set.add(mm["eqn_idx"])
            member_set.update(mm["pro_eqns"])
            member_set.update(mm["rhs_pro_eqns"])
            member_set.update(mm["extra_eqns"])
        outputs, out_cols = [], []
        for v in out_candidates:
            if v in outvar_set or any(ci not in member_set
                                      for ci in consumers.get(v, [])):
                kind, cols = produced_f[v]
                assert kind == "bulk", "segment outputs must be bulk"
                outputs.append(v)
                out_cols.append(cols)
        if not outputs:
            reset()
            return

        # segment-boundary donation: a bulk input whose value dies at
        # this segment may share its buffer with a matching output.
        # Never alias a buffer the matmul side also reads: rhs blocks
        # walk the k axis over ALL rows, so an output row-block written
        # at (i, nk-1) would clobber rhs rows that a later (i+1, k)
        # step still reads (lhs excluded too, conservatively).
        mm_vars: set[Any] = set()
        if mm is not None:
            mm_vars = {mm["rhs"], *(sp.var for sp in mm["lhs_specs"]),
                       *(sp.var for sp in mm["rhs_specs"])}
        donations: list[tuple[int, int]] = []
        taken: set[int] = set()
        for bi, sp in enumerate(operand_specs):
            if sp.role != "bulk" or sp.var in constvar_set or \
                    sp.var in outvar_set or sp.var in mm_vars:
                continue
            if sp.var in invar_set and sp.var not in donate_invars:
                continue
            if any(ci > span_end for ci in consumers.get(sp.var, ())):
                continue
            for oi in range(len(outputs)):
                if oi in taken:
                    continue
                if out_cols[oi] == sp.cols and \
                        outputs[oi].aval.dtype == sp.var.aval.dtype:
                    donations.append((bi, oi))
                    taken.add(oi)
                    break

        anchor_spec = None
        if mm is not None:
            anchor_spec = MatmulAnchor(
                eqn_idx=mm["eqn_idx"], lhs_var=mm["lhs_var"],
                lhs_specs=mm["lhs_specs"], rhs=mm["rhs"],
                pro_eqns=mm["pro_eqns"], k=mm["k"], n=mm["n"],
                out_var=mm["out_var"], out_dtype=mm["out_dtype"],
                form=mm["form"], rhs_specs=mm["rhs_specs"],
                rhs_pro_eqns=mm["rhs_pro_eqns"],
                extra_eqns=mm["extra_eqns"],
                batch=mm.get("batch", 1),
                batch_shape=tuple(mm.get("batch_shape", ())),
                flash=mm.get("flash"))
        seg = Segment(
            eqn_idx=seg_idx, rows=cur_rows, bulk_shape=anchor,
            operand_specs=operand_specs, outputs=outputs, out_cols=out_cols,
            donations=donations, pre_eqns=pre, n_compute=n_compute,
            span_start=span_start, span_end=span_end, matmul=anchor_spec,
            vmem_bytes=policy.vmem_budget)

        # the §IV-B1 decision: price the candidate both ways and let the
        # policy's backend fuse or decline it (the verdict is recorded
        # either way — explain() shows declines with their rationale)
        far_b = _far_decision_bytes(eqns, seg.all_eqn_idx)
        roles = [f"{sp.role}[{sp.rows}x{sp.cols}]"
                 for sp in seg.operand_specs]
        if anchor_spec is not None:
            roles = [f"{sp.role}[{sp.rows}x{sp.cols}]"
                     for sp in (*anchor_spec.lhs_specs,
                                *anchor_spec.rhs_specs)] + roles
        tiling = seg.tiling_violations()
        decision = policy.decide(
            tier="anchor" if anchor_spec is not None else "elementwise",
            n_compute=n_compute, near_bytes=seg.io_bytes(),
            far_bytes=far_b, tiling=tiling[0] if tiling else None)
        decision = decision._with(
            form=_segment_form(seg), rows=cur_rows, roles=tuple(roles),
            batch=anchor_spec.batch_shape if anchor_spec is not None
            else (), eqn=min(seg.all_eqn_idx))
        decisions.append(decision)
        if decision.fused:
            segments.append(seg)
        reset()

    for i, eqn in enumerate(eqns):
        if try_admit(i, eqn):
            continue
        if hoistable(i, eqn):
            hoisted.append(i)
            continue
        flush()
        if not try_admit(i, eqn):
            reset()
    flush()

    # traffic accounting (the TSV analogue): naive = every eqn round-trips
    # HBM; fused = segment boundary tensors only (for anchored segments
    # that includes the matmul operands, while the product itself never
    # leaves the accumulator — the [K, N] rhs weight is counted once per
    # row block, matching the kernel's actual re-streaming); donated =
    # boundary buffers reused in place via input_output_aliases.
    seg_eqns = {i for s in segments for i in s.all_eqn_idx}
    naive = fused = donated = 0
    for i, eqn in enumerate(eqns):
        io_bytes = _eqn_io_bytes(eqn)
        naive += io_bytes
        if i not in seg_eqns:
            fused += io_bytes
    for s in segments:
        fused += s.io_bytes()
        donated += sum(_dtype_size(s.outputs[oi].aval)
                       for _, oi in s.donations)
    return OffloadPlan(ann, segments, naive, fused, donated,
                       decisions=decisions, policy=policy)


# ---------------------------------------------------------------------------
# Segment body: the fused near-bank function over 2-D blocks.
# ---------------------------------------------------------------------------

def _segment_fn(eqns: Sequence, seg: Segment) -> Callable:
    """Build the fused near-bank function for a segment.

    Executed inside the Pallas kernel: every value is a 2-D block —
    bulk/tile values are [block_rows, cols] tiles, params are [1, cols],
    rep values [1, cols] or already repeated to [block_rows, cols]
    (``read_block``) — layout prims become block-local index ops, and
    lane-axis reductions collapse the block to a [block_rows, 1] row
    statistic (the whole lane extent is resident, so the reduce and its
    re-broadcast are two passes over the row inside VMEM).

    For a matmul-anchored segment this is the *epilogue*: the leading
    value is the accumulator block (the dot_general's product), followed
    by the external epilogue operands."""
    in_vars = [s.var for s in seg.operand_specs]
    if seg.matmul is not None:
        in_vars = [seg.matmul.out_var] + in_vars
    rows = seg.rows

    def fn(*vals, block_rows: int):
        env: dict[Any, Any] = dict(zip(in_vars, vals))

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]

        for i in seg.eqn_idx:
            eqn = eqns[i]
            name = eqn.primitive.name
            ins = [read(v) for v in eqn.invars]
            if name == "broadcast_in_dim":
                oshape = tuple(eqn.outvars[0].aval.shape)
                # mirror the planner's view rules: rank-1 [N] outputs
                # are bulk columns (block_rows, 1), not [1, N] params
                if rows > 1 and _is_param_shape(oshape) and \
                        not (len(oshape) == 1 and oshape[0] > 1):
                    target = (1, _lane(oshape))
                else:
                    target = (block_rows, _bulk_view(oshape)[1])
                val = jnp.asarray(ins[0])
                if val.ndim != 2:   # literal / raw param: to [1, lane] view
                    val = val.reshape(1, -1)
                out = jnp.broadcast_to(val, target)
            elif name in ("reshape", "squeeze"):
                out = ins[0]              # identical 2-D view by planning
            elif name == "slice":
                start = eqn.params["start_indices"]
                limit = eqn.params["limit_indices"]
                strides = eqn.params.get("strides") or (1,) * len(start)
                out = ins[0][:, start[-1]:limit[-1]:strides[-1]]
            elif name == "concatenate":
                out = jnp.concatenate([jnp.asarray(x) for x in ins], axis=-1)
            elif name == "reduce_sum":
                out = jnp.asarray(ins[0]).sum(axis=-1, keepdims=True)
            elif name == "reduce_max":
                out = jnp.asarray(ins[0]).max(axis=-1, keepdims=True)
            else:
                out = _bind_f32(eqn, ins)
            env[eqn.outvars[0]] = out
        return tuple(env[v] for v in seg.outputs)

    return fn


def _bind_f32(eqn, ins):
    """Bind one elementwise eqn inside a kernel body.  An op on floats
    narrower than 32 bits computes in f32 and rounds its result back to
    the eqn's dtype: v5e's vector units have no bf16 arithmetic, and
    Mosaic cannot lower every bf16 op (``logistic`` among them)."""
    narrow = [hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
              and x.dtype.itemsize < 4 for x in ins]
    out_dtype = eqn.outvars[0].aval.dtype
    if eqn.primitive.name == "convert_element_type" or not any(narrow):
        out = eqn.primitive.bind(*ins, **eqn.params)
        return out[0] if eqn.primitive.multiple_results else out
    ins = [x.astype(jnp.float32) if n else x for x, n in zip(ins, narrow)]
    out = eqn.primitive.bind(*ins, **eqn.params)
    out = out[0] if eqn.primitive.multiple_results else out
    return out.astype(out_dtype)


def _prologue_fn(eqns: Sequence, mm: MatmulAnchor) -> Callable:
    """The anchored segment's lhs prologue: an elementwise chain applied
    per [rows_block, k_block] tile before each partial product (dtype
    casts, scales, per-channel dequant)."""
    in_vars = [s.var for s in mm.lhs_specs]

    def fn(*vals, block_rows: int):
        env: dict[Any, Any] = dict(zip(in_vars, vals))

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]

        for i in mm.pro_eqns:
            eqn = eqns[i]
            out = eqn.primitive.bind(*(read(v) for v in eqn.invars),
                                     **eqn.params)
            if eqn.primitive.multiple_results:
                out = out[0]
            env[eqn.outvars[0]] = out
        return env[mm.lhs_var]

    return fn


def _rhs_prologue_fn(eqns: Sequence, mm: MatmulAnchor) -> Callable:
    """The anchored segment's weight-side prologue: a dequant-cast chain
    applied per [k_block, N] rhs block (bf16/int8 -> f32, scales) so the
    cast weight is never materialized in HBM."""
    in_vars = [s.var for s in mm.rhs_specs]
    if not mm.rhs_pro_eqns:
        return lambda v, *, block_rows: v

    def fn(*vals, block_rows: int):
        env: dict[Any, Any] = dict(zip(in_vars, vals))

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]

        for i in mm.rhs_pro_eqns:
            eqn = eqns[i]
            if eqn.primitive.name == "broadcast_in_dim":
                # per-channel scale broadcast: keep the [1, lane] param
                # view and let jnp broadcasting meet the weight block
                out = jnp.asarray(read(eqn.invars[0])).reshape(1, -1)
            else:
                out = eqn.primitive.bind(*(read(v) for v in eqn.invars),
                                         **eqn.params)
                if eqn.primitive.multiple_results:
                    out = out[0]
            env[eqn.outvars[0]] = out
        return env[mm.rhs]

    return fn


def _flash_softmax_fn(eqns: Sequence, mm: MatmulAnchor) -> Callable:
    """The flash segment's absorbed scale/softmax chain, replayed
    verbatim (scores -> probabilities) for the ref path — exact numerics
    and, through ``jax.vjp`` over the ref dispatch, exact gradients
    (``stop_gradient`` on the row max included)."""
    fl = mm.flash

    def fn(scores):
        env: dict[Any, Any] = {fl["scores_var"]: scores}
        env.update(fl.get("const_env", {}))

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]

        for j in fl["softmax_eqns"]:
            eqn = eqns[j]
            out = eqn.primitive.bind(*(read(v) for v in eqn.invars),
                                     **eqn.params)
            if eqn.primitive.multiple_results:
                out = out[0]
            env[eqn.outvars[0]] = out
        return env[fl["p_var"]]

    return fn


def _segment_arg_vars(seg: Segment) -> list[Any]:
    """The segment's inputs in the canonical positional order the
    dispatch (and its custom VJP) uses: matmul lhs-side, matmul
    rhs-side, then the epilogue operands."""
    arg_vars: list[Any] = []
    if seg.matmul is not None:
        arg_vars += [s.var for s in seg.matmul.lhs_specs]
        arg_vars += [s.var for s in seg.matmul.rhs_specs]
    arg_vars += [s.var for s in seg.operand_specs]
    return arg_vars


def _segment_form(seg: Segment) -> str | None:
    """A segment's anchor form: None for an elementwise grid, ``flash``
    for a flash anchor (whose base form is dlhs), else fwd/dlhs/drhs."""
    mm = seg.matmul
    if mm is None:
        return None
    return "flash" if mm.flash is not None else mm.form


def _form_kernel(form: str | None) -> str:
    """The guarded kernel a segment of ``form`` runs as."""
    return {None: "fused_segment_grid", "drhs": "fused_matmul_drhs",
            "flash": "fused_flash",
            "dlhs": "fused_matmul_dlhs"}.get(form, "fused_matmul")


def _segment_kernel(seg: Segment) -> str:
    return _form_kernel(_segment_form(seg))


def _decision_scope(eqns: Sequence, d: SegmentDecision) -> str:
    """The name a candidate's ops carry in the compiled program, relative
    to the program holding it: its first eqn's name stack, then
    ``near/<kernel>`` when it runs as a fused kernel (the rewriter
    dispatches it under ``near``, the kernel guard under the kernel).
    Read from the eqns as traced, so a plan replayed from the plan
    cache names what this trace's program carries."""
    if not 0 <= d.eqn < len(eqns):
        return ""
    stack = eqns[d.eqn].source_info.name_stack
    if d.fused:
        stack = stack.extend("near").extend(_form_kernel(d.form))
    return str(stack)


def _segment_dispatch(eqns: Sequence, seg: Segment, vals: Sequence, *,
                      impl: str, donate: Sequence[tuple[int, int]] = ()):
    """Dispatch one planned segment to its fused kernel, routing by
    anchor form (elementwise grid / fwd GEMM / dlhs / drhs).  ``vals``
    follow ``_segment_arg_vars`` order; returns one [rows, out_cols[j]]
    array per segment output."""
    epi_meta = tuple(s.meta for s in seg.operand_specs)
    out_dtypes = [v.aval.dtype for v in seg.outputs]
    mm = seg.matmul
    kernel = _segment_kernel(seg)
    if kernel == "fused_segment_grid":
        return kops.fused_segment_grid(
            _segment_fn(eqns, seg), list(vals), epi_meta, rows=seg.rows,
            out_cols=seg.out_cols, out_dtypes=out_dtypes, donate=donate,
            impl=impl)
    n_lhs, n_rhs = len(mm.lhs_specs), len(mm.rhs_specs)
    lhs_vals = list(vals[:n_lhs])
    rhs_vals = list(vals[n_lhs:n_lhs + n_rhs])
    epi_vals = list(vals[n_lhs + n_rhs:])
    if kernel == "fused_matmul_drhs":
        return kops.fused_matmul_drhs_segment(
            _segment_fn(eqns, seg), lhs_vals[0], rhs_vals[0], epi_vals,
            epi_meta, m_dim=mm.k, rows=seg.rows, n_dim=mm.n,
            acc_dtype=mm.out_dtype, out_cols=seg.out_cols,
            out_dtypes=out_dtypes, donate=donate, impl=impl,
            batch=mm.batch, vmem_bytes=seg.vmem_bytes)
    if kernel == "fused_flash":
        # QK^T -> scale/softmax -> PV as ONE segment
        fl = mm.flash
        return kops.fused_flash_segment(
            _flash_softmax_fn(eqns, mm), lhs_vals[0], rhs_vals[0],
            rhs_vals[1], batch=mm.batch, rows=seg.rows, head_dim=mm.k,
            t_dim=fl["t_dim"], n_dim=mm.n, scale=fl["scale"],
            scores_shape=fl["scores_shape"],
            scores_dtype=fl["scores_dtype"], out_dtype=out_dtypes[0],
            impl=impl)
    if kernel == "fused_matmul_dlhs":
        return kops.fused_matmul_dlhs_segment(
            _prologue_fn(eqns, mm), _segment_fn(eqns, seg), lhs_vals,
            tuple(s.meta for s in mm.lhs_specs), rhs_vals[0], epi_vals,
            epi_meta, rows=seg.rows, k_dim=mm.k, n_dim=mm.n,
            acc_dtype=mm.out_dtype, out_cols=seg.out_cols,
            out_dtypes=out_dtypes, donate=donate, impl=impl,
            batch=mm.batch, vmem_bytes=seg.vmem_bytes)
    return kops.fused_matmul_segment(
        _prologue_fn(eqns, mm), _rhs_prologue_fn(eqns, mm),
        _segment_fn(eqns, seg), lhs_vals,
        tuple(s.meta for s in mm.lhs_specs), rhs_vals,
        tuple(s.meta for s in mm.rhs_specs), epi_vals, epi_meta,
        rows=seg.rows, k_dim=mm.k, n_dim=mm.n, acc_dtype=mm.out_dtype,
        out_cols=seg.out_cols, out_dtypes=out_dtypes, donate=donate,
        impl=impl, batch=mm.batch, vmem_bytes=seg.vmem_bytes)


# ---------------------------------------------------------------------------
# Grad-through-offload: a custom VJP on the fused-segment call.
#
# The fused kernels have no JVP/transpose rules, so differentiating a
# rewritten program would fall over (pallas path) or fall back to
# whatever XLA's AD makes of the ref math (losing the near-bank plan).
# Instead each segment call carries a jax.custom_vjp whose backward
# re-plans the segment's cotangent jaxpr THROUGH THE SAME REWRITER:
# epilogue cotangents fuse as elementwise segments or as anchored
# epilogues/prologues of the dlhs/drhs backward kernels.  Backward
# plans live in a per-segment cache whose keys carry a "bwd" direction
# tag — they can never collide with the forward plan cache (whose keys
# are tagged "fwd" in ``mpu_offload``); module-level counters expose
# their health for tests and benchmarks.
# ---------------------------------------------------------------------------

_BWD_STATS = OffloadStats()
_BWD_PLANS: list[OffloadPlan] = []
_BWD_PLANS_KEEP = 256     # registry ring: bounded introspection window


def bwd_plan_stats() -> OffloadStats:
    """Plan-cache counters for segment cotangent (backward) planning."""
    return _BWD_STATS


def bwd_plans() -> list[OffloadPlan]:
    """Recently compiled backward plans (most recent last)."""
    return list(_BWD_PLANS)


def clear_bwd_plans() -> None:
    _BWD_PLANS.clear()
    _BWD_STATS.reset()


def _segment_bwd_runner(eqns: Sequence, seg: Segment, *,
                        policy: OffloadPolicy) -> Callable:
    """(primals, cotangents) -> operand cotangents, with the cotangent
    jaxpr planned through ``_build_runner`` once per (policy, aval)
    signature and cached on the segment ("bwd"-tagged keys, separate
    from every forward plan cache)."""

    def ref_fn(*vals):
        return _segment_dispatch(eqns, seg, vals, impl="ref", donate=())

    def ct_fn(primals, cts):
        _, vjp_fn = jax.vjp(ref_fn, *primals)
        return tuple(vjp_fn(tuple(cts)))

    cache: dict = seg.__dict__.setdefault("_bwd_plan_cache", {})

    def run_bwd(primals, cts):
        key = ("bwd", policy,
               tuple(_leaf_signature(v) for v in primals),
               tuple(_leaf_signature(v) for v in cts))
        entry = cache.get(key)
        if entry is None:
            _BWD_STATS.plan_misses += 1
            _BWD_STATS.traces += 1
            closed = jax.make_jaxpr(ct_fn)(tuple(primals), tuple(cts))
            run, plan, flat = _build_runner(closed, policy=policy)
            entry = cache[key] = (run, tuple(flat.consts))
            _BWD_PLANS.append(plan)
            del _BWD_PLANS[:-_BWD_PLANS_KEEP]
        else:
            _BWD_STATS.plan_hits += 1
        run, consts = entry
        return tuple(run(consts, [*primals, *cts]))

    return run_bwd


def _segment_vjp(eqns: Sequence, seg: Segment, *,
                 donate: Sequence[tuple[int, int]],
                 policy: OffloadPolicy) -> Callable:
    """The differentiable fused-segment call.  The primal path keeps its
    donation aliases; the VJP forward path drops them (its residuals ARE
    the input buffers the kernel would otherwise overwrite) and the
    backward re-plans the cotangent program through the rewriter under
    the same policy."""
    impl = policy.impl

    @jax.custom_vjp
    def call(*vals):
        return _segment_dispatch(eqns, seg, vals, impl=impl, donate=donate)

    def fwd(*vals):
        outs = _segment_dispatch(eqns, seg, vals, impl=impl, donate=())
        return outs, vals

    bwd_runner = _segment_bwd_runner(eqns, seg, policy=policy)

    def bwd(res, cts):
        return bwd_runner(res, tuple(cts))

    call.defvjp(fwd, bwd)
    return call


# ---------------------------------------------------------------------------
# Plan serialization: the persistent plan cache's payload format.
#
# An OffloadPlan references live jaxpr Vars, so it cannot be pickled
# directly.  But ``jax.make_jaxpr`` + ``_flatten_calls`` on identical
# avals is deterministic, so a plan serializes as *positional var ids*
# over a canonical enumeration of the flattened jaxpr's variables, plus
# a structural fingerprint of that jaxpr.  Deserialization re-traces
# (tracing is needed to build the runner anyway), verifies the
# fingerprint, and rebinds the ids to the fresh trace's Vars — skipping
# the planner entirely.  Anything that fails to match reads as
# corruption: counted, quarantined, and replanned from scratch.
# ---------------------------------------------------------------------------

_PLAN_SCHEMA = 1
_HEXRE = re.compile(r"0x[0-9a-fA-F]+")


class _PlanUnserializable(Exception):
    """This plan cannot round-trip through the payload format (e.g. a
    Literal where a Var is expected) — persistence is skipped, nothing
    else changes."""


class _PlanLedgerMismatch(Exception):
    """A persisted plan does not match the freshly traced program
    (fingerprint skew, exhausted/trailing entries, or a failed
    verify-on-load re-plan comparison) — the caller falls back to a
    fresh plan and quarantines the disk entry."""


def _enumerate_vars(jaxpr) -> dict:
    """Canonical Var -> positional id table (constvars, invars, then
    each eqn's outvars in program order).  Both serialization and
    deserialization enumerate the SAME deterministic trace, so ids line
    up across processes."""
    table: dict[Any, int] = {}

    def add(v):
        if not isinstance(v, jcore.Literal) and v not in table:
            table[v] = len(table)

    for v in jaxpr.constvars:
        add(v)
    for v in jaxpr.invars:
        add(v)
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            add(v)
    return table


def _fp_val(h, val) -> None:
    if isinstance(val, jcore.ClosedJaxpr):
        _fp_jaxpr(h, val.jaxpr)
        return
    if isinstance(val, jcore.Jaxpr):
        _fp_jaxpr(h, val)
        return
    if isinstance(val, (tuple, list)):
        h.update(b"(")
        for v in val:
            _fp_val(h, v)
        h.update(b")")
        return
    if callable(val):
        # function params (custom_vjp rules, jit names): identity by
        # name only — reprs embed process-local addresses
        h.update(f"fn:{getattr(val, '__name__', type(val).__name__)}"
                 .encode())
        return
    h.update(_HEXRE.sub("0x", repr(val)).encode())


def _fp_jaxpr(h, jaxpr) -> None:
    ids: dict[Any, int] = {}

    def vid(v) -> str:
        if isinstance(v, jcore.Literal):
            return f"L:{_HEXRE.sub('0x', repr(v.val))}:{v.aval}"
        if v not in ids:
            ids[v] = len(ids)
        return f"%{ids[v]}:{v.aval}"

    h.update(";".join(vid(v) for v in jaxpr.constvars).encode())
    h.update(b"|")
    h.update(";".join(vid(v) for v in jaxpr.invars).encode())
    for eqn in jaxpr.eqns:
        h.update(f"\n{eqn.primitive.name}(".encode())
        h.update(";".join(vid(v) for v in eqn.invars).encode())
        h.update(b")->")
        h.update(";".join(vid(v) for v in eqn.outvars).encode())
        for k in sorted(eqn.params):
            h.update(f"|{k}=".encode())
            _fp_val(h, eqn.params[k])
    h.update(b"\nout:")
    h.update(";".join(vid(v) for v in jaxpr.outvars).encode())


def _jaxpr_fingerprint(closed: jcore.ClosedJaxpr) -> str:
    h = hashlib.sha256()
    _fp_jaxpr(h, closed.jaxpr)
    return h.hexdigest()


def _spec_payload(sp: OperandSpec, vid) -> dict:
    return {"v": vid(sp.var), "role": sp.role, "rows": sp.rows,
            "cols": sp.cols, "lead": list(sp.lead),
            "out_lead": list(sp.out_lead)}


def _spec_from(d: dict, rev) -> OperandSpec:
    return OperandSpec(rev[d["v"]], d["role"], d["rows"], d["cols"],
                       tuple(d["lead"]), tuple(d["out_lead"]))


def _plan_payload(plan: OffloadPlan, closed: jcore.ClosedJaxpr) -> dict:
    """JSON-able structure of ONE plan level (inner plans are separate
    ledger entries, recorded in recursion order)."""
    table = _enumerate_vars(closed.jaxpr)

    def vid(v) -> int:
        if isinstance(v, jcore.Literal) or v not in table:
            raise _PlanUnserializable(f"unmappable segment var: {v!r}")
        return table[v]

    def mm_payload(mm: MatmulAnchor | None):
        if mm is None:
            return None
        flash = None
        if mm.flash is not None:
            f = mm.flash
            flash = {
                "eqn_idx": f["eqn_idx"], "v_var": vid(f["v_var"]),
                "p_var": vid(f["p_var"]),
                "softmax_eqns": list(f["softmax_eqns"]),
                "scale": float(f["scale"]),
                "scores_var": vid(f["scores_var"]),
                "scores_shape": list(f["scores_shape"]),
                "scores_dtype": str(jnp.dtype(f["scores_dtype"])),
                "t_dim": f["t_dim"],
                "const_env": [
                    [vid(v), float(jnp.asarray(c).reshape(())),
                     str(jnp.asarray(c).dtype), list(jnp.shape(c))]
                    for v, c in f["const_env"].items()],
            }
        return {
            "eqn_idx": mm.eqn_idx, "lhs_var": vid(mm.lhs_var),
            "lhs_specs": [_spec_payload(s, vid) for s in mm.lhs_specs],
            "rhs": vid(mm.rhs), "pro_eqns": list(mm.pro_eqns),
            "k": mm.k, "n": mm.n, "out_var": vid(mm.out_var),
            "out_dtype": str(jnp.dtype(mm.out_dtype)), "form": mm.form,
            "rhs_specs": [_spec_payload(s, vid) for s in mm.rhs_specs],
            "rhs_pro_eqns": list(mm.rhs_pro_eqns),
            "extra_eqns": list(mm.extra_eqns), "batch": mm.batch,
            "batch_shape": list(mm.batch_shape), "flash": flash,
        }

    return {
        "fingerprint": _jaxpr_fingerprint(closed),
        "naive": plan.naive_hbm_bytes,
        "fused": plan.fused_hbm_bytes,
        "donated": plan.donated_hbm_bytes,
        "segments": [{
            "eqn_idx": list(s.eqn_idx), "rows": s.rows,
            "bulk_shape": list(s.bulk_shape),
            "operand_specs": [_spec_payload(sp, vid)
                              for sp in s.operand_specs],
            "outputs": [vid(v) for v in s.outputs],
            "out_cols": list(s.out_cols),
            "donations": [list(d) for d in s.donations],
            "pre_eqns": list(s.pre_eqns), "n_compute": s.n_compute,
            "span_start": s.span_start, "span_end": s.span_end,
            "matmul": mm_payload(s.matmul), "vmem_bytes": s.vmem_bytes,
        } for s in plan.segments],
        "decisions": [dataclasses.asdict(d) for d in plan.decisions],
    }


def _plan_from_payload(payload: dict, closed: jcore.ClosedJaxpr,
                       policy: OffloadPolicy) -> OffloadPlan:
    """Rebind a persisted plan to a freshly traced jaxpr.  Raises
    ``_PlanLedgerMismatch`` on any structural disagreement."""
    if payload.get("fingerprint") != _jaxpr_fingerprint(closed):
        raise _PlanLedgerMismatch("jaxpr fingerprint skew")
    try:
        rev = {i: v for v, i in _enumerate_vars(closed.jaxpr).items()}
        n_eqns = len(closed.jaxpr.eqns)

        def mm_from(d):
            if d is None:
                return None
            flash = None
            if d["flash"] is not None:
                f = d["flash"]
                flash = dict(
                    eqn_idx=f["eqn_idx"], v_var=rev[f["v_var"]],
                    p_var=rev[f["p_var"]],
                    softmax_eqns=tuple(f["softmax_eqns"]),
                    scale=f["scale"], scores_var=rev[f["scores_var"]],
                    scores_shape=tuple(f["scores_shape"]),
                    scores_dtype=jnp.dtype(f["scores_dtype"]),
                    t_dim=f["t_dim"],
                    const_env={
                        rev[i]: jnp.asarray(v, dtype=dt).reshape(shp)
                        for i, v, dt, shp in f["const_env"]})
            return MatmulAnchor(
                eqn_idx=d["eqn_idx"], lhs_var=rev[d["lhs_var"]],
                lhs_specs=[_spec_from(s, rev) for s in d["lhs_specs"]],
                rhs=rev[d["rhs"]], pro_eqns=list(d["pro_eqns"]),
                k=d["k"], n=d["n"], out_var=rev[d["out_var"]],
                out_dtype=jnp.dtype(d["out_dtype"]), form=d["form"],
                rhs_specs=[_spec_from(s, rev) for s in d["rhs_specs"]],
                rhs_pro_eqns=list(d["rhs_pro_eqns"]),
                extra_eqns=list(d["extra_eqns"]), batch=d["batch"],
                batch_shape=tuple(d["batch_shape"]), flash=flash)

        segments = []
        for s in payload["segments"]:
            if not (0 <= s["span_start"] <= s["span_end"] < n_eqns):
                raise _PlanLedgerMismatch("segment span out of range")
            segments.append(Segment(
                eqn_idx=list(s["eqn_idx"]), rows=s["rows"],
                bulk_shape=tuple(s["bulk_shape"]),
                operand_specs=[_spec_from(sp, rev)
                               for sp in s["operand_specs"]],
                outputs=[rev[i] for i in s["outputs"]],
                out_cols=list(s["out_cols"]),
                donations=[tuple(d) for d in s["donations"]],
                pre_eqns=list(s["pre_eqns"]), n_compute=s["n_compute"],
                span_start=s["span_start"], span_end=s["span_end"],
                matmul=mm_from(s["matmul"]),
                vmem_bytes=s["vmem_bytes"]))
        decisions = [SegmentDecision(**{
            **d, "roles": tuple(d["roles"]), "batch": tuple(d["batch"])})
            for d in payload["decisions"]]
    except _PlanLedgerMismatch:
        raise
    except Exception as e:
        raise _PlanLedgerMismatch(f"payload decode failed: {e}") from e
    ann = annotate_jaxpr(closed, bulk_threshold=policy.bulk_threshold)
    return OffloadPlan(ann, segments, payload["naive"], payload["fused"],
                       payload["donated"], decisions=decisions,
                       policy=policy)


def _plan_structure(plan: OffloadPlan) -> tuple:
    """The structural signature verify-on-load compares: segment spans,
    block views, and anchor identity — everything that determines WHAT
    the runner fuses (byte accounting rides along in the payload and is
    not re-derived, so it is excluded)."""
    out = []
    for s in plan.segments:
        mm = s.matmul
        out.append((tuple(s.eqn_idx), s.span_start, s.span_end, s.rows,
                    tuple(s.out_cols), tuple(s.pre_eqns),
                    tuple(sp.meta for sp in s.operand_specs),
                    None if mm is None else
                    (mm.eqn_idx, mm.form, mm.k, mm.n, mm.batch,
                     mm.flash is not None)))
    return tuple(out)


class _PlanLedger:
    """Ordered record/replay of every plan one ``_build_runner``
    recursion builds: the top-level plan first, then scan/jit body
    plans in recursion order.  Record mode captures payloads for
    persistence; replay mode feeds them back so a warm process does
    ZERO fresh planning.  A plan that cannot serialize poisons the
    ledger (``entries`` becomes None): the build proceeds normally, it
    just is not persisted."""

    def __init__(self, entries: list | None = None,
                 policy: OffloadPolicy | None = None):
        self.replaying = entries is not None
        self.entries: list | None = list(entries) if entries is not None \
            else []
        self.policy = policy
        self._i = 0

    def record(self, closed: jcore.ClosedJaxpr, plan: OffloadPlan) -> None:
        if self.entries is None:
            return
        try:
            self.entries.append(_plan_payload(plan, closed))
        except _PlanUnserializable:
            self.entries = None

    def take(self, closed: jcore.ClosedJaxpr) -> OffloadPlan:
        if self.entries is None or self._i >= len(self.entries):
            raise _PlanLedgerMismatch("ledger exhausted")
        payload = self.entries[self._i]
        self._i += 1
        return _plan_from_payload(payload, closed, self.policy)

    def complete(self) -> bool:
        return self.entries is not None and self._i == len(self.entries)


# ---------------------------------------------------------------------------
# The compile-time rewriter.
# ---------------------------------------------------------------------------

def _build_runner(closed: jcore.ClosedJaxpr, *, policy: OffloadPolicy,
                  donate_leaves: Sequence[int] = (),
                  ledger: "_PlanLedger | None" = None, scope: str = ""
                  ) -> tuple[Callable, OffloadPlan, jcore.ClosedJaxpr]:
    """The compile-time pass: flatten + plan once under ``policy``, then
    bake every offload decision into a flat list of step closures.

    Returns ``(run, plan, flat)`` where ``flat`` is the flattened
    ClosedJaxpr the plan indexes into, and ``run(consts, args)`` is a
    pure, jit-traceable function: near segments dispatch to
    ``kops.fused_segment_grid`` (with donation aliases baked in), scan
    bodies carry a pre-rewritten body runner, non-trivial jit eqns are
    re-emitted through ``jax.jit`` with their shardings/donation, and
    everything else re-binds its primitive unchanged.

    ``ledger`` threads the persistent plan cache through the recursion:
    in replay mode each level's plan is reconstructed from the durable
    payload instead of running the planner; in record mode each level's
    plan is captured for persistence.  ``scope`` is the name stack of
    the eqns whose bodies hold this level (``OffloadPlan.scope``)."""
    closed = _flatten_calls(closed)
    donate_invars = frozenset(closed.jaxpr.invars[i] for i in donate_leaves)
    if ledger is not None and ledger.replaying:
        plan = ledger.take(closed)
    else:
        plan = plan_offload(closed, policy=policy,
                            donate_invars=donate_invars)
        if ledger is not None:
            ledger.record(closed, plan)
    plan.scope = scope
    jaxpr = closed.jaxpr
    eqns = jaxpr.eqns
    seg_by_start = {s.span_start: s for s in plan.segments}

    def recurse(eqn, inner: jcore.ClosedJaxpr,
                donate_inner: Sequence[int] = ()) -> tuple[Callable, tuple]:
        inner_run, inner_plan, inner_flat = _build_runner(
            inner, policy=policy, donate_leaves=donate_inner,
            ledger=ledger, scope="/".join(
                x for x in (scope, str(eqn.source_info.name_stack)) if x))
        plan.inner_plans.append(inner_plan)
        return inner_run, tuple(inner_flat.consts)

    def make_seg_step(seg: Segment) -> Callable:
        out_shapes = [tuple(v.aval.shape) for v in seg.outputs]
        arg_vars = _segment_arg_vars(seg)
        call = _segment_vjp(eqns, seg, donate=tuple(seg.donations),
                            policy=policy)
        first = eqns[min(seg.all_eqn_idx)]

        def step(env, read):
            with _eqn_scope(first, "near"):
                outs = call(*[read(v) for v in arg_vars])
            for var, val, shp in zip(seg.outputs, outs, out_shapes):
                env[var] = val.reshape(shp)
        return step

    def make_scan_step(eqn) -> Callable:
        p = eqn.params
        n_consts, n_carry = p["num_consts"], p["num_carry"]
        # scan carries are donation candidates inside the rewritten
        # body: a carry whose value dies at a body segment shares its
        # buffer with a matching segment output (lax.scan double-buffers
        # carries, so in-place reuse within one iteration is safe; the
        # planner still verifies the value is dead past the segment)
        inner_run, inner_consts = recurse(
            eqn, p["jaxpr"], donate_inner=tuple(
                range(n_consts, n_consts + n_carry)))

        def step(env, read):
            invals = [read(v) for v in eqn.invars]
            sc = tuple(invals[:n_consts])
            carry0 = tuple(invals[n_consts:n_consts + n_carry])
            xs = tuple(invals[n_consts + n_carry:])

            def body(carry, x):
                outs = inner_run(inner_consts, (*sc, *carry, *x))
                return tuple(outs[:n_carry]), tuple(outs[n_carry:])

            with _eqn_scope(eqn):
                carry, ys = jax.lax.scan(
                    body, carry0, xs, length=p["length"],
                    reverse=p.get("reverse", False),
                    unroll=p.get("unroll", 1))
            for var, val in zip(eqn.outvars, (*carry, *ys)):
                env[var] = val
        return step

    def make_inline_call_step(eqn, inner_run, inner_consts) -> Callable:
        def step(env, read):
            with _eqn_scope(eqn):
                outs = inner_run(inner_consts,
                                 [read(v) for v in eqn.invars])
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return step

    def make_jit_step(eqn) -> Callable:
        """Re-emit non-trivial jit eqns through ``jax.jit`` so their
        in/out shardings and donated invars survive the rewrite instead
        of being dropped on inlining."""
        inner_run, inner_consts = recurse(eqn, eqn.params["jaxpr"])
        in_sh = eqn.params.get("in_shardings", ())
        out_sh = eqn.params.get("out_shardings", ())
        donated = tuple(i for i, d
                        in enumerate(eqn.params.get("donated_invars", ()))
                        if d)
        # only fully-specified sharding tuples pass through: a partially
        # specified tuple would need UnspecifiedValue placeholders that
        # jax.jit's public API does not accept, so those are dropped
        # (same placement loss as inlining, but donation is still kept)
        jit_kwargs: dict[str, Any] = {}
        if in_sh and all(not _unspecified(s) for s in in_sh):
            jit_kwargs["in_shardings"] = tuple(in_sh)
        if out_sh and all(not _unspecified(s) for s in out_sh):
            jit_kwargs["out_shardings"] = tuple(out_sh)
        if not jit_kwargs and not donated:
            return make_inline_call_step(eqn, inner_run, inner_consts)

        def call(*a):
            return inner_run(inner_consts, a)

        jitted = jax.jit(call, donate_argnums=donated, **jit_kwargs)

        def step(env, read):
            with _eqn_scope(eqn):
                outs = jitted(*[read(v) for v in eqn.invars])
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return step

    def make_eqn_step(eqn) -> Callable:
        def step(env, read):
            outs = _bind_eqn(eqn, [read(v) for v in eqn.invars])
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return step

    steps: list[Callable] = []
    i = 0
    while i < len(eqns):
        if i in seg_by_start:
            seg = seg_by_start[i]
            for j in seg.pre_eqns:
                steps.append(make_eqn_step(eqns[j]))
            steps.append(make_seg_step(seg))
            i = seg.span_end + 1
            continue
        eqn = eqns[i]
        name = eqn.primitive.name
        if name == "scan":
            steps.append(make_scan_step(eqn))
        elif name == "jit":
            steps.append(make_jit_step(eqn))
        else:
            # custom_jvp_call/closed_call never reach here (their bodies
            # are inlined by _flatten_calls); custom_vjp eqns DO — they
            # re-bind unchanged so the user's backward rule survives
            steps.append(make_eqn_step(eqn))
        i += 1

    def run(consts, args):
        env: dict[Any, Any] = {}

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]

        for var, val in zip(jaxpr.constvars, consts):
            env[var] = val
        for var, val in zip(jaxpr.invars, args):
            env[var] = val
        for step in steps:
            step(env, read)
        return tuple(read(v) for v in jaxpr.outvars)

    return run, plan, closed


def _normalize_donate(donate_argnums) -> tuple[int, ...]:
    if isinstance(donate_argnums, int):
        return (donate_argnums,)
    return tuple(donate_argnums)


def _donate_leaf_indices(args, donate: tuple[int, ...]) -> tuple[int, ...]:
    """Map user-level donated argument positions to flat leaf indices
    (== jaxpr invar indices) of the traced call."""
    idx: list[int] = []
    off = 0
    for ai, a in enumerate(args):
        n = len(jax.tree.leaves(a))
        if ai in donate:
            idx.extend(range(off, off + n))
        off += n
    return tuple(idx)


def rewrite_offload(closed: jcore.ClosedJaxpr, *,
                    policy: OffloadPolicy | None = None,
                    bulk_threshold: int | None = None,
                    min_segment: int | None = None, impl: str | None = None,
                    donate_argnums: int | Sequence[int] = ()
                    ) -> tuple[jcore.ClosedJaxpr, OffloadPlan]:
    """jaxpr -> jaxpr: re-stage the runner so each near segment appears
    as a single fused kernel eqn (carrying its ``input_output_aliases``)
    in the returned ``ClosedJaxpr``.  ``policy`` selects the decision
    backend (default: the active ``offload_policy`` scope);
    ``donate_argnums`` indexes the (flat) jaxpr invars whose buffers
    segments may alias."""
    policy = resolve_policy(policy, bulk_threshold=bulk_threshold,
                            min_segment=min_segment, impl=impl)
    run, plan, flat = _build_runner(
        closed, policy=policy,
        donate_leaves=_normalize_donate(donate_argnums))
    consts = tuple(flat.consts)
    avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
             for v in flat.jaxpr.invars]
    rewritten = jax.make_jaxpr(lambda *a: run(consts, a))(*avals)
    return rewritten, plan


def _leaf_signature(leaf) -> tuple:
    """Hashable aval signature of one argument leaf (what
    ``jax.eval_shape`` would see)."""
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:  # python scalar
        dtype = jnp.result_type(leaf)
    return (shape, jnp.dtype(dtype).name,
            bool(getattr(leaf, "weak_type", isinstance(leaf, (int, float)))))


@dataclass
class _CompiledOffload:
    """One plan-cache entry: everything derived from an aval signature."""

    plan: OffloadPlan
    executable: Callable         # jitted flat runner
    out_tree: Any
    closed: jcore.ClosedJaxpr    # the original (pre-rewrite) jaxpr
    run: Callable                # un-jitted runner (for re-staging)
    flat: jcore.ClosedJaxpr      # the flattened jaxpr the plan indexes

    def restage(self) -> jcore.ClosedJaxpr:
        """The rewritten ClosedJaxpr, staged from the already-built
        runner (no second flatten/plan/build)."""
        consts = tuple(self.flat.consts)
        avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                 for v in self.flat.jaxpr.invars]
        return jax.make_jaxpr(lambda *a: self.run(consts, a))(*avals)


def mpu_offload(fn: Callable, *, policy: OffloadPolicy | None = None,
                donate_argnums: int | Sequence[int] = (),
                persist_dir: str | None = None,
                verify_loaded: bool | None = None,
                verify_plans: bool | None = None,
                bulk_threshold: int | None = None,
                min_segment: int | None = None, impl: str | None = None,
                max_plans: int | None = None) -> Callable:
    """Compile-time offload transform with a bounded, policy-keyed plan
    cache.

    ``policy`` (an ``OffloadPolicy``) is the single configuration
    object: decision mode (greedy/cost/all_near/all_far), planner
    thresholds, kernel impl, VMEM budget, machine model and cache
    bound.  When omitted, the wrapper is *unpinned*: each call resolves
    the active ``with offload_policy(p):`` scope (else the default).  A
    scoped override always wins — even over a pinned policy — for the
    duration of the scope; because the policy is part of every
    plan-cache key, the same avals under a different policy compile a
    fresh plan and can never hit a stale one.  The legacy kwargs
    (``bulk_threshold``/``min_segment``/``impl``/``max_plans``) still
    work through a deprecation shim that builds the equivalent pinned
    policy.

    Returns ``wrapped`` such that ``wrapped(*args)``:
      1. looks up (effective policy, aval signature) in the plan cache;
      2. on miss, traces ``fn`` once, runs the rewriter once, and stages
         the result through ``jax.jit`` (evicting the least-recently-used
         plan beyond ``max_plans`` entries);
      3. on hit (and on every later call with the same key) dispatches
         straight into the compiled executable — zero re-planning, zero
         re-tracing.

    ``donate_argnums`` marks positional arguments whose buffers fused
    segments may reuse in place (threaded through the staged jit's
    ``donate_argnums`` AND the kernels' ``input_output_aliases``); as
    with ``jax.jit``, donated arguments must be fresh on every call.

    ``persist_dir`` (default: the ``MPU_PLAN_CACHE`` env var) enables
    the **persistent plan cache**: plans are serialized to a durable
    ``ArtifactStore`` keyed by (policy, direction, jaxpr fingerprint,
    donation), so a fresh process — or a fleet sharing the directory —
    starts hot: an in-memory miss that hits disk reconstructs the plan
    with ZERO fresh planning (``stats.disk_hits``, and NOT a
    ``plan_miss``).  Corrupt / truncated / version-skewed entries are
    counted (``disk_corrupt``), quarantined on disk, and fall back to a
    fresh plan — never an exception.  Guard interplay: while the kernel
    guard is degraded for this policy's impl, the store is neither read
    nor written (quarantined kernels must never be served from disk,
    and degraded all_far plans are never persisted).  ``verify_loaded``
    (default: the ``MPU_PLAN_VERIFY`` env var) re-plans on every disk
    load and structurally compares — a safety net for fingerprint
    collisions that turns any mismatch into ``disk_corrupt``.

    ``verify_plans`` (default: the ``MPU_VERIFY_PLANS`` env var) runs
    the static plan verifier (``repro.analysis``) over every plan this
    wrapper compiles — fresh AND disk-loaded — and raises
    ``PlanVerificationError`` on any error-severity finding before the
    plan is staged.  Plans persisted under verification carry a
    ``verified`` marker in their artifact meta.

    ``wrapped`` composes with ``jax.jit`` / donation (the inner jit
    collapses into the outer trace), and exposes:
      * ``wrapped.stats``        — OffloadStats
                                   (plan_hits/plan_misses/traces/evictions)
      * ``wrapped.policy``       — the pinned policy (None if unpinned)
      * ``wrapped.plan_for(*a)`` — the OffloadPlan for a signature
      * ``wrapped.explain(*a)``  — the per-segment DecisionReport (tier,
                                   anchor form, io bytes, modeled
                                   near/far time, fuse/decline rationale)
      * ``wrapped.rewritten(*a)``— the rewritten ClosedJaxpr
      * ``wrapped.cache_clear()`` / ``wrapped.cache_size()``
    """
    def _enforce_verified(plan: OffloadPlan) -> None:
        from repro.analysis import PlanVerificationError, verify_plan

        findings = verify_plan(plan)
        errors = [f for f in findings if f.severity == "error"]
        if errors:
            raise PlanVerificationError(errors)

    policy = fold_legacy_kwargs(
        policy, where="mpu_offload", bulk_threshold=bulk_threshold,
        min_segment=min_segment, impl=impl, max_plans=max_plans)
    donate = _normalize_donate(donate_argnums)
    cache: OrderedDict[Any, _CompiledOffload] = OrderedDict()
    stats = OffloadStats()
    if persist_dir is None:
        persist_dir = os.environ.get("MPU_PLAN_CACHE") or None
    if verify_loaded is None:
        verify_loaded = os.environ.get("MPU_PLAN_VERIFY", "") not in ("", "0")
    if verify_plans is None:
        verify_plans = os.environ.get("MPU_VERIFY_PLANS", "") \
            not in ("", "0")
    store_box: list = []   # lazily-built ArtifactStore (or None on failure)

    def persist_store():
        if persist_dir is None:
            return None
        if not store_box:
            from repro.core.artifacts import ArtifactStore
            try:
                store_box.append(ArtifactStore(persist_dir))
            except OSError:
                store_box.append(None)
        return store_box[0]
    # the LRU bound is a property of this wrapper's cache, fixed at wrap
    # time (a scoped policy override re-keys plans but does not resize)
    cache_bound = (policy or OffloadPolicy()).max_plans

    # kernel-guard epoch this wrapper's cache was last validated against
    # (quarantines/resets bump the global epoch; see sync_guard below)
    guard_seen = [kernel_guard().epoch]

    def effective_policy() -> OffloadPolicy:
        override = active_policy_override()
        pol = override if override is not None else (
            policy if policy is not None else OffloadPolicy())
        # graceful degradation: while any fused-segment kernel is
        # quarantined at this policy's resolved impl, plan everything on
        # the far pipeline (the paper's always-works tier).  The policy
        # is part of every cache key, so the all_far plan is a fresh
        # compile — and when the quarantine lifts (guard reset) the
        # original keys resolve again untouched.
        if pol.mode != "all_far" and kernel_guard().degraded_for(pol.impl):
            pol = pol.replace(mode="all_far")
        return pol

    def sync_guard(count: bool) -> None:
        """On a kernel-guard epoch change (quarantine tripped or reset),
        invalidate cached plans that dispatch fused segments — their
        compiled executables bake in the now-suspect kernel.  all_far
        plans (zero segments) survive: they never touch Pallas."""
        guard = kernel_guard()
        if guard.epoch == guard_seen[0]:
            return
        guard_seen[0] = guard.epoch
        stale = [k for k, e in cache.items() if e.plan.total_segments > 0]
        for k in stale:
            del cache[k]
            if count:
                stats.plan_invalidations += 1

    def try_disk_load(store, dkey, flat0, pol, donate_leaves):
        """One attempt to rebuild the runner from a persisted ledger.
        Returns ``(run, plan, flat)`` or None; every failure mode
        (checksum, version skew, structure mismatch, failed verify)
        lands in ``disk_corrupt`` + on-disk quarantine."""
        raw, status = store.fetch(dkey)
        if status == "corrupt":
            stats.disk_corrupt += 1
            return None
        if raw is None:
            stats.disk_misses += 1
            return None
        try:
            doc = json.loads(raw.decode())
            if doc.get("schema") != _PLAN_SCHEMA:
                raise _PlanLedgerMismatch("plan payload schema skew")
            ledger = _PlanLedger(entries=doc["plans"], policy=pol)
            run, plan, flat = _build_runner(
                flat0, policy=pol, donate_leaves=donate_leaves,
                ledger=ledger)
            if not ledger.complete():
                raise _PlanLedgerMismatch("trailing ledger entries")
            if verify_loaded:
                fresh = plan_offload(
                    flat, policy=pol,
                    donate_invars=frozenset(flat.jaxpr.invars[i]
                                            for i in donate_leaves))
                if _plan_structure(fresh) != _plan_structure(plan):
                    raise _PlanLedgerMismatch("verify-on-load mismatch")
            stats.disk_hits += 1
            return run, plan, flat
        except Exception as e:  # counted fallback, never an exception
            stats.disk_corrupt += 1
            store.quarantine(dkey, f"{type(e).__name__}: {e}")
            return None

    def compile_for(pol: OffloadPolicy, args,
                    count: bool = True) -> _CompiledOffload:
        # one trace serves both the jaxpr and the output tree
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        donate_leaves = _donate_leaf_indices(args, donate)
        out_tree = jax.tree.structure(out_shape)
        # the persistent plan cache (count=False introspection probes
        # leave the store untouched, like the in-memory LRU).  While the
        # guard is degraded for this impl the store is bypassed both
        # ways: a quarantined kernel must never be served from disk, and
        # a degraded (all_far-coerced) plan must never be persisted.
        store = persist_store() if count else None
        degraded = kernel_guard().degraded_for(pol.impl)
        built = None
        dkey = None
        ledger = None
        if store is not None and not degraded:
            flat0 = _flatten_calls(closed)
            dkey = store.key_for("plan", "fwd", repr(pol),
                                 repr(tuple(donate_leaves)),
                                 _jaxpr_fingerprint(flat0))
            built = try_disk_load(store, dkey, flat0, pol, donate_leaves)
            if built is None:
                ledger = _PlanLedger()
        if built is None:
            if count:
                stats.plan_misses += 1
            run, plan, flat = _build_runner(
                closed, policy=pol, donate_leaves=donate_leaves,
                ledger=ledger)
            if verify_plans:
                _enforce_verified(plan)   # before persisting: the
                                          # "verified" marker is honest
            if ledger is not None and ledger.entries is not None and \
                    dkey is not None:
                payload = json.dumps({"schema": _PLAN_SCHEMA,
                                      "plans": ledger.entries}).encode()
                evicted = store.put(dkey, payload,
                                    meta={"direction": "fwd",
                                          "policy": repr(pol),
                                          "verified": bool(verify_plans)})
                if evicted > 0:
                    stats.disk_evictions += evicted
        else:
            run, plan, flat = built
            if verify_plans:
                # disk-loaded plans are re-verified too: the persisted
                # payload may predate the verifier (or carry
                # verified=False meta) and reconstruction trusts it
                _enforce_verified(plan)
        consts = tuple(flat.consts)

        def flat_runner(*flat_args):
            stats.traces += 1  # counted once per (re)trace, not per call
            return run(consts, flat_args)

        executable = jax.jit(flat_runner,
                             donate_argnums=tuple(donate_leaves))
        return _CompiledOffload(plan, executable, out_tree, closed,
                                run, flat)

    def entry_for(args, count: bool = True) -> tuple[_CompiledOffload, list]:
        """``count=False`` is the introspection path (plan_for/rewritten/
        explain): it may compile a transient entry, but never mutates the
        LRU (no insertion, no eviction, no recency bump) or the health
        counters — probing a novel shape must not evict a hot compiled
        plan."""
        sync_guard(count)
        pol = effective_policy()
        leaves, in_tree = jax.tree.flatten(args)
        # policy- and direction-tagged: the same avals under a different
        # policy are a different plan (miss, not a stale hit), and
        # backward (cotangent) plans live in their own "bwd"-keyed caches
        # (see _segment_bwd_runner) so they can never collide with or
        # evict a forward plan
        key = ("fwd", pol, in_tree,
               tuple(_leaf_signature(l) for l in leaves))
        entry = cache.get(key)
        if entry is None:
            if not count:
                return compile_for(pol, args, count=False), leaves
            # a disk hit inside compile_for reconstructs the plan with
            # zero fresh planning and counts disk_hits INSTEAD of
            # plan_misses — a warm restart replans nothing
            entry = cache[key] = compile_for(pol, args)
            while len(cache) > cache_bound:
                cache.popitem(last=False)
                stats.evictions += 1
        elif count:
            cache.move_to_end(key)
            stats.plan_hits += 1
        return entry, leaves

    def wrapped(*args):
        entry, leaves = entry_for(args)
        flat = entry.executable(*leaves)
        return jax.tree.unflatten(entry.out_tree, flat)

    wrapped.stats = stats
    wrapped.policy = policy
    wrapped.plan_for = lambda *args: entry_for(args, count=False)[0].plan
    wrapped.verify = lambda *args: \
        entry_for(args, count=False)[0].plan.verify()
    wrapped.explain = lambda *args: \
        entry_for(args, count=False)[0].plan.report()
    wrapped.rewritten = lambda *args: \
        entry_for(args, count=False)[0].restage()
    wrapped.cache_clear = cache.clear
    wrapped.cache_size = lambda: len(cache)
    return wrapped


def offload_report(fn: Callable, *args,
                   policy: OffloadPolicy | None = None,
                   bulk_threshold: int | None = None,
                   min_segment: int | None = None,
                   donate_argnums: int | Sequence[int] = ()) -> OffloadPlan:
    """Trace + plan only (no rewrite, no execution): the OffloadPlan for
    ``fn(*args)`` under ``policy`` — the paper's TSV-style traffic
    accounting plus the per-candidate decision list."""
    closed = _flatten_calls(jax.make_jaxpr(fn)(*args))
    donate_leaves = _donate_leaf_indices(args, _normalize_donate(
        donate_argnums))
    donate_invars = frozenset(closed.jaxpr.invars[i] for i in donate_leaves)
    return plan_offload(closed, policy=policy,
                        bulk_threshold=bulk_threshold,
                        min_segment=min_segment,
                        donate_invars=donate_invars)


def offload_explain(fn: Callable, *args,
                    policy: OffloadPolicy | None = None,
                    donate_argnums: int | Sequence[int] = ()
                    ) -> DecisionReport:
    """The decision report for ``fn(*args)`` without wrapping: what
    ``mpu_offload(fn, policy=...).explain(*args)`` would return."""
    return offload_report(fn, *args, policy=policy,
                          donate_argnums=donate_argnums).report()
