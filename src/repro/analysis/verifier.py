"""Static plan verifier — proves offload-plan safety without executing.

The offload rewriter (``repro.core.offload``) emits donation aliases,
N-D block index maps, flash segments, and persisted plans; every safety
rule it relies on (the k-axis re-read race that forbids aliasing a
contraction stream, the accumulator VMEM clamp, far-prim exclusion)
lives as inline guards in the PLANNER.  This module is the independent
checker — MPU's compilation flow (§V) runs a verifying backend before
offloading instructions near-bank, and this is that pass over our
plans:

  1. **alias safety** — every ``input_output_aliases`` target is dead
     after its aliased write; the dlhs/drhs k-axis race is detected
     *structurally* (a write-then-read hazard on the kernel's grid
     schedule) rather than by the planner's "never donate lhs/rhs" rule.
  2. **index-map coverage / bounds** — per kernel form the grid is
     enumerated symbolically: every output block written exactly once,
     every operand block view (including ``_bcast_row_index`` branches)
     in-bounds against the operand's actual aval.
  3. **VMEM legality** — the f32 accumulator obeys the policy budget and
     the whole per-step block footprint is sized against the physical
     VMEM capacity, using the EXACT block extents the kernels pick
     (the block-selection helpers are imported from the kernels, not
     re-implemented); and every block meets the TPU tiling rule
     (``repro.kernels.tiling``) the Mosaic compiler enforces.
  4. **well-formedness** — no FAR_PRIMS inside near segments, spans
     consistent, the ``decisions`` table in agreement with the emitted
     segments, persisted-plan fingerprints re-verifiable.

Findings are data (``Finding``), never exceptions; callers that want to
fail hard use ``PlanVerificationError`` on ``has_errors`` findings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Sequence

import numpy as np
from jax.extend import core as jcore

from repro.core import prims
from repro.core.offload import (
    MatmulAnchor,
    OffloadPlan,
    OperandSpec,
    Segment,
    _jaxpr_fingerprint,
)
from repro.kernels.fused_elementwise import (
    REP_SPAN,
    _bcast_row_index,
    row_view,
    segment_row_block,
)
from repro.kernels.fused_matmul import (
    _ACC_VMEM_BYTES,
    _row_block,
    contraction_block,
)
from repro.kernels.fused_matmul_bwd import drhs_blocks, drhs_m_block
from repro.kernels.tiling import VMEM_LIMIT_BYTES

SEVERITIES = ("info", "warning", "error")

# The scoped VMEM the kernels ask Mosaic for, which the whole per-step
# footprint (operand blocks + accumulator scratch + output blocks) is
# sized against.  The policy's ``vmem_budget`` only clamps the
# ACCUMULATOR (an error to exceed — the kernel's row-block floor of 8
# can genuinely overflow a small budget); the footprint rule is
# advisory (warning) because the elementwise grid intentionally does not
# lane-block wide operands (e.g. a [rows, vocab] softmax segment keeps
# whole rows resident).
VMEM_CAPACITY_BYTES = VMEM_LIMIT_BYTES

# full grid enumeration cap; larger grids are edge-sampled
_ENUM_CAP = 1 << 15


@dataclasses.dataclass(frozen=True)
class Finding:
    """One verification finding.

    ``rule`` is a stable identifier (see docs/analysis.md for the
    catalog), ``severity`` one of ``SEVERITIES``, ``segment`` the index
    into ``plan.segments`` (-1 for plan-level findings), ``detail`` a
    human-readable explanation."""

    rule: str
    severity: str
    segment: int
    detail: str

    def __str__(self) -> str:
        where = f"seg {self.segment}" if self.segment >= 0 else "plan"
        return f"[{self.severity}] {self.rule} ({where}): {self.detail}"


class PlanVerificationError(RuntimeError):
    """Raised by enforcing callers (``mpu_offload(verify_plans=True)``)
    when a plan carries error-severity findings."""

    def __init__(self, findings: Sequence[Finding]):
        self.findings = list(findings)
        super().__init__(
            "offload plan failed verification:\n  "
            + "\n  ".join(str(f) for f in self.findings))


def max_severity(findings: Iterable[Finding]) -> str | None:
    worst = None
    for f in findings:
        if worst is None or SEVERITIES.index(f.severity) > \
                SEVERITIES.index(worst):
            worst = f.severity
    return worst


def has_errors(findings: Iterable[Finding]) -> bool:
    return any(f.severity == "error" for f in findings)


# ---------------------------------------------------------------------------
# small helpers over jaxpr structure
# ---------------------------------------------------------------------------

def _aval_size(v) -> int:
    return int(getattr(v.aval, "size", 0))


def _itemsize(v) -> int:
    return int(np.dtype(v.aval.dtype).itemsize)


def _consumers(jaxpr) -> dict[Any, list[int]]:
    out: dict[Any, list[int]] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jcore.Literal):
                out.setdefault(v, []).append(i)
    return out


def _mm_stream_vars(mm: MatmulAnchor) -> set:
    """Vars the contraction side of an anchored kernel streams across
    grid steps (re-read after output blocks are written)."""
    return {mm.rhs, *(sp.var for sp in mm.lhs_specs),
            *(sp.var for sp in mm.rhs_specs)}


def _grid_range(n: int, cap: int) -> list[int]:
    """Indices to evaluate an index map at: the full range when small,
    otherwise the edges plus an interior stride sample."""
    if n <= cap:
        return list(range(n))
    edge = list(range(64)) + list(range(n - 64, n))
    step = max(n // cap, 1)
    return sorted(set(edge + list(range(0, n, step))))


# ---------------------------------------------------------------------------
# alias safety
# ---------------------------------------------------------------------------

def _flat_interval(row_lo: int, row_hi: int, view_cols: int
                   ) -> tuple[int, int]:
    """Bounding flat-element interval of a row range in a 2-D view.
    Over-approximates partial-width blocks to full width — safe for
    hazard detection (may only add overlap, never miss it)."""
    return row_lo * view_cols, row_hi * view_cols


def _stream_race(seg: Segment, sp: OperandSpec, oi: int) -> str | None:
    """Structural write-then-read hazard for donating a contraction
    stream: enumerate the kernel's grid schedule (last axis innermost /
    sequential), place each output-block write at its final contraction
    step, and look for any read of the donated buffer at a strictly
    later step that overlaps the written flat-element region.  This is
    the k-axis race the planner forbids by name — here it is *derived*
    from the schedule, so a corrupted plan smuggling a stream into the
    donation list is caught for the actual reason."""
    mm = seg.matmul
    rows, batch, n = seg.rows, mm.batch, mm.n
    vmem = seg.vmem_bytes
    epi_meta = [s.meta for s in seg.operand_specs]
    out_cols = seg.out_cols[oi]

    if mm.flash is not None:
        return None          # flash dispatch drops donations entirely

    is_rhs = sp.var is mm.rhs or \
        any(sp.var is s.var and s.role == "bulk_w" for s in mm.rhs_specs)
    is_lhs = any(sp.var is s.var and s.role != "param_k"
                 for s in mm.lhs_specs)
    if not (is_rhs or is_lhs):
        return None          # epilogue operand: reads ride the write step

    writes: list[tuple[int, int, int]] = []   # (t, flat_lo, flat_hi)
    reads: list[tuple[int, int, int]] = []

    if mm.form in ("fwd", "dlhs"):
        rb = _row_block(rows, epi_meta, 512, n, vmem, batch)
        kd = mm.k
        kb = contraction_block(kd, n, vmem_bytes=vmem)
        if rows % rb or kd % kb:
            return None      # geometry broken: bounds rules report it
        R, K = rows // rb, kd // kb
        q = max((rows // batch) // rb, 1)
        for i in _grid_range(R, 256):
            t = i * K + (K - 1)
            writes.append((t, *_flat_interval(i * rb, (i + 1) * rb, n)))
            for k in _grid_range(K, 64):
                tk = i * K + k
                if is_rhs and mm.form == "fwd":
                    nk = K
                    base = ((i // q) * nk + k) * kb if batch > 1 else k * kb
                    reads.append((tk, *_flat_interval(base, base + kb, n)))
                elif is_rhs:      # dlhs streams the full [n, k] slice
                    base = (i // q) * n if batch > 1 else 0
                    reads.append((tk, *_flat_interval(base, base + n, kd)))
                elif is_lhs:      # bulk_k rides the output row block
                    reads.append((tk, *_flat_interval(i * rb, (i + 1) * rb,
                                                      kd)))
    elif mm.form == "drhs":
        pb, nb = drhs_blocks(rows, n, vmem_bytes=vmem, batch=batch)
        mb = drhs_m_block(mm.k, batch)
        if rows % pb or n % nb or mm.k % mb:
            return None
        R, NB, NM = rows // pb, n // nb, mm.k // mb
        q = max((rows // batch) // pb, 1)
        mr = mm.k // mb
        for i in _grid_range(R, 64):
            for j in _grid_range(NB, 16):
                t = (i * NB + j) * NM + (NM - 1)
                writes.append((t, *_flat_interval(i * pb, (i + 1) * pb, n)))
                for m in _grid_range(NM, 16):
                    tm = (i * NB + j) * NM + m
                    row = ((i // q) * mr + m) * mb if batch > 1 else m * mb
                    cols = (rows // batch) if is_lhs else n
                    reads.append((tm, *_flat_interval(row, row + mb, cols)))
    else:
        return f"unknown anchor form {mm.form!r}"

    for wt, wlo, whi in writes:
        for rt, rlo, rhi in reads:
            if rt > wt and rlo < whi and wlo < rhi:
                return (f"write of output {oi} rows at grid step {wt} is "
                        f"re-read by the {'rhs' if is_rhs else 'lhs'} "
                        f"stream at step {rt} (flat [{rlo}, {rhi}) vs "
                        f"written [{wlo}, {whi}))")
    return None


def _check_aliases(seg: Segment, si: int, consumers, invar_set,
                   outvar_set, constvar_set,
                   findings: list[Finding]) -> None:
    taken: set[int] = set()
    for bi, oi in seg.donations:
        if not (0 <= bi < len(seg.operand_specs)) or \
                not (0 <= oi < len(seg.outputs)):
            findings.append(Finding(
                "alias-index", "error", si,
                f"donation ({bi}, {oi}) out of range "
                f"({len(seg.operand_specs)} operands, "
                f"{len(seg.outputs)} outputs)"))
            continue
        if oi in taken:
            findings.append(Finding(
                "alias-index", "error", si,
                f"output {oi} aliased by more than one operand"))
        taken.add(oi)
        sp = seg.operand_specs[bi]
        if sp.role != "bulk":
            findings.append(Finding(
                "alias-role", "error", si,
                f"donated operand {bi} has role {sp.role!r}; only bulk "
                f"operands own a full [rows, cols] buffer to reuse"))
            continue
        ov = seg.outputs[oi]
        if sp.cols != seg.out_cols[oi] or \
                sp.var.aval.dtype != ov.aval.dtype or \
                _aval_size(sp.var) != _aval_size(ov):
            findings.append(Finding(
                "alias-shape", "error", si,
                f"donated operand {bi} "
                f"[{sp.rows}x{sp.cols} {sp.var.aval.dtype}] does not "
                f"match output {oi} "
                f"[{seg.rows}x{seg.out_cols[oi]} {ov.aval.dtype}]"))
            continue
        if sp.var in outvar_set:
            findings.append(Finding(
                "alias-live", "error", si,
                f"donated operand {bi} is a program output: its buffer "
                f"outlives the segment"))
        if sp.var in constvar_set:
            findings.append(Finding(
                "alias-live", "error", si,
                f"donated operand {bi} is a captured constant"))
        late = [ci for ci in consumers.get(sp.var, ())
                if ci > seg.span_end]
        if late:
            findings.append(Finding(
                "alias-live", "error", si,
                f"donated operand {bi} is still read by eqn(s) "
                f"{late} after the segment span ends at "
                f"{seg.span_end}"))
        if sp.var in invar_set:
            findings.append(Finding(
                "alias-invar", "info", si,
                f"donated operand {bi} is a program input; legal only "
                f"when the caller donated it (donate_argnums)"))
        if seg.matmul is not None and sp.var in _mm_stream_vars(seg.matmul):
            race = _stream_race(seg, sp, oi)
            if race:
                findings.append(Finding("alias-kaxis-race", "error", si,
                                        race))
    if seg.donations and seg.matmul is not None and \
            seg.matmul.flash is not None:
        findings.append(Finding(
            "donation-dropped", "warning", si,
            "flash segments dispatch without input_output_aliases; the "
            "plan's donated-byte accounting assumes these aliases hold"))
    if seg.donations and seg.matmul is None:
        _, pad, keep = segment_row_block(
            seg.rows, [s.meta for s in seg.operand_specs], 512,
            donate=True)
        if not keep:
            findings.append(Finding(
                "donation-dropped", "warning", si,
                f"row padding ({pad} rows) forces the kernel to drop "
                f"this segment's aliases at launch"))


# ---------------------------------------------------------------------------
# index-map coverage / bounds
# ---------------------------------------------------------------------------

def _bcast_reference_row(out_row: int, lead: tuple, out_lead: tuple) -> int:
    """Operand row a broadcast output row reads, by numpy broadcasting
    semantics — the independent reference `_bcast_row_index` must agree
    with."""
    idx = 0
    rem = out_row
    coords = []
    for od in reversed(out_lead):
        coords.append(rem % od)
        rem //= od
    coords.reverse()
    for c, od, pd in zip(coords, out_lead, lead):
        idx = idx * pd + (c if pd != 1 else 0)
    return idx


def _check_epi_spec(sp: OperandSpec, si: int, rows: int, rb: int,
                    n_row_blocks: int, findings: list[Finding]) -> None:
    """Bounds/coverage for one epilogue/elementwise operand spec against
    the row grid the kernel will launch (``n_row_blocks`` blocks of
    ``rb`` rows)."""
    size = _aval_size(sp.var)
    if sp.cols <= 0 or sp.rows <= 0:
        findings.append(Finding(
            "index-bounds", "error", si,
            f"operand {sp.role} view [{sp.rows}x{sp.cols}] is empty"))
        return
    if size != sp.rows * sp.cols:
        findings.append(Finding(
            "index-bounds", "error", si,
            f"operand {sp.role} view [{sp.rows}x{sp.cols}] does not "
            f"tile its aval ({size} elements)"))
        return
    if sp.role == "param":
        if sp.rows != 1:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"param operand must be a [1, cols] view, got "
                f"[{sp.rows}x{sp.cols}]"))
        return
    if sp.role == "bulk":
        if sp.rows != rows:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"bulk operand spans {sp.rows} rows but the segment "
                f"grid covers {rows}"))
        return
    if sp.role == "rep":
        if rows % sp.rows:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"rep operand rows {sp.rows} do not divide segment "
                f"rows {rows}"))
            return
        q = rows // sp.rows
        if q % rb and (rb % q or rb // q > REP_SPAN):
            findings.append(Finding(
                "index-bounds", "error", si,
                f"row block {rb} neither divides the rep repeat factor "
                f"{q} nor spans at most {REP_SPAN} whole repeats"))
            return
        top = ((n_row_blocks - 1) * rb) // q + max(rb // q, 1) - 1
        if top >= sp.rows:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"rep index map reaches row {top} of a {sp.rows}-row "
                f"operand"))
        return
    if sp.role == "tile":
        if sp.rows % rb:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"tile period {sp.rows} is not a multiple of the row "
                f"block {rb}"))
        return
    if sp.role == "bcast":
        lead, out_lead = tuple(sp.lead), tuple(sp.out_lead)
        if len(lead) != len(out_lead) or not out_lead:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"bcast lead ranks differ: {lead} vs {out_lead}"))
            return
        if int(np.prod(out_lead)) != rows or \
                int(np.prod(lead)) != sp.rows:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"bcast leads {lead}->{out_lead} do not multiply out to "
                f"[{sp.rows} -> {rows}] rows"))
            return
        if out_lead[-1] % rb:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"row block {rb} does not divide the innermost out lead "
                f"dim {out_lead[-1]}"))
            return
        brows, fn = _bcast_row_index(lead, out_lead, rb)
        for i in _grid_range(n_row_blocks, _ENUM_CAP):
            bidx = fn(i)
            if bidx < 0 or (bidx + 1) * brows > sp.rows:
                findings.append(Finding(
                    "index-bounds", "error", si,
                    f"bcast index map sends block {i} to operand rows "
                    f"[{bidx * brows}, {(bidx + 1) * brows}) outside "
                    f"[0, {sp.rows})"))
                return
            ref = _bcast_reference_row(i * rb, lead, out_lead)
            if bidx * brows != ref:
                findings.append(Finding(
                    "index-coverage", "error", si,
                    f"bcast index map reads operand row "
                    f"{bidx * brows} for output row {i * rb}; "
                    f"broadcasting semantics require row {ref}"))
                return
        return
    findings.append(Finding(
        "index-bounds", "error", si,
        f"unknown operand role {sp.role!r}"))


def _check_outputs(seg: Segment, si: int, findings: list[Finding],
                   expect_cols: int | None = None) -> None:
    for oi, (v, c) in enumerate(zip(seg.outputs, seg.out_cols)):
        if _aval_size(v) != seg.rows * c:
            findings.append(Finding(
                "index-coverage", "error", si,
                f"output {oi} has {_aval_size(v)} elements; the grid "
                f"writes exactly {seg.rows} x {c}"))
        if expect_cols is not None and c != expect_cols:
            findings.append(Finding(
                "index-coverage", "error", si,
                f"output {oi} is {c} lanes wide but the kernel's "
                f"output tiles span {expect_cols}"))


def _check_matmul_streams(seg: Segment, si: int,
                          findings: list[Finding]) -> None:
    mm = seg.matmul
    rows, batch = seg.rows, mm.batch
    if batch < 1 or rows % batch:
        findings.append(Finding(
            "index-coverage", "error", si,
            f"batch {batch} does not divide segment rows {rows}"))
        return
    if mm.flash is not None:
        bulk_rhs = [s for s in mm.rhs_specs if s.role != "param_w"]
        if len(bulk_rhs) < 2:
            findings.append(Finding(
                "index-bounds", "error", si,
                "flash segment needs streamed K and V operands"))
            return
        kv, vv = bulk_rhs[0].var, bulk_rhs[1].var
        t_dim = mm.flash.get("t_dim", 0)
        if t_dim <= 0:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"flash t_dim {t_dim} must be positive"))
            return
        if _aval_size(kv) != batch * t_dim * mm.k:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"flash K stream has {_aval_size(kv)} elements, "
                f"expected batch*t*head = {batch * t_dim * mm.k}"))
        if _aval_size(vv) != batch * t_dim * mm.n:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"flash V stream has {_aval_size(vv)} elements, "
                f"expected batch*t*n = {batch * t_dim * mm.n}"))
        for s in mm.lhs_specs:
            if s.role != "param_k" and _aval_size(s.var) != rows * mm.k:
                findings.append(Finding(
                    "index-bounds", "error", si,
                    f"flash Q stream has {_aval_size(s.var)} elements, "
                    f"expected rows*head = {rows * mm.k}"))
        return
    if mm.form in ("fwd", "dlhs"):
        for s in mm.lhs_specs:
            if s.role == "param_k":
                if _aval_size(s.var) != s.cols:
                    findings.append(Finding(
                        "index-bounds", "error", si,
                        f"param_k operand has {_aval_size(s.var)} "
                        f"elements, spec says {s.cols}"))
            elif _aval_size(s.var) != rows * mm.k:
                findings.append(Finding(
                    "index-bounds", "error", si,
                    f"bulk_k operand has {_aval_size(s.var)} elements; "
                    f"the [rows, k] view needs {rows} x {mm.k}"))
        if mm.form == "fwd":
            for s in mm.rhs_specs:
                if s.role == "param_w":
                    continue
                if _aval_size(s.var) != batch * mm.k * mm.n:
                    findings.append(Finding(
                        "index-bounds", "error", si,
                        f"bulk_w operand has {_aval_size(s.var)} "
                        f"elements; the [batch*k, n] view needs "
                        f"{batch * mm.k} x {mm.n}"))
        else:   # dlhs reads the weight [batch*n, k]
            if _aval_size(mm.rhs) != batch * mm.n * mm.k:
                findings.append(Finding(
                    "index-bounds", "error", si,
                    f"dlhs rhs has {_aval_size(mm.rhs)} elements; the "
                    f"[batch*n, k] view needs {batch * mm.n} x {mm.k}"))
        return
    if mm.form == "drhs":
        lhs = mm.lhs_specs[0] if mm.lhs_specs else None
        if lhs is None or lhs.role != "bulk_m":
            findings.append(Finding(
                "index-bounds", "error", si,
                "drhs segment needs a bulk_m row source"))
            return
        if _aval_size(lhs.var) != mm.k * rows:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"drhs lhs has {_aval_size(lhs.var)} elements; the "
                f"[batch*m, rows/batch] view needs {mm.k} x {rows}"))
        if _aval_size(mm.rhs) != batch * mm.k * mm.n:
            findings.append(Finding(
                "index-bounds", "error", si,
                f"drhs rhs has {_aval_size(mm.rhs)} elements; the "
                f"[batch*m, n] view needs {batch * mm.k} x {mm.n}"))
        return
    findings.append(Finding(
        "index-bounds", "error", si,
        f"unknown anchor form {mm.form!r}"))


# ---------------------------------------------------------------------------
# VMEM legality
# ---------------------------------------------------------------------------

def _epi_block_bytes(sp: OperandSpec, rows: int, rb: int) -> int:
    """Bytes of the block the kernel fetches for one epilogue operand,
    from the kernel's own ``row_view``."""
    return row_view(sp.meta, rows, rb)[1][0] * sp.cols * _itemsize(sp.var)


def _check_vmem(seg: Segment, si: int, findings: list[Finding]) -> None:
    budget = seg.vmem_bytes if seg.vmem_bytes is not None \
        else _ACC_VMEM_BYTES
    rows = seg.rows
    epi_meta = [s.meta for s in seg.operand_specs]
    mm = seg.matmul
    acc = 0
    blocks = 0
    if mm is None:
        rb, _, _ = segment_row_block(rows, epi_meta, 512,
                                     donate=bool(seg.donations))
        blocks += sum(_epi_block_bytes(s, rows, rb)
                      for s in seg.operand_specs)
        blocks += sum(rb * c * _itemsize(v)
                      for v, c in zip(seg.outputs, seg.out_cols))
    elif mm.flash is not None:
        s_pb = max(rows // mm.batch, 1)
        qb = min(256, s_pb)
        tb = min(256, mm.flash.get("t_dim", 1) or 1)
        acc = qb * mm.n * 4 + 2 * qb * 4          # o/m/l scratch
        blocks += qb * mm.k * 4 + tb * mm.k * 4 + tb * mm.n * 4
        blocks += qb * mm.n * _itemsize(seg.outputs[0])
    elif mm.form == "drhs":
        pb, nb = drhs_blocks(rows, mm.n, vmem_bytes=seg.vmem_bytes,
                             batch=mm.batch)
        mb = drhs_m_block(mm.k, mm.batch)
        acc = pb * nb * 4
        if mm.lhs_specs:
            blocks += mb * pb * _itemsize(mm.lhs_specs[0].var)
        blocks += mb * nb * _itemsize(mm.rhs)
        blocks += sum(_epi_block_bytes(s, rows, pb)
                      for s in seg.operand_specs)
        blocks += sum(pb * nb * _itemsize(v) for v in seg.outputs)
    else:
        rb = _row_block(rows, epi_meta, 512, mm.n, seg.vmem_bytes,
                        mm.batch)
        kd = mm.k
        kb = contraction_block(kd, mm.n, vmem_bytes=seg.vmem_bytes)
        acc = rb * mm.n * 4
        for s in mm.lhs_specs:
            blocks += (kb if s.cols == kd else s.cols) * _itemsize(s.var) \
                * (rb if s.role == "bulk_k" else 1)
        if mm.form == "fwd":
            for s in mm.rhs_specs:
                blocks += (kb * mm.n if s.role != "param_w"
                           else s.cols) * _itemsize(s.var)
        else:
            blocks += mm.n * kb * _itemsize(mm.rhs)
        blocks += sum(_epi_block_bytes(s, rows, rb)
                      for s in seg.operand_specs)
        blocks += sum(rb * c * _itemsize(v)
                      for v, c in zip(seg.outputs, seg.out_cols))
    if acc > VMEM_CAPACITY_BYTES:
        findings.append(Finding(
            "vmem-accumulator", "error", si,
            f"f32 accumulator scratch is {acc} bytes — beyond the "
            f"{VMEM_CAPACITY_BYTES}-byte physical VMEM model; the "
            f"kernel cannot launch (policy budget {budget})"))
    elif acc > budget:
        # the kernels floor their row block at 8 to keep the MXU fed, so
        # very wide N overshoots the soft budget deliberately
        findings.append(Finding(
            "vmem-accumulator", "warning", si,
            f"f32 accumulator scratch is {acc} bytes, over the "
            f"{budget}-byte policy budget (8-row block floor on a "
            f"wide-N contraction)"))
    total = acc + blocks
    if total > VMEM_CAPACITY_BYTES:
        findings.append(Finding(
            "vmem-footprint", "warning", si,
            f"per-step block footprint {total} bytes exceeds the "
            f"{VMEM_CAPACITY_BYTES}-byte VMEM capacity model"))


# ---------------------------------------------------------------------------
# segment well-formedness + decisions drift
# ---------------------------------------------------------------------------

def _check_wellformed(seg: Segment, si: int, jaxpr,
                      findings: list[Finding]) -> None:
    n_eqns = len(jaxpr.eqns)
    for i in seg.all_eqn_idx + list(seg.pre_eqns):
        if not (0 <= i < n_eqns):
            findings.append(Finding(
                "segment-span", "error", si,
                f"eqn index {i} outside the program "
                f"(0..{n_eqns - 1})"))
            return
    lo, hi = seg.span_start, seg.span_end
    if lo > hi or not (0 <= lo <= hi < n_eqns):
        findings.append(Finding(
            "segment-span", "error", si,
            f"span [{lo}, {hi}] is not a valid eqn range"))
        return
    anchor_eqns = set()
    absorbed = set()
    if seg.matmul is not None:
        anchor_eqns.add(seg.matmul.eqn_idx)
        if seg.matmul.flash is not None:
            anchor_eqns.add(seg.matmul.flash["eqn_idx"])
        # extra_eqns are far-by-opcode eqns the anchor absorbs BY DESIGN
        # (the adjacent transpose of a drhs product, jax's grad emission
        # order); they are span-checked but tier-exempt
        absorbed.update(seg.matmul.extra_eqns)
    for i in seg.all_eqn_idx:
        if not (lo <= i <= hi):
            findings.append(Finding(
                "segment-span", "error", si,
                f"fused eqn {i} lies outside the segment span "
                f"[{lo}, {hi}]"))
        name = jaxpr.eqns[i].primitive.name
        tier = prims.eqn_tier(name)
        if i in anchor_eqns:
            if tier != "anchor":
                findings.append(Finding(
                    "far-prim-in-segment", "error", si,
                    f"anchor eqn {i} is {name!r} (tier {tier}), not a "
                    f"contraction"))
        elif tier not in ("near", "layout", "reduce") and \
                i not in absorbed:
            findings.append(Finding(
                "far-prim-in-segment", "error", si,
                f"eqn {i} ({name!r}) is tier {tier!r}; only "
                f"near/layout/reduce prims may fuse into a segment"))


def decision_statuses(plan: OffloadPlan) -> list[str]:
    """Cross-check the plan's decision rows against its emitted
    segments: one status string per decision ("ok", "-" for declines,
    "MISMATCH(...)" / "MISSING-SEGMENT" on drift).  ``explain()`` renders
    these as the ``verified`` column."""
    statuses: list[str] = []
    si = 0
    for d in plan.decisions:
        if not d.fused:
            statuses.append("-")
            continue
        if si >= len(plan.segments):
            statuses.append("MISSING-SEGMENT")
            si += 1
            continue
        seg = plan.segments[si]
        si += 1
        probs = []
        form = None
        if seg.matmul is not None:
            form = "flash" if seg.matmul.flash is not None \
                else seg.matmul.form
        if (d.form or None) != form:
            probs.append(f"form {d.form or '-'} != {form or '-'}")
        if d.rows != seg.rows:
            probs.append(f"rows {d.rows} != {seg.rows}")
        exp_tier = "anchor" if seg.matmul is not None else "elementwise"
        if d.tier != exp_tier:
            probs.append(f"tier {d.tier} != {exp_tier}")
        statuses.append("ok" if not probs
                        else "MISMATCH(" + ", ".join(probs) + ")")
    return statuses


def _check_decisions(plan: OffloadPlan, findings: list[Finding]) -> None:
    statuses = decision_statuses(plan)
    fused = sum(1 for d in plan.decisions if d.fused)
    if fused != len(plan.segments):
        findings.append(Finding(
            "decision-drift", "error", -1,
            f"{fused} fused decision row(s) vs {len(plan.segments)} "
            f"emitted segment(s)"))
    seg_i = -1
    for di, (d, st) in enumerate(zip(plan.decisions, statuses)):
        if d.fused:
            seg_i += 1
        if st not in ("ok", "-"):
            findings.append(Finding(
                "decision-drift", "error",
                seg_i if seg_i < len(plan.segments) else -1,
                f"decision row {di}: {st}"))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _verify_segment(seg: Segment, si: int, jaxpr, consumers, invar_set,
                    outvar_set, constvar_set,
                    findings: list[Finding]) -> None:
    _check_wellformed(seg, si, jaxpr, findings)
    _check_aliases(seg, si, consumers, invar_set, outvar_set,
                   constvar_set, findings)
    mm = seg.matmul
    if mm is None:
        rb, pad, _ = segment_row_block(
            seg.rows, [s.meta for s in seg.operand_specs], 512,
            donate=bool(seg.donations))
        n_blocks = (seg.rows + pad) // rb
        for sp in seg.operand_specs:
            _check_epi_spec(sp, si, seg.rows, rb, n_blocks, findings)
        _check_outputs(seg, si, findings)
    else:
        _check_matmul_streams(seg, si, findings)
        if mm.flash is None and mm.form in ("fwd", "dlhs"):
            rb = _row_block(seg.rows, [s.meta for s in seg.operand_specs],
                            512, mm.n, seg.vmem_bytes, mm.batch)
            if seg.rows % rb:
                findings.append(Finding(
                    "index-coverage", "error", si,
                    f"row block {rb} does not tile {seg.rows} rows"))
            else:
                n_blocks = seg.rows // rb
                for sp in seg.operand_specs:
                    _check_epi_spec(sp, si, seg.rows, rb, n_blocks,
                                    findings)
            _check_outputs(seg, si, findings)
        elif mm.flash is None and mm.form == "drhs":
            pb, _ = drhs_blocks(seg.rows, mm.n,
                                vmem_bytes=seg.vmem_bytes,
                                batch=mm.batch)
            for sp in seg.operand_specs:
                if sp.role not in ("param", "bulk"):
                    findings.append(Finding(
                        "index-bounds", "error", si,
                        f"drhs epilogue cannot block a {sp.role!r} "
                        f"operand"))
                    continue
                _check_epi_spec(sp, si, seg.rows, pb, seg.rows // pb,
                                findings)
            _check_outputs(seg, si, findings, expect_cols=mm.n)
        else:
            _check_outputs(seg, si, findings)
    _check_vmem(seg, si, findings)
    for why in seg.tiling_violations():
        findings.append(Finding("tpu-tiling", "error", si, why))


def verify_plan(plan: OffloadPlan, closed=None) -> list[Finding]:
    """Statically verify one offload plan; returns all findings (empty
    when the plan proves out).  ``closed``, when given, is the jaxpr the
    caller is about to execute the plan against — its fingerprint must
    match the plan's own (the persisted-plan integrity check)."""
    findings: list[Finding] = []
    plan_closed = plan.annotation.jaxpr
    if closed is not None:
        try:
            if _jaxpr_fingerprint(closed) != _jaxpr_fingerprint(plan_closed):
                findings.append(Finding(
                    "plan-fingerprint", "error", -1,
                    "plan was built for a different jaxpr than the one "
                    "it is being applied to"))
        except Exception as e:   # fingerprinting must never crash verify
            findings.append(Finding(
                "plan-fingerprint", "warning", -1,
                f"could not fingerprint jaxpr: {e}"))
    jaxpr = plan_closed.jaxpr
    consumers = _consumers(jaxpr)
    invar_set = set(jaxpr.invars)
    outvar_set = {v for v in jaxpr.outvars
                  if not isinstance(v, jcore.Literal)}
    constvar_set = set(jaxpr.constvars)
    for si, seg in enumerate(plan.segments):
        _verify_segment(seg, si, jaxpr, consumers, invar_set,
                        outvar_set, constvar_set, findings)
    _check_decisions(plan, findings)
    for pi, inner in enumerate(plan.inner_plans):
        for f in verify_plan(inner):
            findings.append(dataclasses.replace(
                f, detail=f"inner[{pi}]: {f.detail}"))
    return findings


def verify_paged_decode(block_tables, lengths, *, num_pages: int,
                        page_size: int) -> list[Finding]:
    """Bounds proof for ``paged_decode_attention``'s scalar-prefetched
    gathers.  The K/V BlockSpec index maps (``streamed_page``) run for
    EVERY grid step — including steps the compute mask skips — and read
    table entries up to each row's length, so every table entry must
    name a real page (padding included, as a table's tail is stale),
    and no sequence may claim more KV slots than its table can
    address."""
    findings: list[Finding] = []
    t = np.asarray(block_tables)
    lens = np.asarray(lengths)
    if t.ndim != 2:
        findings.append(Finding(
            "page-table-bounds", "error", -1,
            f"block table must be [batch, n_pages], got shape "
            f"{t.shape}"))
        return findings
    bad = np.argwhere((t < 0) | (t >= num_pages))
    for b, p in bad[:8]:
        findings.append(Finding(
            "page-table-bounds", "error", -1,
            f"table[{b}, {p}] = {int(t[b, p])} outside the "
            f"[0, {num_pages}) page pool — gathered even on masked "
            f"grid steps"))
    if len(bad) > 8:
        findings.append(Finding(
            "page-table-bounds", "error", -1,
            f"... and {len(bad) - 8} more out-of-range table entries"))
    cap = t.shape[1] * page_size
    for b, ln in enumerate(lens.reshape(-1)[: t.shape[0]]):
        if ln < 0 or ln > cap:
            findings.append(Finding(
                "page-length-bounds", "error", -1,
                f"sequence {b} claims {int(ln)} KV positions; its "
                f"table addresses at most {cap}"))
    return findings
