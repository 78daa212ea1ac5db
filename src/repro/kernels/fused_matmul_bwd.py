"""Grad-time matmul-anchored segments — the backward contraction kernels.

The forward anchored kernel (repro.kernels.fused_matmul) covers the
x[M,K] @ w[K,N] form.  Training spends most of its FLOPs and HBM bytes
on the two *transposed* grad-time forms, which near-bank designs must
map with per-bank accumulators (the MPU §IV-B1 offload decision applied
to the backward dataflow):

  dGRAD_LHS   dx[M,K] = g[M,N] @ w[K,N]^T
      Same (row_blocks, c_blocks) grid as the forward kernel, but the
      [K,N] weight is read COLUMN-MAJOR via its own block index map —
      blocks walk the contraction (N) axis on the weight's lane axis, so
      no transposed copy of w is ever materialized.  The elementwise
      prologue (cotangent scales/casts) applies per g tile, the epilogue
      (the previous layer's activation backward) applies to the [rb, K]
      accumulator before its single store.

  dGRAD_RHS   dw[K,N] = x[M,K]^T @ g[M,N]
      (k_rows, n_blocks, m_blocks) grid with the M (row) contraction
      INNERMOST, accumulating into an f32 [Kb, Nb] VMEM scratch — the
      per-bank-accumulator mapping of a reduction over rows.  Both
      operands stream contraction-major ([mb, kb] / [mb, nb] tiles); the
      epilogue (weight decay, grad-accumulation adds) applies to the
      finished [Kb, Nb] accumulator in-registers.

Both kernels honor the forward kernel's VMEM accumulator budget
(`fused_matmul._ACC_VMEM_BYTES`) by shrinking their block extents, and
both export grid-count helpers (`matmul_row_blocks` is reused for dlhs;
`drhs_grid_blocks` here) that the offload planner's ``Segment.io_bytes``
and the roofline walker share — kernel, planner, and roofline always
agree on the modeled HBM traffic.

Block sizes are divisors of the extents (exact tiling, no padding), so
segment-boundary donation on dead epilogue operands always holds.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_elementwise import (
    block_specs,
    read_block,
    row_picks,
)
from repro.kernels.fused_matmul import (
    _block_budget,
    _epi_views,
    _row_block,
    contraction_block,
)
from repro.kernels.tiling import LANE, VMEM_LIMIT_BYTES, tiled_divisor

# dx = g @ wT contracts lhs lane with RHS LANE (dim 1 of the [K,N]
# weight): the column-major read of the forward weight.
_DLHS_DIMS = (((1,), (1,)), ((), ()))
# dw = xT @ g contracts the ROW (dim 0) axis of both streamed tiles.
_DRHS_DIMS = (((0,), (0,)), ((), ()))


def _dlhs_kernel(*refs, pro_fn: Callable, epi_fn: Callable, n_lhs: int,
                 epi_picks: Sequence, acc_dtype):
    n_epi = len(epi_picks)
    acc_ref = refs[-1]
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = pro_fn(*[r[...] for r in refs[:n_lhs]])
    w = refs[n_lhs][...]                     # [n_dim, ck] column-major blk
    acc_ref[...] += jax.lax.dot_general(
        g, w, _DLHS_DIMS, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        h = acc_ref[...].astype(acc_dtype)
        epi_vals = [read_block(r, p) for r, p in zip(
            refs[n_lhs + 1:n_lhs + 1 + n_epi], epi_picks)]
        outs = epi_fn(h, *epi_vals)
        for o_ref, o in zip(refs[n_lhs + 1 + n_epi:-1], outs):
            o_ref[...] = o.astype(o_ref.dtype)


def dlhs_layout(rows: int, k_dim: int, n_dim: int,
                lhs_specs: Sequence[tuple], epi_specs: Sequence[tuple],
                out_cols: Sequence[int], *, batch: int = 1,
                vmem_bytes: int | None = None, rows_block: int = 512,
                k_block: int = 512):
    """The dlhs kernel's geometry: ``(rb, ck, ins, outs)`` with one
    ``(view, block, index_map)`` per operand (lhs side, the [n, k]
    weight, epilogue) and per output."""
    rb = _row_block(rows, epi_specs, rows_block, n_dim, vmem_bytes, batch)
    ck = contraction_block(k_dim, n_dim, k_block, vmem_bytes)
    q_steps = (rows // batch) // rb       # row blocks per batch slice
    ins = []
    for spec in lhs_specs:
        c = spec[2]
        if spec[0] == "param_k":
            if c == k_dim:
                ins.append(((1, c), (1, ck), lambda i, k: (0, k)))
            else:               # [1, 1] scalar param
                ins.append(((1, c), (1, c), lambda i, k: (0, 0)))
        else:                   # bulk_k: the [rows, k_dim] cotangent
            ins.append(((rows, k_dim), (rb, ck), lambda i, k: (i, k)))
    if batch > 1:
        ins.append(((batch * n_dim, k_dim), (n_dim, ck),
                    lambda i, k, q=q_steps: (i // q, k)))
    else:
        ins.append(((n_dim, k_dim), (n_dim, ck), lambda i, k: (0, k)))
    ins += _epi_views(epi_specs, rows, rb)
    outs = [((rows, c), (rb, c), lambda i, k: (i, 0)) for c in out_cols]
    return rb, ck, ins, outs


def fused_matmul_dlhs_segment(
    pro_fn: Callable,
    epi_fn: Callable,
    lhs_operands: Sequence[jnp.ndarray],
    lhs_specs: Sequence[tuple[str, int, int]],
    rhs: jnp.ndarray,
    epi_operands: Sequence[jnp.ndarray],
    epi_specs: Sequence[tuple[str, int, int]],
    *,
    rows: int,
    k_dim: int,
    n_dim: int,
    acc_dtype,
    out_cols: Sequence[int],
    out_dtypes: Sequence,
    donate: Sequence[tuple[int, int]] = (),
    rows_block: int = 512,
    k_block: int = 512,
    batch: int = 1,
    vmem_bytes: int | None = None,
    interpret: bool = False,
) -> tuple:
    """One fused launch for a dGRAD_LHS-anchored segment.

    ``rhs`` is the FORWARD [n_dim, k_dim] weight (n_dim == the output
    lane width K_fwd, k_dim == the contraction extent N_fwd); it is
    never transposed in HBM — each grid step reads the [n_dim, ck]
    column block and contracts it lane-against-lane on the MXU.
    Everything else (prologue per lhs tile, epilogue on the accumulator,
    donation on dead epilogue operands) mirrors the forward kernel.

    ``batch`` > 1 admits leading batch dims on BOTH operands (attention
    QK^T is this form per batch slice): ``rows`` spans all batches, row
    blocks never straddle a batch slice, and the rhs — viewed
    [batch * n_dim, k_dim] — streams its own slice per row block.
    """
    rb, ck, in_views, out_views = dlhs_layout(
        rows, k_dim, n_dim, lhs_specs, epi_specs, out_cols, batch=batch,
        vmem_bytes=vmem_bytes, rows_block=rows_block, k_block=k_block)
    grid = (rows // rb, k_dim // ck)
    operands = (*lhs_operands, rhs, *epi_operands)
    ops2, in_specs, out_shape, out_specs = block_specs(
        operands, in_views, out_views, out_dtypes)
    aliases = {len(lhs_operands) + 1 + bi: oi for bi, oi in donate}

    outs = pl.pallas_call(
        functools.partial(
            _dlhs_kernel,
            pro_fn=functools.partial(pro_fn, block_rows=rb),
            epi_fn=functools.partial(epi_fn, block_rows=rb),
            n_lhs=len(lhs_operands),
            epi_picks=row_picks(epi_specs, rows, rb),
            acc_dtype=acc_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rb, n_dim), jnp.float32)],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="fused_matmul_dlhs",
        interpret=interpret,
    )(*ops2)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    return tuple(outs)


# ---------------------------------------------------------------------------
# dGRAD_RHS
# ---------------------------------------------------------------------------

def drhs_blocks(rows: int, n_dim: int, rows_block: int = 512,
                n_block: int = 512,
                vmem_bytes: int | None = None,
                batch: int = 1) -> tuple[int, int]:
    """(row_block, n_block) extents of the drhs kernel: the lane block is
    fixed first, then the row block shrinks so the f32 [Kb, Nb] scratch
    stays within the shared VMEM accumulator budget.  With ``batch`` > 1
    the row block divides the PER-BATCH row extent so no output tile
    straddles a batch slice."""
    per = rows // batch
    nb = tiled_divisor(n_dim, max(min(n_block, n_dim), 1), full=n_dim,
                       aligns=(LANE,))
    # the row block is the lane axis of the contraction-major lhs view
    pb = tiled_divisor(
        per, max(min(_block_budget(rows_block, nb, vmem_bytes), per), 1),
        full=per, aligns=(LANE,))
    return pb, nb


def drhs_m_block(m_dim: int, batch: int = 1, m_block: int = 512) -> int:
    """Contraction (M) block extent of the drhs kernel: the sublane axis
    of both streamed operands."""
    return tiled_divisor(m_dim, max(min(m_block, m_dim), 1),
                         full=batch * m_dim)


def drhs_grid_blocks(rows: int, n_dim: int, rows_block: int = 512,
                     n_block: int = 512,
                     vmem_bytes: int | None = None,
                     batch: int = 1) -> tuple[int, int]:
    """(row_blocks, n_blocks) of the drhs kernel grid.  The [M, K] lhs is
    re-streamed once per n block and the [M, N] rhs once per PER-BATCH
    row block; the offload planner's ``Segment.io_bytes`` uses this same
    computation so the modeled bytes match what the kernel actually
    reads."""
    pb, nb = drhs_blocks(rows, n_dim, rows_block, n_block, vmem_bytes,
                         batch)
    return (rows // batch) // pb, n_dim // nb


def _drhs_kernel(*refs, epi_fn: Callable, n_epi: int, acc_dtype):
    acc_ref = refs[-1]
    mi = pl.program_id(2)
    nm = pl.num_programs(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xt = refs[0][...]                        # [mb, pb] contraction-major
    g = refs[1][...]                         # [mb, nb]
    acc_ref[...] += jax.lax.dot_general(
        xt, g, _DRHS_DIMS, preferred_element_type=jnp.float32)

    @pl.when(mi == nm - 1)
    def _store():
        h = acc_ref[...].astype(acc_dtype)
        epi_vals = [r[...] for r in refs[2:2 + n_epi]]
        outs = epi_fn(h, *epi_vals)
        for o_ref, o in zip(refs[2 + n_epi:-1], outs):
            o_ref[...] = o.astype(o_ref.dtype)


def drhs_layout(m_dim: int, rows: int, n_dim: int,
                epi_specs: Sequence[tuple], out_cols: Sequence[int], *,
                batch: int = 1, vmem_bytes: int | None = None,
                rows_block: int = 512, n_block: int = 512,
                m_block: int = 512):
    """The drhs kernel's geometry: ``(pb, nb, mb, ins, outs)`` with one
    ``(view, block, index_map)`` per operand (activation, cotangent,
    epilogue) and per output."""
    pb, nb = drhs_blocks(rows, n_dim, rows_block, n_block, vmem_bytes,
                         batch)
    mb = drhs_m_block(m_dim, batch, m_block)
    q_steps = (rows // batch) // pb       # row blocks per batch slice
    m_rows = m_dim // mb                  # m blocks per batch slice
    lhs_view = (batch * m_dim, rows // batch)
    rhs_view = (batch * m_dim, n_dim)
    if batch > 1:
        ins = [(lhs_view, (mb, pb),
                lambda i, j, m, q=q_steps, mr=m_rows:
                ((i // q) * mr + m, i % q)),
               (rhs_view, (mb, nb),
                lambda i, j, m, q=q_steps, mr=m_rows:
                ((i // q) * mr + m, j))]
    else:
        ins = [(lhs_view, (mb, pb), lambda i, j, m: (m, i)),
               (rhs_view, (mb, nb), lambda i, j, m: (m, j))]
    for spec in epi_specs:
        role, c = spec[0], spec[2]
        if role == "param":
            if c == n_dim:
                ins.append(((1, c), (1, nb), lambda i, j, m: (0, j)))
            else:               # [1, 1] scalar param
                ins.append(((1, c), (1, c), lambda i, j, m: (0, 0)))
        elif c == n_dim:        # bulk [rows, n_dim]
            ins.append(((rows, c), (pb, nb), lambda i, j, m: (i, j)))
        else:                   # bulk [rows, 1] column
            ins.append(((rows, c), (pb, c), lambda i, j, m: (i, 0)))
    outs = [((rows, c), (pb, nb), lambda i, j, m: (i, j)) for c in out_cols]
    return pb, nb, mb, ins, outs


def fused_matmul_drhs_segment(
    epi_fn: Callable,
    lhs: jnp.ndarray,
    rhs: jnp.ndarray,
    epi_operands: Sequence[jnp.ndarray],
    epi_specs: Sequence[tuple[str, int, int]],
    *,
    m_dim: int,
    rows: int,
    n_dim: int,
    acc_dtype,
    out_cols: Sequence[int],
    out_dtypes: Sequence,
    donate: Sequence[tuple[int, int]] = (),
    rows_block: int = 512,
    n_block: int = 512,
    m_block: int = 512,
    batch: int = 1,
    vmem_bytes: int | None = None,
    interpret: bool = False,
) -> tuple:
    """One fused launch for a dGRAD_RHS-anchored segment.

    ``lhs`` is the [m_dim, rows] forward activation (contraction-major:
    its ROWS are contracted), ``rhs`` the [m_dim, n_dim] cotangent; the
    output is the [rows, n_dim] weight gradient.  The grid iterates
    (k_rows, n_blocks, m_blocks) with M innermost so each (Kb, Nb)
    output tile accumulates its whole row reduction in the f32 VMEM
    scratch before the epilogue + single store.  Epilogue operands are
    lane-blocked too ((pb, nb) tiles at (i, j)); the planner restricts
    drhs epilogues to pure elementwise eqns so no lane statistic is ever
    needed across an (i, j) tile boundary.

    ``batch`` > 1 admits leading batch dims on BOTH operands: lhs and
    rhs are viewed [batch * m_dim, ·], ``rows`` spans all batches'
    output rows, and the row-block index selects the owning batch's
    m-row range so each output tile reduces ONLY its own slice.
    """
    pb, nb, mb, in_views, out_views = drhs_layout(
        m_dim, rows, n_dim, epi_specs, out_cols, batch=batch,
        vmem_bytes=vmem_bytes, rows_block=rows_block, n_block=n_block,
        m_block=m_block)
    grid = (rows // pb, n_dim // nb, m_dim // mb)
    operands = (lhs, rhs, *epi_operands)
    ops2, in_specs, out_shape, out_specs = block_specs(
        operands, in_views, out_views, out_dtypes)
    aliases = {2 + bi: oi for bi, oi in donate}

    outs = pl.pallas_call(
        functools.partial(
            _drhs_kernel,
            epi_fn=functools.partial(epi_fn, block_rows=pb),
            n_epi=len(epi_operands),
            acc_dtype=acc_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((pb, nb), jnp.float32)],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="fused_matmul_drhs",
        interpret=interpret,
    )(*ops2)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    return tuple(outs)
