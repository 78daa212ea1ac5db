"""Matmul-anchored near-bank segment — the fused-GEMM-epilogue kernel.

The offload planner (repro.core.offload) anchors a near segment on a
qualifying ``dot_general``: this kernel runs the [rows, K] x [K, N]
contraction over a (row_blocks, k_blocks) grid with an f32 accumulator
in VMEM scratch, applies the elementwise *prologue* to each lhs tile
before its partial product (dtype casts, scales, per-channel dequant)
and the *epilogue* (bias+gelu, swiglu gate/split, residual add,
lane-axis reductions, dtype cast) to the finished accumulator
in-registers before the single store.  The product tensor itself never
round-trips HBM — the flash-attention-style producer/consumer fusion of
the paper's §IV-B1 offload decision applied at the MXU boundary.

Grid: (rows // rows_block, K // k_block), K innermost (sequential);
block sizes are divisors of the extents so no padding is ever needed
and segment-boundary donation (``input_output_aliases`` on dead
epilogue operands) always holds.

Operand roles (see repro.core.offload.OperandSpec):
  * lhs side  — ``bulk_k`` [rows, K] tiles walk (i, k); ``param_k``
                [1, K] vectors walk (0, k) ([1, 1] scalars stay put)
  * rhs side  — ``bulk_w`` [K, N] weight-side operands, streamed (k, 0)
                in their RAW dtype with the weight prologue (bf16/int8
                dequant cast, scales) applied per block in VMEM;
                ``param_w`` scalars stay put
  * epilogue  — the usual ``bulk``/``param``/``rep``/``tile`` row views,
                blocked over rows only (the k axis revisits them)

The two grad-time contraction forms (dx = g @ wT, dw = xT @ g) live in
``repro.kernels.fused_matmul_bwd`` and share this module's VMEM
accumulator budget and block-extent math.
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
    row_block,
    row_picks,
    row_view,
)
from repro.kernels.tiling import LANE, VMEM_LIMIT_BYTES, tiled_divisor


# VMEM budget for the f32 accumulator (and, symmetrically, the rhs
# block): wide-N dots shrink their row/k blocks to stay on-chip instead
# of failing to compile.
_ACC_VMEM_BYTES = 4 * 1024 * 1024


def _block_budget(block: int, n_dim: int,
                  vmem_bytes: int | None = None) -> int:
    """Clamp a row/k block extent so block x n_dim f32 fits the budget
    (``vmem_bytes`` overrides the built-in budget — an
    ``OffloadPolicy.vmem_budget``; planner and kernel pass the same
    value so modeled and actual re-streaming agree)."""
    budget = _ACC_VMEM_BYTES if vmem_bytes is None else vmem_bytes
    return max(min(block, budget // (4 * max(n_dim, 1))), 8)


def _row_block(rows: int, epi_specs: Sequence[tuple],
               rows_block: int, n_dim: int,
               vmem_bytes: int | None = None, batch: int = 1) -> int:
    """Row-block extent: the largest block the epilogue operands admit
    (``row_block``; else a divisor of ``rows``) that fits the
    (VMEM-clamped) block budget — exact tiling, so donation aliases
    always hold.  With ``batch`` > 1 the block must also divide the
    PER-BATCH row extent so every row block sits inside a single batch
    slice of the outer grid."""
    limit = max(min(_block_budget(rows_block, n_dim, vmem_bytes), rows), 1)
    per = rows // batch if batch > 1 else 0
    return row_block(rows, epi_specs, limit, per) or \
        tiled_divisor(rows, limit, full=rows)


def contraction_block(k_dim: int, n_dim: int, block: int = 512,
            vmem_bytes: int | None = None) -> int:
    """Contraction block extent: the largest divisor of ``k_dim`` within
    the VMEM clamp that is lane-aligned (a multiple of 128, or all of
    ``k_dim``) — the k block is the lhs tile's lane axis."""
    limit = max(min(_block_budget(block, n_dim, vmem_bytes), k_dim), 1)
    return tiled_divisor(k_dim, limit, full=k_dim, aligns=(LANE,))


def _epi_views(epi_specs: Sequence[tuple], rows: int, rb: int) -> list:
    """Epilogue operand views with 2-D ``(i, k)`` grid index maps."""
    out = []
    for spec in epi_specs:
        view, block, f = row_view(spec, rows, rb)
        out.append((view, block, lambda i, k, f=f: (f(i), 0)))
    return out


def matmul_layout(rows: int, k_dim: int, n_dim: int,
                  lhs_specs: Sequence[tuple], rhs_specs: Sequence[tuple],
                  epi_specs: Sequence[tuple], out_cols: Sequence[int], *,
                  batch: int = 1, vmem_bytes: int | None = None,
                  rows_block: int = 512, k_block: int = 512):
    """The forward kernel's geometry: ``(rb, rk, ins, outs)`` with one
    ``(view, block, index_map)`` per operand (lhs side, rhs side,
    epilogue — the kernel's argument order) and per output."""
    rb = _row_block(rows, epi_specs, rows_block, n_dim, vmem_bytes, batch)
    rk = contraction_block(k_dim, n_dim, k_block, vmem_bytes)
    q_steps = (rows // batch) // rb       # row blocks per batch slice
    ins = []
    for spec in lhs_specs:
        c = spec[2]
        if spec[0] == "param_k":
            if c == k_dim:
                ins.append(((1, c), (1, rk), lambda i, k: (0, k)))
            else:               # [1, 1] scalar param
                ins.append(((1, c), (1, c), lambda i, k: (0, 0)))
        else:                   # bulk_k
            ins.append(((rows, k_dim), (rb, rk), lambda i, k: (i, k)))
    for spec in rhs_specs:
        c = spec[2]
        if spec[0] == "param_w":
            ins.append(((1, c), (1, c), lambda i, k: (0, 0)))
        elif batch > 1:         # bulk_w slice of the [batch * K, N] view
            ins.append(((batch * k_dim, n_dim), (rk, n_dim),
                        lambda i, k, q=q_steps, nk=k_dim // rk:
                        ((i // q) * nk + k, 0)))
        else:                   # bulk_w: a raw [K, N] weight-side operand
            ins.append(((k_dim, n_dim), (rk, n_dim), lambda i, k: (k, 0)))
    ins += _epi_views(epi_specs, rows, rb)
    outs = [((rows, c), (rb, c), lambda i, k: (i, 0)) for c in out_cols]
    return rb, rk, ins, outs


def matmul_row_blocks(rows: int, epi_specs: Sequence[tuple],
                      n_dim: int, rows_block: int = 512,
                      vmem_bytes: int | None = None,
                      batch: int = 1) -> int:
    """Number of PER-BATCH row blocks the anchored kernel launches.  The
    per-batch [K, N] rhs slice is re-streamed once per row block of that
    slice; the offload planner's traffic accounting multiplies the FULL
    rhs byte count by this value, so it is per batch slice by
    construction.  Planner and kernel share this computation so the
    modeled bytes match what the kernel actually reads."""
    return (rows // batch) // _row_block(rows, epi_specs, rows_block,
                                         n_dim, vmem_bytes, batch)


def _mm_kernel(*refs, pro_fn: Callable, rhs_pro_fn: Callable, n_lhs: int,
               n_rhs: int, epi_fn: Callable, epi_picks: Sequence, acc_dtype):
    n_epi = len(epi_picks)
    acc_ref = refs[-1]
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lhs = pro_fn(*[r[...] for r in refs[:n_lhs]])
    rhs = rhs_pro_fn(*[r[...] for r in refs[n_lhs:n_lhs + n_rhs]])
    acc_ref[...] += jnp.dot(lhs, rhs, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        h = acc_ref[...].astype(acc_dtype)
        epi_vals = [read_block(r, p) for r, p in zip(
            refs[n_lhs + n_rhs:n_lhs + n_rhs + n_epi], epi_picks)]
        outs = epi_fn(h, *epi_vals)
        for o_ref, o in zip(refs[n_lhs + n_rhs + n_epi:-1], outs):
            o_ref[...] = o.astype(o_ref.dtype)


def fused_matmul_segment(
    pro_fn: Callable,
    rhs_pro_fn: Callable,
    epi_fn: Callable,
    lhs_operands: Sequence[jnp.ndarray],
    lhs_specs: Sequence[tuple[str, int, int]],
    rhs_operands: Sequence[jnp.ndarray],
    rhs_specs: Sequence[tuple[str, int, int]],
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
    """One fused launch for an anchored segment.

    ``pro_fn(*lhs_tiles, block_rows)`` maps the lhs-side tiles to one
    [rows_block, k_block] tile; ``rhs_pro_fn(*rhs_blocks, block_rows)``
    maps the weight-side blocks (``bulk_w`` [K, N] operands streamed
    once per row block in their RAW dtype, plus ``param_w`` scalars) to
    one [k_block, N] f32 block — a bf16/int8 dequant cast fused into the
    kernel instead of materializing the cast weight;
    ``epi_fn(acc, *epi_blocks, block_rows)`` maps the [rows_block, N]
    accumulator (+ external epilogue blocks) to one
    [rows_block, out_cols[j]] block per output.  ``donate`` pairs index
    into ``epi_operands`` and become Pallas ``input_output_aliases``
    (offset past the lhs/rhs inputs).

    ``batch`` > 1 generalizes the grid to a batched contraction
    ([B.., M, K] @ [B.., K, N]): ``rows`` is the FULL row extent
    (batch * per-batch M), row blocks never straddle a batch slice, and
    the bulk_w rhs — viewed [batch * K, N] — streams its own batch
    slice's [K, N] once per row block of that slice (the batch axes are
    outer grid positions realized through the block index maps).
    """
    rb, rk, in_views, out_views = matmul_layout(
        rows, k_dim, n_dim, lhs_specs, rhs_specs, epi_specs, out_cols,
        batch=batch, vmem_bytes=vmem_bytes, rows_block=rows_block,
        k_block=k_block)
    grid = (rows // rb, k_dim // rk)
    operands = (*lhs_operands, *rhs_operands, *epi_operands)
    ops2, in_specs, out_shape, out_specs = block_specs(
        operands, in_views, out_views, out_dtypes)
    n_mm = len(lhs_operands) + len(rhs_operands)
    aliases = {n_mm + bi: oi for bi, oi in donate}

    outs = pl.pallas_call(
        functools.partial(
            _mm_kernel,
            pro_fn=functools.partial(pro_fn, block_rows=rb),
            rhs_pro_fn=functools.partial(rhs_pro_fn, block_rows=rb),
            n_lhs=len(lhs_operands),
            n_rhs=len(rhs_operands),
            epi_fn=functools.partial(epi_fn, block_rows=rb),
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
        name="fused_matmul",
        interpret=interpret,
    )(*ops2)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    return tuple(outs)
