"""Generic fused-elementwise Pallas kernel — the offload engine's target.

This is the paper's instruction-offloading mechanism made concrete on
TPU: ``repro.core.offload`` extracts a maximal near-bank subgraph (a
chain/DAG of elementwise "value" instructions, per the Algorithm-1
locator) and executes it here as ONE pass over HBM.  Far-bank execution
(plain XLA, un-fused) would round-trip HBM once per instruction; the
near-bank version reads each operand once, keeps every intermediate in
VMEM (the near-bank register file), and writes each output once.

Operands come in two flavors, mirroring MPU's register classes:
  * bulk   — full [R, C] tensors, tiled over the grid (near-bank values)
  * param  — [C] vectors or scalars, broadcast to every block (the
             equivalent of far-bank registers moved once over the TSVs)
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import VMEM_LIMIT_BYTES, tiled_divisor


def _ew_kernel(*refs, fn: Callable, n_bulk: int, n_param: int, n_out: int):
    ins = refs[: n_bulk + n_param]
    outs = refs[n_bulk + n_param:]
    vals = [r[...] for r in ins]
    res = fn(*vals)
    if not isinstance(res, (tuple, list)):
        res = (res,)
    for o_ref, r in zip(outs, res):
        o_ref[...] = r.astype(o_ref.dtype)


def fused_elementwise(
    fn: Callable,
    bulk: Sequence[jnp.ndarray],
    params: Sequence[jnp.ndarray] = (),
    *,
    out_dtypes: Sequence | None = None,
    n_outputs: int = 1,
    rows_block: int = 512,
    interpret: bool = False,
):
    """Apply ``fn(*bulk_blocks, *param_blocks) -> array | tuple`` in one
    HBM pass.  All ``bulk`` arrays must share one shape [..., C]; ``params``
    are rank-1 [C] or scalars (reshaped to [1] for SMEM-friendliness)."""
    assert bulk, "need at least one bulk operand"
    shape = bulk[0].shape
    c = shape[-1] if len(shape) > 1 else 1
    rows = bulk[0].size // c
    for a in bulk:
        assert a.shape == shape, "bulk operands must share a shape"
    b2 = [a.reshape(rows, c) for a in bulk]
    p2 = [jnp.asarray(p).reshape(-1) for p in params]

    rows_block = min(rows_block, rows)
    pad = (-rows) % rows_block
    if pad:
        b2 = [jnp.pad(a, ((0, pad), (0, 0))) for a in b2]
    grid = ((rows + pad) // rows_block,)

    if out_dtypes is None:
        out_dtypes = [bulk[0].dtype] * n_outputs
    out_shape = [jax.ShapeDtypeStruct((rows + pad, c), dt) for dt in out_dtypes]

    def wrapped(*blocks):
        bulk_blocks = blocks[: len(b2)]
        param_blocks = [
            p if p.shape[0] == c else p[0] for p in blocks[len(b2):]
        ]
        return fn(*bulk_blocks, *param_blocks)

    outs = pl.pallas_call(
        functools.partial(_ew_kernel, fn=wrapped, n_bulk=len(b2),
                          n_param=len(p2), n_out=n_outputs),
        grid=grid,
        in_specs=[pl.BlockSpec((rows_block, c), lambda r: (r, 0))
                  for _ in b2]
                 + [pl.BlockSpec((p.shape[0],), lambda r: (0,)) for p in p2],
        out_specs=[pl.BlockSpec((rows_block, c), lambda r: (r, 0))
                   for _ in out_shape],
        out_shape=out_shape,
        name="fused_elementwise",
        interpret=interpret,
    )(*b2, *p2)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    result = tuple(o[:rows].reshape(shape) for o in outs)
    return result[0] if n_outputs == 1 else result


def _bcast_row_index(op_lead: tuple, out_lead: tuple,
                     rb: int) -> tuple[int, Callable]:
    """Block extent and row-grid index map for an interior-broadcast
    ("bcast") operand — e.g. [B,1,S,1,D] read against [B,H,S,W,D] rows.

    The row-block index ``i`` decomposes over the output's leading dims
    (``rb`` divides ``out_lead[-1]`` by the caller's gcd constraint);
    only the operand's non-broadcast dims contribute to its row index,
    so each distinct operand row is read once per visit instead of the
    broadcast tensor being materialized.  Returns ``(block_rows, fn)``
    where ``fn(i)`` is the operand's block-row index: when the operand's
    innermost lead dim is broadcast the block is a single row (the whole
    ``rb``-row output block maps to one operand row), otherwise the
    block spans ``rb`` operand rows."""
    inner = out_lead[-1] // rb
    if op_lead[-1] == 1:
        def fn(i):
            j = i // inner
            idx = 0
            stride = 1
            for od, pd in zip(reversed(out_lead[:-1]),
                              reversed(op_lead[:-1])):
                d = j % od
                if pd != 1:
                    idx = idx + d * stride
                    stride *= pd
                j = j // od
            return idx
        return 1, fn

    def fn(i):
        j = i // inner
        idx = i % inner
        stride = inner
        for od, pd in zip(reversed(out_lead[:-1]), reversed(op_lead[:-1])):
            d = j % od
            if pd != 1:
                idx = idx + d * stride
                stride *= pd
            j = j // od
        return idx
    return rb, fn


#: most operand rows a ``rep`` operand's block may span: a row block
#: that is a multiple of the repeat factor reads several operand rows,
#: each re-broadcast by one select over the block (``read_block``)
REP_SPAN = 8


def _row_span(spec: tuple, rows: int, rb: int
              ) -> tuple[Callable, int, int] | None:
    """``(start, m, sb)`` for an operand whose ``rb``-row block is made
    of ``m`` consecutive operand rows, each repeated ``rb // m`` times,
    the first being row ``start(i)`` at grid step ``i``: ``rep`` (``rb``
    divides the repeat factor, m = 1, or is a multiple of it) and a
    ``bcast`` whose innermost lead dim is broadcast (m = 1).  None for
    operands read in whole ``rb``-row blocks.

    A block of a few rows of a many-row array breaks the TPU sublane
    tile, so the kernel fetches the operand in ``sb``-row blocks — the
    whole array when it has at most ``max(rb, 16)`` rows, else the
    largest tile-aligned divisor within that holding whole spans — and
    picks the rows in VMEM (``read_block``)."""
    if spec[0] == "rep":
        q = rows // spec[1]
        start, m = (lambda i: (i * rb) // q), max(rb // q, 1)
    elif spec[0] == "bcast":
        brows, start = _bcast_row_index(spec[3], spec[4], rb)
        if brows != 1:
            return None
        m = 1
    else:
        return None
    sb = tiled_divisor(spec[1], max(rb, 16), full=spec[1],
                       admit=lambda d: d % m == 0)
    return start, m, sb


def row_view(spec: tuple, rows: int, rb: int
             ) -> tuple[tuple[int, int], tuple[int, int], Callable]:
    """One operand's 2-D block view on a row grid of ``rb``-row blocks:
    ``(view_shape, block_shape, row_fn)``, where the operand is reshaped
    to ``view_shape`` and grid step ``i`` reads its block at block row
    ``row_fn(i)``.  ``spec`` is a (role, op_rows, cols) triple or an
    interior-broadcast 5-tuple (see repro.core.offload.OperandSpec).
    Operands read a few rows at a time come in ``_row_span`` blocks;
    ``row_picks`` says which rows of the block each step reads."""
    role, op_rows, c = spec[0], spec[1], spec[2]
    if role == "param":
        return (1, c), (1, c), lambda i: 0
    if role == "bulk":
        return (rows, c), (rb, c), lambda i: i
    span = _row_span(spec, rows, rb)
    if span is not None:                  # rep, one-row bcast
        start, _, sb = span
        return (op_rows, c), (sb, c), lambda i: start(i) // sb
    if role == "bcast":                   # interior broadcast
        brows, fn = _bcast_row_index(spec[3], spec[4], rb)
        return (op_rows, c), (brows, c), fn
    p = op_rows // rb                     # tile: rb divides the period
    return (op_rows, c), (rb, c), lambda i: i % p


def row_picks(specs: Sequence[tuple], rows: int, rb: int) -> list:
    """Per operand of a ``row_view`` layout: None when the kernel uses
    its whole block, else ``(pick, m, rb)``: grid step ``i`` reads the
    ``m`` rows of the fetched block from row ``pick(i)`` on, each
    repeated ``rb // m`` times."""
    picks = []
    for spec in specs:
        span = _row_span(spec, rows, rb)
        if span is None:
            picks.append(None)
        else:
            start, m, sb = span
            picks.append((lambda i, start=start, sb=sb: start(i) % sb,
                          m, rb))
    return picks


def _load_row(ref, r):
    """Row ``r`` (traced) of a VMEM block as a [1, cols] value, in 32
    bits for bool and packed dtypes.  A dynamic one-row load needs
    unpacked (32-bit or bool) rows; packed rows are selected by a masked
    sum in 32 bits, which is exact."""
    if ref.dtype == jnp.bool_:
        return ref[pl.ds(r, 1), :].astype(jnp.int32)
    if ref.dtype.itemsize >= 4:
        return ref[pl.ds(r, 1), :]
    blk = ref[...]
    acc = jnp.float32 if jnp.issubdtype(blk.dtype, jnp.floating) \
        else jnp.int32
    hit = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == r
    return jnp.sum(jnp.where(hit, blk, jnp.zeros_like(blk)).astype(acc),
                   axis=0, keepdims=True)


def read_block(ref, pick):
    """An operand's value at this grid step: its whole block, or — for
    a ``row_picks`` entry — its one picked row as a [1, cols] value, or
    its ``m`` picked rows each repeated to fill the [rb, cols] block
    (selected in 32 bits: Mosaic relayouts packed selects badly)."""
    if pick is None:
        return ref[...]
    fn, m, rb = pick
    r = fn(pl.program_id(0))
    out = _load_row(ref, r)
    if m > 1:
        shape = (rb, out.shape[-1])
        which = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // (rb // m)
        out = jnp.broadcast_to(out, shape)
        for j in range(1, m):
            out = jnp.where(which == j, _load_row(ref, r + j), out)
    return out != 0 if ref.dtype == jnp.bool_ else out.astype(ref.dtype)


def row_block(rows: int, specs: Sequence[tuple], limit: int,
              period: int = 0) -> int:
    """The row block every operand's view admits, or 0 when no operand
    constrains it: the largest tile-aligned (``tiled_divisor``) block
    within ``limit`` that divides every tile period, every bcast
    operand's innermost output lead dim and ``period``, and for each
    rep operand either divides its repeat factor or spans at most
    ``REP_SPAN`` whole repeats of it.  Shared by the row-grid and the
    anchored kernels, and the static verifier re-derives it."""
    g, reps = period, []
    for spec in specs:
        if spec[0] == "rep":
            reps.append(rows // spec[1])
        elif spec[0] == "tile":
            g = math.gcd(g, spec[1])
        elif spec[0] == "bcast":
            g = math.gcd(g, spec[4][-1])
    if not (g or reps):
        return 0
    return tiled_divisor(
        g or rows, limit, full=rows,
        admit=lambda d: all(q % d == 0 or (d % q == 0
                                           and d // q <= REP_SPAN)
                            for q in reps))


def segment_row_block(rows: int, specs: Sequence[tuple],
                      rows_block: int = 512,
                      donate: bool = False) -> tuple[int, int, bool]:
    """Row-block selection for ``fused_segment_grid`` — exported so the
    static plan verifier (``repro.analysis``) re-derives the EXACT block
    sizes this kernel will pick, rather than re-implementing (and
    drifting from) the math.

    Returns ``(rb, pad, donate_kept)``: the block extent, the row padding
    the kernel will add, and whether donation survives (padding forces
    the kernel to drop ``input_output_aliases`` unless a row-dividing
    block of acceptable size exists)."""
    limit = max(min(rows_block, rows), 1)
    rb = row_block(rows, specs, limit) or limit
    pad = (-rows) % rb
    if pad and donate:
        # aliasing a jnp.pad temporary reuses a dead buffer, not the
        # real boundary tensor; prefer a row-dividing block (rep/tile
        # constraints guarantee pad == 0, so none applies here), and
        # only give up donation when that would tank the block size
        alt = tiled_divisor(rows, limit, full=rows)
        if alt >= max(limit // 8, 16):
            rb, pad = alt, 0
    return rb, pad, donate and not pad


def segment_grid_layout(rows: int, specs: Sequence[tuple],
                        out_cols: Sequence[int], rows_block: int = 512,
                        donate: bool = False):
    """``fused_segment_grid``'s geometry: ``(rb, pad, donate_kept,
    in_views, out_views)`` with one ``(view, block, index_map)`` per
    operand and per output, over the padded row extent."""
    rb, pad, keep = segment_row_block(rows, specs, rows_block, donate)
    padded = rows + pad
    views = [row_view(spec, padded, rb) for spec in specs]
    ins = [(view, block, lambda i, f=f: (f(i), 0))
           for view, block, f in views]
    outs = [((padded, c), (rb, c), lambda i: (i, 0)) for c in out_cols]
    return rb, pad, keep, ins, outs


def block_specs(operands: Sequence, in_views: Sequence,
                out_views: Sequence, out_dtypes: Sequence):
    """A ``(view, block, index_map)`` layout made concrete: the operands
    reshaped to their views, their BlockSpecs, the output shapes and the
    output BlockSpecs — the four arguments of a kernel's pallas_call."""
    ops2 = [jnp.asarray(v).reshape(view)
            for v, (view, _, _) in zip(operands, in_views)]
    in_specs = [pl.BlockSpec(block, imap) for _, block, imap in in_views]
    out_shape = [jax.ShapeDtypeStruct(view, dt)
                 for (view, _, _), dt in zip(out_views, out_dtypes)]
    out_specs = [pl.BlockSpec(block, imap) for _, block, imap in out_views]
    return ops2, in_specs, out_shape, out_specs


def _seg_kernel(*refs, fn: Callable, picks: Sequence):
    n_in = len(picks)
    vals = [read_block(r, p) for r, p in zip(refs[:n_in], picks)]
    outs = fn(*vals)
    for o_ref, o in zip(refs[n_in:], outs):
        o_ref[...] = o.astype(o_ref.dtype)


def fused_segment_grid(
    fn: Callable,
    operands: Sequence[jnp.ndarray],
    specs: Sequence[tuple[str, int, int]],
    *,
    rows: int,
    out_cols: Sequence[int],
    out_dtypes: Sequence,
    donate: Sequence[tuple[int, int]] = (),
    rows_block: int = 512,
    interpret: bool = False,
) -> tuple:
    """Cross-shape near-bank segment — the offload rewriter's target.

    Every operand carries its own 2-D block view via ``specs``
    (``(role, op_rows, cols)`` triples — or 5-tuples
    ``("bcast", op_rows, cols, lead, out_lead)`` for interior
    broadcasts — see repro.core.offload.OperandSpec): ``bulk`` operands
    tile the row grid, ``param`` operands broadcast one [1, cols] block
    to every step, ``rep``/``tile`` operands remap the grid index
    (``i // q`` / ``i % p``) so row-broadcast tensors like [B,1,D] are
    read once per distinct row instead of being materialized, and
    ``bcast`` operands ([B,1,S,1,D]-style interior broadcasts)
    decompose the row-block index over the output's leading dims and
    stride only their non-broadcast dims (``_bcast_row_index``).  ``fn``
    maps the blocks (plus a static ``block_rows``) to one
    [block_rows, out_cols[j]] block per output, all written in the same
    single HBM pass.

    ``donate`` is a sequence of (operand index, output index) pairs
    emitted as Pallas ``input_output_aliases``: segment-boundary buffers
    that die at this segment are reused in place for the outputs.

    Lane-axis reductions fuse here as a two-pass row-reduce: blocks span
    the full lane extent of their rows, so ``fn`` computes the row
    statistic ([block_rows, 1]) in a first pass over the resident block
    and applies/re-broadcasts it in a second pass — both in VMEM, with
    no extra HBM traffic (rmsnorm/softmax row stats; see
    ``repro.core.offload`` REDUCE_LANE_PRIMS admission).
    """
    rb, pad, keep, in_views, out_views = segment_grid_layout(
        rows, specs, out_cols, rows_block, donate=bool(donate))
    if not keep:
        donate = ()
    grid = ((rows + pad) // rb,)

    if pad:
        operands = [jnp.pad(jnp.asarray(v).reshape(rows, spec[2]),
                            ((0, pad), (0, 0))) if spec[0] == "bulk" else v
                    for spec, v in zip(specs, operands)]
    ops2, in_specs, out_shape, out_specs = block_specs(
        operands, in_views, out_views, out_dtypes)

    outs = pl.pallas_call(
        functools.partial(_seg_kernel,
                          fn=functools.partial(fn, block_rows=rb),
                          picks=row_picks(specs, rows + pad, rb)),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=dict(donate),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="fused_segment_grid",
        interpret=interpret,
    )(*ops2)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    return tuple(o[:rows] for o in outs)


def fused_segment(
    fn: Callable,
    bulk: Sequence[jnp.ndarray],
    params: Sequence[jnp.ndarray] = (),
    *,
    out_dtypes: Sequence,
    rows_block: int = 512,
    interpret: bool = False,
) -> tuple:
    """Multi-output segment entry point — what the offload rewriter emits.

    One eqn per near-bank segment: ``fn`` maps the segment's bulk blocks
    (+ broadcast params) to ``len(out_dtypes)`` outputs, all written in
    the same single HBM pass.  Always returns a tuple (one element per
    segment output), unlike ``fused_elementwise`` which unwraps
    single-output calls."""
    outs = fused_elementwise(fn, bulk, params, out_dtypes=list(out_dtypes),
                             n_outputs=len(out_dtypes),
                             rows_block=rows_block, interpret=interpret)
    return outs if isinstance(outs, tuple) else (outs,)
