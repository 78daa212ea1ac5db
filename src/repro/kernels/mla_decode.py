"""Paged latent-attention decode (MLA, absorbed form) as a Pallas TPU
kernel.

Every head of a latent-attention layer reads the same cached row per
token, ``[c ‖ k_pe]`` (the normed latent and the roped key slice), so
a decode step is one query block ``[NQ, C]`` per sequence against a
single page pool ``[P, page, C]``: the absorbed query ``[q_lat ‖ q_pe]``
scores each row, and the output is the softmax-weighted sum of the
rows' latent part ``c`` (``[NQ, R]``, R = C - rope width).  The heads
are decompressed afterwards by the caller (``o_lat @ W_UV``).

Like ``paged_decode_attention``, ``lengths`` and ``block_tables`` are
scalar-prefetched and drive the page index maps.  Each grid step takes
``pages_per_step`` pages through as many inputs of the same pool (one
block index map each), so a long table costs few grid steps; pages
past a sequence's length map to its last page, whose block is already
resident, and are masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mla_kernel(lengths_ref, tables_ref, q_ref, *refs, page: int,
                per_step: int, rank: int, scale: float):
    page_refs = refs[:per_step]
    o_ref, acc_ref, m_ref, l_ref = refs[per_step:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                    # [NQ, C]
    for j, ref in enumerate(page_refs):
        start = (i * per_step + j) * page

        @pl.when(start < length)
        def _page(ref=ref, start=start):
            rows = ref[0]                           # [page, C]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [NQ, page]
            pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            m_ref[...] = m_new
            pv = jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [NQ, R]
            acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)
                    ).astype(o_ref.dtype)


def pages_per_step(n_pages: int, limit: int = 8) -> int:
    """Pages each grid step takes: the largest divisor of the table
    width up to ``limit``."""
    return max(d for d in range(1, min(limit, n_pages) + 1)
               if n_pages % d == 0)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def paged_mla_decode(
    q: jnp.ndarray,             # [B, NQ, C] absorbed query [q_lat ‖ q_pe]
    pages: jnp.ndarray,         # [P, page, C] latent pool
    block_tables: jnp.ndarray,  # [B, NP] int32 page ids per sequence
    lengths: jnp.ndarray,       # [B] int32 valid cache lengths
    *,
    rank: int,                  # R: the latent part of a row
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed latent attention over a paged pool: ``[B, NQ, R]`` in the
    pool's dtype, the softmax(q · row * scale)-weighted sum of each row's
    first ``rank`` entries over the first ``lengths[b]`` positions of
    sequence ``b``."""
    b, nq, c = q.shape
    q = q.astype(pages.dtype)
    page = pages.shape[1]
    n_pages = block_tables.shape[1]
    per = pages_per_step(n_pages)

    def page_map(j):
        def index(bb, i, L, T):
            last = jnp.maximum(L[bb] - 1, 0) // page
            return (T[bb, jnp.minimum(i * per + j, last)], 0, 0)
        return index

    out = pl.pallas_call(
        functools.partial(_mla_kernel, page=page, per_step=per, rank=rank,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages // per),
            in_specs=[pl.BlockSpec((1, nq, c), lambda bb, i, L, T: (bb, 0, 0))]
            + [pl.BlockSpec((1, page, c), page_map(j)) for j in range(per)],
            out_specs=pl.BlockSpec((1, nq, rank),
                                   lambda bb, i, L, T: (bb, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((nq, rank), jnp.float32),
                pltpu.VMEM((nq, 1), jnp.float32),
                pltpu.VMEM((nq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nq, rank), pages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_mla_decode",
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      q, *([pages] * per))
    return out
