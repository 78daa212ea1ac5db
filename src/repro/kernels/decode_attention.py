"""Split-KV decode attention (flash-decoding) as Pallas TPU kernels.

The canonical near-bank op: one query token streams the whole KV cache
(arithmetic intensity ~1 FLOP/byte), so performance == bank bandwidth.
Both kernels tile the cache over the grid's sequential axis; the partial
(acc, m, l) triple lives in VMEM scratch — exactly MPU's near-bank
register file holding partial results while the "bank" (cache block)
streams past.  ``lengths`` rides in SMEM via scalar prefetch, mirroring
MPU's far-bank address path (LSU) vs near-bank value path split.

Two cache layouts:

* ``decode_attention`` — one contiguous cache per sequence.  The pool
  should be kept **head-major** ``[B, NK, T, H]`` with ``T`` padded to a
  block multiple **once at allocation** (``head_major=True``): the
  kernel then reads the pool in place.  The legacy token-major
  ``[B, T, NK, H]`` layout still works but costs a full
  ``jnp.pad``+``transpose`` copy of the cache on every call.
* ``paged_decode_attention`` — the cache is a global pool of fixed-size
  pages ``[P, NK, page, H]`` indexed per sequence by a ``block_tables``
  row (MPU's "multiple activated row-buffers" told in JAX).  The table
  is scalar-prefetched next to ``lengths`` and drives the K/V block
  index maps.  A block is one whole page, every kv head of it
  (``(1, NK, page, H)``, contiguous in the pool), and q and the output
  are one row's ``(1, NK, G, H)``, so scores and ``p @ V`` are batched
  over the head axis and the grid is ``(B, width // per)``.  Each grid
  step takes ``per`` pages through as many inputs of the same pool:
  the largest divisor of the table width, at most 8, whose K and V
  pages fit ``KV_STEP_BYTES`` (2 MiB: 8 pages of Qwen3-1.7B at 256 KB
  of K and V a page, 2 of DeepSeek-7B at 1 MB).  The index maps
  (``streamed_page``) clamp at each row's length: past its last used
  page an input keeps the page it holds, and an input the row never
  uses parks on pool page 0, so the block index repeats and the
  pipeline issues no DMA past a sequence's length; an empty row reads
  only ``T[b, 0]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, kv_block: int, scale: float):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk_blocks = pl.num_programs(2)
    length = lengths_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ki * kv_block

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [G, H]
        k = k_ref[0, 0].astype(jnp.float32)      # [Kb, H]
        v = v_ref[0, 0].astype(jnp.float32)      # [Kb, H]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, Kb]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ki == nk_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)[:, None]
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("kv_block", "head_major", "interpret"))
def decode_attention(
    q: jnp.ndarray,        # [B, NQ, H]
    k_cache: jnp.ndarray,  # [B, T, NK, H] (or [B, NK, T, H] head-major)
    v_cache: jnp.ndarray,  # same layout as k_cache
    lengths: jnp.ndarray,  # [B] int32
    *,
    kv_block: int = 512,
    head_major: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    b, nq, h = q.shape
    if head_major:
        # pool layout [B, NK, T, H], T padded once at allocation: the
        # kernel reads the cache in place — no per-step copy.
        nk, t = k_cache.shape[1], k_cache.shape[2]
        kv_block = min(kv_block, t)
        if t % kv_block:                       # fallback, off the hot path
            t_pad = (-t) % kv_block
            k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
            v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        kr, vr = k_cache, v_cache
        st = kr.shape[2]
    else:
        t, nk = k_cache.shape[1], k_cache.shape[2]
        kv_block = min(kv_block, t)
        t_pad = (-t) % kv_block
        kp = jnp.pad(k_cache, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        vp = jnp.pad(v_cache, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        st = t + t_pad
        kr = kp.transpose(0, 2, 1, 3)  # [B, NK, T, H]
        vr = vp.transpose(0, 2, 1, 3)
    g = nq // nk
    qr = q.reshape(b, nk, g, h)
    grid = (b, nk, st // kv_block)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, kv_block=kv_block,
                          scale=1.0 / (h ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, h), lambda bb, kh, ki, L: (bb, kh, 0, 0)),
                pl.BlockSpec((1, 1, kv_block, h),
                             lambda bb, kh, ki, L: (bb, kh, ki, 0)),
                pl.BlockSpec((1, 1, kv_block, h),
                             lambda bb, kh, ki, L: (bb, kh, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, h),
                                   lambda bb, kh, ki, L: (bb, kh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, h), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nk, g, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kr, vr)
    return out.reshape(b, nq, h)


# ---------------------------------------------------------------------------
# paged variant: block-table-indexed page pool
# ---------------------------------------------------------------------------

# K and V bytes one grid step of ``paged_decode_attention`` may take.
# Double-buffered that is 4 MiB, well inside the 16 MiB of VMEM a v5e
# kernel is scoped to by default.
KV_STEP_BYTES = 2 << 20


def pages_per_step(n_pages: int, page_bytes: int) -> int:
    """Pages each grid step takes: the largest divisor of the table
    width, up to 8 (each is an unrolled body), whose K and V pages
    (``page_bytes`` each) fit ``KV_STEP_BYTES``; 1 when not even one
    pair does."""
    return max(d for d in range(1, min(8, n_pages) + 1)
               if n_pages % d == 0
               and (d == 1 or 2 * d * page_bytes <= KV_STEP_BYTES))


def streamed_page(tables, lengths, b, i, j, *, page: int, per: int):
    """Pool page that input ``j`` of grid step ``i`` holds for row ``b``.

    Input ``j`` reads the row's table entries ``j, j + per, ...``.
    Past the row's last used page (``(lengths[b] - 1) // page``, 0 for
    an empty row) it keeps the last of them it read, and an input the
    row never uses parks on pool page 0.  Both repeat the previous grid
    step's block index wherever they can, and a repeated index issues
    no DMA, so no page past a row's length is streamed.  Pure, so the
    index maps (SMEM refs) and the tests (arrays) share it."""
    last = jnp.maximum(lengths[b] - 1, 0) // page
    held = jnp.minimum(i, jnp.maximum(last - j, 0) // per) * per + j
    return jnp.where(j <= last, tables[b, held], 0)


def _paged_decode_kernel(lengths_ref, tables_ref, q_ref, *refs, page: int,
                         per_step: int, scale: float):
    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * per_step:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                    # [NK, G, H]
    for j in range(per_step):
        start = (i * per_step + j) * page

        @pl.when(start < length)
        def _page(k_ref=k_refs[j], v_ref=v_refs[j], start=start):
            k = k_ref[0]                            # [NK, page, H]
            ct = jnp.promote_types(q.dtype, k.dtype)
            s = jax.lax.dot_general(
                q.astype(ct), k.astype(ct), (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # [NK, G, page]
            pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_ref[...]                     # [NK, G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            m_ref[...] = m_new
            pv = jax.lax.dot_general(
                p, v_ref[0].astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)          # [NK, G, H]
            acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jnp.ndarray,             # [B, NQ, H]
    k_pages: jnp.ndarray,       # [P, NK, page, H] global page pool
    v_pages: jnp.ndarray,       # [P, NK, page, H]
    block_tables: jnp.ndarray,  # [B, NP] int32 page ids per sequence
    lengths: jnp.ndarray,       # [B] int32 valid cache lengths
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention over a paged KV pool.

    ``block_tables[b, i]`` names the pool page holding positions
    ``[i*page, (i+1)*page)`` of sequence ``b``; rows shorter than NP
    pad with any valid page id (masked by ``lengths``).  The table is
    scalar-prefetched (SMEM) beside ``lengths`` and drives the K/V
    block index maps — the far-bank address path picks which "row
    buffer" (page) the near-bank value path streams next.
    """
    b, nq, h = q.shape
    nk, page = k_pages.shape[1], k_pages.shape[2]
    n_pages = block_tables.shape[1]
    g = nq // nk
    qr = q.reshape(b, nk, g, h)
    per = pages_per_step(n_pages, nk * page * h * k_pages.dtype.itemsize)

    def page_map(j):
        def index(bb, i, L, T):
            return (streamed_page(T, L, bb, i, j, page=page, per=per),
                    0, 0, 0)
        return index

    page_specs = [pl.BlockSpec((1, nk, page, h), page_map(j))
                  for j in range(per)]
    row = pl.BlockSpec((1, nk, g, h), lambda bb, i, L, T: (bb, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page, per_step=per,
                          scale=1.0 / (h ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages // per),
            in_specs=[row] + page_specs + page_specs,
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((nk, g, h), jnp.float32),
                pltpu.VMEM((nk, g, 1), jnp.float32),
                pltpu.VMEM((nk, g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nk, g, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_decode_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), qr,
      *([k_pages.reshape(-1, nk, page, h)] * per),
      *([v_pages.reshape(-1, nk, page, h)] * per))
    return out.reshape(b, nq, h)
