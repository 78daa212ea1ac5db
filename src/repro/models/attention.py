"""Attention: GQA / MHA, sliding-window, qk-norm, cross-attention, KV cache.

Two execution paths share one parameter layout:

* ``attention_apply`` — training/prefill path.  Blockwise *flash-style*
  online-softmax attention written in pure jnp with a double ``lax.scan``
  (query blocks outer, KV blocks inner).  It never materializes the
  [S, T] score matrix, matching the dataflow of the Pallas TPU kernel
  (``repro.kernels.flash_attention``) so the dry-run memory analysis
  reflects what actually runs on TPU.
* ``decode_attention_apply`` — single-token decode against a KV cache,
  chunked over the cache (split-KV / flash-decoding dataflow; the
  distributed version LSE-combines per-shard partials — the pod-level
  analogue of MPU's near-bank offload, see DESIGN.md §2).

Shapes: q [B, S, NQ, H]; k/v [B, T, NK, H]; GQA groups G = NQ // NK.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    Params,
    apply_rope,
    dense_init,
    init_rmsnorm,
    rmsnorm_apply,
)
from repro.sharding.constraints import model_axis_size, shard_act

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, *, cross: bool = False,
                   dtype=jnp.float32) -> Params:
    d = cfg.d_model
    h = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, nq * h, dtype),
        "wk": dense_init(ks[1], d, nkv * h, dtype),
        "wv": dense_init(ks[2], d, nkv * h, dtype),
        "wo": dense_init(ks[3], nq * h, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * h,), dtype)
        p["bk"] = jnp.zeros((nkv * h,), dtype)
        p["bv"] = jnp.zeros((nkv * h,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(h, dtype)
        p["k_norm"] = init_rmsnorm(h, dtype)
    return p


def project_qkv(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray | None,
                kv_input: jnp.ndarray | None = None):
    """Project to q, k, v (with bias / qk-norm / rope as configured).

    ``kv_input`` (cross-attention): keys/values come from encoder memory
    and carry no rope.
    """
    h = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    kv_src = x if kv_input is None else kv_input
    with jax.named_scope("qkv"):
        q = x @ params["wq"].astype(x.dtype)
        k = kv_src @ params["wk"].astype(x.dtype)
        v = kv_src @ params["wv"].astype(x.dtype)
        if cfg.qkv_bias:
            q = q + params["bq"].astype(x.dtype)
            k = k + params["bk"].astype(x.dtype)
            v = v + params["bv"].astype(x.dtype)
        q = q.reshape(*q.shape[:-1], nq, h)
        k = k.reshape(*k.shape[:-1], nkv, h)
        v = v.reshape(*v.shape[:-1], nkv, h)
        if cfg.qk_norm:
            q = rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
            k = rmsnorm_apply(params["k_norm"], k, cfg.norm_eps)
    if positions is not None and kv_input is None:
        with jax.named_scope("rope"):
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# blockwise flash-style attention (pure jnp oracle-grade implementation)
# ---------------------------------------------------------------------------

def _block_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, *, causal: bool,
                window: int) -> jnp.ndarray:
    """[Qb, Kb] additive-mask predicate (True = attend)."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return ok


def blockwise_attention(
    q: jnp.ndarray,  # [B, S, NQ, H]
    k: jnp.ndarray,  # [B, T, NK, H]
    v: jnp.ndarray,  # [B, T, NK, Hv]
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 512,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Online-softmax attention; never materializes [S, T] scores.

    ``q_offset``: absolute position of q[0] (for cached decode/prefill
    continuation).  Softmax statistics are fp32.  Values may be of
    another head size than queries and keys (latent attention); the
    output has the values' [B, S, NQ, Hv].
    """
    B, S, NQ, H = q.shape
    T, NK = k.shape[1], k.shape[2]
    HV = v.shape[-1]
    G = NQ // NK
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    # pad to block multiples
    s_pad = (-S) % q_block
    t_pad = (-T) % kv_block
    qp = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    nqb, nkb = (S + s_pad) // q_block, (T + t_pad) // kv_block

    # [nqb, B, qb, NK, G, H]
    qb = qp.reshape(B, nqb, q_block, NK, G, H).transpose(1, 0, 2, 3, 4, 5)
    kb = kp.reshape(B, nkb, kv_block, NK, H).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nkb, kv_block, NK, HV).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / (H ** 0.5)

    def q_step(_, q_idx_and_block):
        q_idx, qblk = q_idx_and_block
        q_pos = q_offset + q_idx * q_block + jnp.arange(q_block)

        def kv_step(carry, k_idx_and_blocks):
            acc, m, l = carry
            k_idx, kblk, vblk = k_idx_and_blocks
            k_pos = k_idx * kv_block + jnp.arange(kv_block)
            # scores [B, qb, NK, G, kb] fp32
            s = jnp.einsum(
                "bqkgh,bckh->bqkgc", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale
            ok = _block_mask(q_pos, k_pos, causal=causal, window=window)
            ok = ok & (k_pos < T)[None, :]  # mask kv padding
            s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bqkgc,bckh->bqkgh", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, q_block, NK, G, HV), jnp.float32)
        m0 = jnp.full((B, q_block, NK, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_block, NK, G), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), (jnp.arange(nkb), kb, vb)
        )
        out = acc / jnp.maximum(l[..., None], 1e-37)
        return None, out.astype(q.dtype)

    _, ob = jax.lax.scan(q_step, None, (jnp.arange(nqb), qb))
    # ob: [nqb, B, qb, NK, G, HV] -> [B, S, NQ, HV]
    out = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, nqb * q_block, NQ, HV)
    return out[:, :S]


def decode_attention(
    q: jnp.ndarray,        # [B, NQ, H] single query token
    k_cache: jnp.ndarray,  # [B, T, NK, H]
    v_cache: jnp.ndarray,  # [B, T, NK, H]
    lengths: jnp.ndarray,  # [B] valid cache lengths (the new token's position + 1)
    *,
    window: int = 0,
    kv_block: int = 1024,
    return_stats: bool = False,
) -> jnp.ndarray:
    """Split-KV decode attention (flash-decoding dataflow), chunked over the
    cache.  Memory-bound: ~2 FLOPs/byte — the canonical near-bank op.
    ``return_stats``: return the raw (acc, m, l) online-softmax partials
    (used by the cross-shard LSE combine)."""
    B, NQ, H = q.shape
    T, NK = k_cache.shape[1], k_cache.shape[2]
    G = NQ // NK
    kv_block = min(kv_block, T)
    t_pad = (-T) % kv_block
    kp = jnp.pad(k_cache, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    nkb = (T + t_pad) // kv_block
    kb = kp.reshape(B, nkb, kv_block, NK, H).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nkb, kv_block, NK, H).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(B, NK, G, H)
    scale = 1.0 / (H ** 0.5)

    def kv_step(carry, idx_and_blocks):
        acc, m, l = carry
        k_idx, kblk, vblk = idx_and_blocks
        k_pos = k_idx * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("bkgh,bckh->bkgc", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        ok = k_pos[None, :] < lengths[:, None]
        if window > 0:
            ok &= k_pos[None, :] > (lengths[:, None] - 1 - window)
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgc,bckh->bkgh", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        return (acc * corr[..., None] + pv, m_new, l_new), None

    acc0 = jnp.zeros((B, NK, G, H), jnp.float32)
    m0 = jnp.full((B, NK, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, NK, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                  (jnp.arange(nkb), kb, vb))
    if return_stats:
        return acc, m, l
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.reshape(B, NQ, H).astype(q.dtype)


# ---------------------------------------------------------------------------
# module-level apply fns
# ---------------------------------------------------------------------------

def _attention_layout(cfg: ModelConfig, batch: int, seq: int) -> str:
    """Pick the attention sharding layout (EXPERIMENTS.md SPerf):
    head-TP when q AND kv heads divide the model axis (zero resharding);
    else shard_map sequence-parallelism (each model shard owns a q slice,
    the small GQA k/v are gathered once) — SPerf iteration 2; the 2D-batch
    constraint of iteration 1 was refuted (GSPMD replicated before
    resharding: all-gather grew 5x)."""
    m = model_axis_size()
    if m <= 1:
        return "none"
    if cfg.num_kv_heads % m == 0 and cfg.num_heads % m == 0:
        return "head_tp"
    if seq % m == 0:
        return "seq_mp"
    return "none"


def _shard_qkv(q, k, v, layout: str):
    if layout == "head_tp":
        q = shard_act(q, "batch", None, "heads", None)
        k = shard_act(k, "batch", None, "heads", None)
        v = shard_act(v, "batch", None, "heads", None)
    return q, k, v


def _seq_sharded_attention(q, k, v, *, causal: bool, window: int):
    """shard_map sequence-parallel flash attention over the model axis.

    Every model shard computes online-softmax attention for its local
    query slice against the (gathered) full k/v — the distributed
    analogue of MPU near-bank offload: queries stay resident, only the
    small shared operands move over the links."""
    from repro.sharding.constraints import policy
    from jax.sharding import PartitionSpec as P

    pol = policy()
    m = pol.sizes.get("model", 1)
    fsdp = pol.fsdp
    s_loc = q.shape[1] // m

    def local(q_l, k_g, v_g):
        idx = jax.lax.axis_index("model")
        return blockwise_attention(
            q_l, k_g, v_g, causal=causal, window=window,
            q_offset=idx * s_loc)

    return jax.shard_map(
        local,
        mesh=pol.mesh,
        in_specs=(P(fsdp, "model", None, None),
                  P(fsdp, None, None, None),
                  P(fsdp, None, None, None)),
        out_specs=P(fsdp, "model", None, None),
        check_vma=False,
    )(q, k, v)


def attention_apply(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,               # [B, S, D]
    positions: jnp.ndarray,       # [B, S]
    *,
    causal: bool = True,
    kv_input: jnp.ndarray | None = None,  # cross-attention memory [B, T, D]
) -> jnp.ndarray:
    q, k, v = project_qkv(params, cfg, x, positions, kv_input)
    layout = _attention_layout(cfg, x.shape[0], q.shape[1])
    is_causal = causal and kv_input is None
    window = cfg.sliding_window if kv_input is None else 0
    if layout == "seq_mp":
        out = _seq_sharded_attention(q, k, v, causal=is_causal,
                                     window=window)
    else:
        q, k, v = _shard_qkv(q, k, v, layout)
        out = blockwise_attention(q, k, v, causal=is_causal, window=window)
    out = out.reshape(*x.shape[:-1], cfg.num_heads * cfg.resolved_head_dim)
    out = shard_act(out, "batch", None, None)
    return out @ params["wo"].astype(x.dtype)


def attention_prefill_apply(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,               # [B, S, D]
    positions: jnp.ndarray,       # [B, S]
    max_len: int,
    cache_dtype=jnp.bfloat16,
    length: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Parallel prefill: full-sequence attention + KV cache capture.

    Returns (out [B,S,D], k_cache [B,T,NK,H], v_cache) with T = max_len
    (or the sliding window for SWA archs, arranged rolling so that decode
    continues with slot = pos %% window).

    ``length`` (traced scalar): number of *real* tokens when the input
    is right-padded to a shape bucket — the SWA rolling capture then
    arranges by the real length so pad tokens never occupy a slot a
    real token owns (dense capture needs no masking: pad entries sit at
    positions >= length and decode overwrites them before its length
    mask would ever admit them)."""
    b, s, _ = x.shape
    q, k, v = project_qkv(params, cfg, x, positions)
    layout = _attention_layout(cfg, b, s)
    if layout == "seq_mp":
        out = _seq_sharded_attention(q, k, v, causal=True,
                                     window=cfg.sliding_window)
    else:
        q, k, v = _shard_qkv(q, k, v, layout)
        out = blockwise_attention(q, k, v, causal=True,
                                  window=cfg.sliding_window)
    out = out.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
    out = shard_act(out, "batch", None, None)
    out = out @ params["wo"].astype(x.dtype)

    w = cfg.sliding_window
    if w > 0:
        size = min(max_len, w)
        if s >= size and length is not None:
            # length-aware rolling: slot j holds token t, the last real
            # t with t % size == j; slots no real token reaches are
            # zeroed (length <= size leaves slots j >= length empty —
            # the same layout the unpadded s < size branch produces).
            j = jnp.arange(size)
            last = (length - 1) - (length - 1 - j) % size
            valid = last >= 0
            k_c = jnp.take(k, jnp.clip(last, 0, s - 1), axis=1)
            v_c = jnp.take(v, jnp.clip(last, 0, s - 1), axis=1)
            k_c = jnp.where(valid[None, :, None, None], k_c, 0)
            v_c = jnp.where(valid[None, :, None, None], v_c, 0)
        elif s >= size:
            # rolling arrangement: buf[slot] = token t, t = last with t%size==slot
            last = s - 1 - (s - 1 - jnp.arange(size)) % size
            k_c = jnp.take(k, last, axis=1)
            v_c = jnp.take(v, last, axis=1)
        else:
            pad = size - s
            k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        pad = max_len - s
        k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out, k_c.astype(cache_dtype), v_c.astype(cache_dtype)


# ---------------------------------------------------------------------------
# paged KV cache: block-table indexed page pools
# ---------------------------------------------------------------------------

def gather_kv_pages(pages: jnp.ndarray, block_tables: jnp.ndarray
                    ) -> jnp.ndarray:
    """[P, NK, page, H] pool + [B, NP] table -> token-major [B, T, NK, H]
    contiguous view (T = NP * page).  Only the *bucketed* pages move —
    the jnp analogue of the paged Pallas kernel's block index maps."""
    b, n_pages = block_tables.shape
    nk, page, h = pages.shape[1:]
    g = pages[block_tables]              # [B, NP, NK, page, H]
    return g.transpose(0, 1, 3, 2, 4).reshape(b, n_pages * page, nk, h)


def write_kv_page_entries(pages: jnp.ndarray, new: jnp.ndarray,
                          page_ids: jnp.ndarray, offsets: jnp.ndarray
                          ) -> jnp.ndarray:
    """Scatter per-row entries into the pool: ``new`` [R, NK, H] lands at
    ``pages[page_ids[r], :, offsets[r]]``.  Rows meant to be dropped
    should point at the reserved scratch page 0.

    The scatter runs on the pool viewed as ``[P * NK * page, H]`` rows:
    indexing dims 0 and 2 of the 4-D pool makes the TPU compiler lay the
    pool out head-minor for the scatter and copy it whole back into the
    layout the paged kernel reads, where a row scatter keeps the
    kernel's layout and updates the pool in place."""
    _, nk, page, h = pages.shape
    rows = (page_ids[:, None] * nk + jnp.arange(nk)) * page + offsets[:, None]
    flat = pages.reshape(-1, h).at[rows.reshape(-1)].set(
        new.reshape(-1, h).astype(pages.dtype))
    return flat.reshape(pages.shape)


def _paged_kernel() -> bool:
    """True where the paged decode streams pages through the Pallas
    kernel's block index maps (TPU); elsewhere it gathers them in jnp."""
    return jax.default_backend() == "tpu"


def attention_decode_paged(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,               # [B, 1, D] new token
    pages_k: jnp.ndarray,         # [P, NK, page, H] global page pool
    pages_v: jnp.ndarray,
    pos: jnp.ndarray,             # [B] position of the new token
    block_tables: jnp.ndarray,    # [B, NP] int32 (bucketed width)
    active: jnp.ndarray,          # [B] bool — inactive rows write scratch
    *,
    kv_capacity: int,             # logical per-request cache size
    page_offset: jnp.ndarray | int = 0,  # this layer's first pool page
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step against the paged pool: project the new token,
    scatter its K/V into the owning page (inactive rows land in the
    layer's reserved scratch page), attend over the *bucketed* gathered
    pages.

    The pool may hold several layers' pools back to back
    (``[L * P, ...]``): ``page_offset`` (``layer * P``) shifts every
    table entry into this layer's rows, so the write updates one row of
    the shared buffer in place and nothing slices a layer out of it.

    Single-device path (the distributed engine uses the sequence-sharded
    dense cache).  On TPU the gather never happens — the paged Pallas
    kernel streams pages through block index maps."""
    B = x.shape[0]
    page = pages_k.shape[2]
    q, k, v = project_qkv(params, cfg, x, pos[:, None])
    if cfg.sliding_window > 0:
        slot = pos % kv_capacity
        lengths = jnp.minimum(pos + 1, kv_capacity)
    else:
        slot = jnp.minimum(pos, kv_capacity - 1)
        lengths = pos + 1
    lengths = jnp.where(active, lengths, 0)
    pi = jnp.clip(slot // page, 0, block_tables.shape[1] - 1)
    gp = jnp.where(active, block_tables[jnp.arange(B), pi], 0) + page_offset
    off = slot % page
    block_tables = block_tables + page_offset
    with jax.named_scope("kv_write"):
        pages_k = write_kv_page_entries(pages_k, k[:, 0], gp, off)
        pages_v = write_kv_page_entries(pages_v, v[:, 0], gp, off)
    with jax.named_scope("paged_attention"):
        if _paged_kernel():
            from repro.kernels import ops as kops
            out = kops.paged_decode_attention(
                q[:, 0], pages_k, pages_v, block_tables, lengths)
        else:
            # slice the gather to the logical capacity: the bucketed
            # table width rounds up to pow2 pages, and trimming the tail
            # keeps the chunked online-softmax bit-identical to the
            # dense-cache path
            k_cache = gather_kv_pages(pages_k, block_tables)[:, :kv_capacity]
            v_cache = gather_kv_pages(pages_v, block_tables)[:, :kv_capacity]
            out = decode_attention(q[:, 0], k_cache, v_cache, lengths,
                                   window=0)
    with jax.named_scope("out_proj"):
        out = out.reshape(B, 1, cfg.num_heads * cfg.resolved_head_dim)
        return out @ params["wo"].astype(x.dtype), pages_k, pages_v


def attention_prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,               # [1, C, D] prompt chunk (right-padded)
    pages_k: jnp.ndarray,         # [P, NK, page, H]
    pages_v: jnp.ndarray,
    block_table: jnp.ndarray,     # [NP] int32 — this request's pages
    ctx_len: jnp.ndarray,         # scalar: tokens already cached
    n_valid: jnp.ndarray,         # scalar: real tokens in this chunk
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked prefill for dense (non-SWA) attention: write the chunk's
    K/V into the request's pages, then attend the chunk's queries over
    the gathered context+chunk.  Pad rows of the chunk scatter into the
    scratch page and produce unused outputs."""
    assert cfg.sliding_window == 0, "chunked prefill is dense-only"
    _, c, _ = x.shape
    page = pages_k.shape[2]
    positions = (ctx_len + jnp.arange(c))[None]
    q, k, v = project_qkv(params, cfg, x, positions)
    pos_t = ctx_len + jnp.arange(c)
    valid = jnp.arange(c) < n_valid
    pi = jnp.clip(pos_t // page, 0, block_table.shape[0] - 1)
    gp = jnp.where(valid, block_table[pi], 0)
    off = pos_t % page
    pages_k = write_kv_page_entries(pages_k, k[0], gp, off)
    pages_v = write_kv_page_entries(pages_v, v[0], gp, off)
    kg = gather_kv_pages(pages_k, block_table[None])   # [1, T, NK, H]
    vg = gather_kv_pages(pages_v, block_table[None])
    out = blockwise_attention(q, kg, vg, causal=True, window=0,
                              q_offset=ctx_len)
    out = out.reshape(1, c, cfg.num_heads * cfg.resolved_head_dim)
    return out @ params["wo"].astype(x.dtype), pages_k, pages_v


def _split_kv_decode_sharded(q, cache_k, cache_v, new_k, new_v, slot,
                             lengths):
    """shard_map split-KV decode over a sequence-sharded cache.

    The pod-level near-bank pattern (DESIGN.md §2): each model shard holds
    a KV-cache slice (its "banks"), updates the slice owning the write
    slot, computes partial online-softmax attention locally (the "NBU"),
    and only the tiny (acc, m, l) statistics cross the links (the
    "register move" over the TSV) for an LSE-weighted combine.  Replaces
    the baseline's full-cache all-gather (60 GB/step for qwen2.5-32b
    decode_32k -> ~200 KB/step)."""
    from repro.sharding.constraints import policy
    from jax.sharding import PartitionSpec as P

    pol = policy()
    m = pol.sizes.get("model", 1)
    fsdp = pol.fsdp
    # drop the batch axis from the specs when the batch doesn't divide it
    # (long_500k runs batch=1)
    n_fsdp = 1
    for a in (fsdp if isinstance(fsdp, tuple) else (fsdp,)):
        n_fsdp *= pol.sizes.get(a, 1) if a else 1
    if q.shape[0] % max(n_fsdp, 1) != 0:
        fsdp = None
    t_loc = cache_k.shape[1] // m

    def local(q_l, kc, vc, nk, nv, slot_l, len_l):
        b = q_l.shape[0]  # local batch (B / fsdp)
        idx = jax.lax.axis_index("model")
        start = idx * t_loc
        s_loc = slot_l - start
        in_range = (s_loc >= 0) & (s_loc < t_loc)
        safe = jnp.clip(s_loc, 0, t_loc - 1)
        bidx = jnp.arange(b)
        old_k = kc[bidx, safe]
        old_v = vc[bidx, safe]
        kc = kc.at[bidx, safe].set(
            jnp.where(in_range[:, None, None], nk.astype(kc.dtype), old_k))
        vc = vc.at[bidx, safe].set(
            jnp.where(in_range[:, None, None], nv.astype(vc.dtype), old_v))
        local_len = jnp.clip(len_l - start, 0, t_loc)
        acc, mx, l = decode_attention(q_l, kc, vc, local_len,
                                      return_stats=True)
        # LSE combine across shards: only the statistics move
        accs = jax.lax.all_gather(acc, "model")   # [m, B, NK, G, H]
        ms = jax.lax.all_gather(mx, "model")      # [m, B, NK, G]
        ls = jax.lax.all_gather(l, "model")
        m_g = jnp.max(ms, axis=0)
        w = jnp.exp(ms - m_g[None])
        acc_g = jnp.sum(accs * w[..., None], axis=0)
        l_g = jnp.sum(ls * w, axis=0)
        out = acc_g / jnp.maximum(l_g[..., None], 1e-37)
        nq = out.shape[1] * out.shape[2]
        return out.reshape(b, nq, -1).astype(q_l.dtype), kc, vc

    return jax.shard_map(
        local,
        mesh=pol.mesh,
        in_specs=(P(fsdp, None, None),
                  P(fsdp, "model", None, None),
                  P(fsdp, "model", None, None),
                  P(fsdp, None, None), P(fsdp, None, None),
                  P(fsdp), P(fsdp)),
        out_specs=(P(fsdp, None, None),
                   P(fsdp, "model", None, None),
                   P(fsdp, "model", None, None)),
        check_vma=False,
    )(q, cache_k, cache_v, new_k, new_v, slot, lengths)


def attention_decode_apply(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,               # [B, 1, D] new token
    cache_k: jnp.ndarray,         # [B, T, NK, H]
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,             # [B] position of the new token
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step: project new token, update rolling/linear cache,
    attend over the cache.  Returns (out [B,1,D], new_k, new_v)."""
    from repro.sharding.constraints import policy

    B = x.shape[0]
    T = cache_k.shape[1]
    q, k, v = project_qkv(params, cfg, x, pos[:, None])
    # write position: linear cache -> pos; rolling (SWA) cache -> pos % T
    slot = pos % T if cfg.sliding_window > 0 else jnp.minimum(pos, T - 1)
    lengths = jnp.minimum(pos + 1, T) if cfg.sliding_window > 0 else pos + 1

    pol = policy()
    m = pol.sizes.get("model", 1) if pol is not None else 1
    use_split = (pol is not None and m > 1
                 and cfg.num_kv_heads % m != 0 and T % m == 0)
    if use_split:
        out, new_k, new_v = _split_kv_decode_sharded(
            q[:, 0], cache_k, cache_v, k[:, 0], v[:, 0], slot, lengths)
    else:
        bidx = jnp.arange(B)
        new_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
        new_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))
        out = decode_attention(q[:, 0], new_k, new_v, lengths, window=0)
    out = out.reshape(B, 1, cfg.num_heads * cfg.resolved_head_dim)
    return out @ params["wo"].astype(x.dtype), new_k, new_v


def reference_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """Naive O(S*T) oracle used only by tests."""
    B, S, NQ, H = q.shape
    T, NK = k.shape[1], k.shape[2]
    G = NQ // NK
    qg = q.reshape(B, S, NK, G, H)
    s = jnp.einsum("bskgh,btkh->bskgt", qg, k,
                   preferred_element_type=jnp.float32) / (H ** 0.5)
    q_pos = q_offset + jnp.arange(S)
    k_pos = jnp.arange(T)
    ok = jnp.ones((S, T), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bskgt,btkh->bskgh", p.astype(v.dtype), v)
    return out.reshape(B, S, NQ, H).astype(q.dtype)
