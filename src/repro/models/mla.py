"""Multi-head latent attention (DeepSeek-V2/V3 MLA, no query low-rank
path).

Per token, with ``h`` the normed input and R = kv_rank:

    q = h W_q                      viewed [NQ, nope + rope]
    [c ‖ k_pe] = h W_kva;  c = RMSNorm_kv(c)
    [k_nope ‖ v] = c W_kvb         viewed [NQ, nope + v]
    q_pe, k_pe roped (one k_pe shared by every head)
    o = softmax((q_nope·k_nope + q_pe·k_pe) / sqrt(nope + rope)) v;  o W_o

RoPE rotates adjacent pairs (2i, 2i+1) of the rope slice at frequency
i, as ``modeling_deepseek.py`` does by de-interleaving the pairs before
its rotate-half; the rotated slice is kept in that de-interleaved order
(evens then odds) in q and k alike.

Prefill (and the training forward) decompresses K and V per head and
runs the causal blockwise attention.  Decode never builds per-head K or
V: the cache holds one row ``[c ‖ k_pe]`` per token, ``W_kvb`` splits
into ``W_UK`` and ``W_UV``, the query is absorbed into the latent space
(``q_lat[h] = q_nope[h] W_UK[h]^T``), every head scores the rows with
``[q_lat ‖ q_pe]``, the softmax-weighted sum of the rows' ``c`` comes
back per head and ``W_UV`` decompresses it.

A cache row is stored ``cache_row`` wide: ``c ‖ k_pe`` padded with
zeros to whole 128-lane tiles (576 -> 640).  The TPU tiles the minor
axis in 128 lanes either way; with the padding explicit the pool keeps
the row-major layout the paged kernel reads, where a 576-wide pool is
laid out page-minor and copied whole into the kernel's layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ref import gather_rows, ref_mla_decode
from repro.models.attention import blockwise_attention
from repro.models.layers import (
    Params,
    dense_init,
    init_rmsnorm,
    rmsnorm_apply,
    rope_frequencies,
    round_up,
)


def cache_row(cfg: ModelConfig) -> int:
    """Stored width of one latent cache row."""
    return round_up(cfg.mla.cache_dim, 128)


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    m, d, nq = cfg.mla, cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, nq * m.qk_dim, dtype),
        "wkv_a": dense_init(ks[1], d, m.cache_dim, dtype),
        "kv_norm": init_rmsnorm(m.kv_rank, dtype),
        "wkv_b": dense_init(ks[2], m.kv_rank, nq * (m.nope_dim + m.v_dim),
                            dtype),
        "wo": dense_init(ks[3], nq * m.v_dim, d, dtype),
    }


def rope_pairs(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x [..., S, heads, r]; positions [..., S].  Rotates pairs
    (2i, 2i+1) by position * theta^(-2i/r); returns the rotated pairs
    de-interleaved: [evens ‖ odds]."""
    freqs = rope_frequencies(x.shape[-1], theta)
    ang = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _project(params: Params, cfg: ModelConfig, x: jnp.ndarray,
             positions: jnp.ndarray):
    """x [B, S, d] -> q_nope [B,S,NQ,nope], q_pe [B,S,NQ,rope],
    c [B,S,R] (normed), k_pe [B,S,rope] (roped)."""
    m, nq = cfg.mla, cfg.num_heads
    with jax.named_scope("q_proj"):
        q = (x @ params["wq"].astype(x.dtype)).reshape(
            *x.shape[:-1], nq, m.qk_dim)
        kv = x @ params["wkv_a"].astype(x.dtype)
        c = rmsnorm_apply(params["kv_norm"], kv[..., :m.kv_rank],
                          cfg.norm_eps)
        q_pe = rope_pairs(q[..., m.nope_dim:], positions, cfg.rope_theta)
        k_pe = rope_pairs(kv[..., None, m.kv_rank:], positions,
                          cfg.rope_theta)[..., 0, :]
    return q[..., :m.nope_dim], q_pe, c, k_pe


def _rows(cfg: ModelConfig, c: jnp.ndarray, k_pe: jnp.ndarray) -> jnp.ndarray:
    """Cache rows ``[c ‖ k_pe ‖ 0]`` of width ``cache_row``."""
    pad = cache_row(cfg) - cfg.mla.cache_dim
    zeros = jnp.zeros((*c.shape[:-1], pad), c.dtype)
    return jnp.concatenate([c, k_pe.astype(c.dtype), zeros], -1)


def mla_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Causal full-sequence MLA in the decompressed form.  Returns
    (out [B,S,d], cache rows [B,S,cache_row])."""
    m, nq = cfg.mla, cfg.num_heads
    q_nope, q_pe, c, k_pe = _project(params, cfg, x, positions)
    kvb = (c @ params["wkv_b"].astype(x.dtype)).reshape(
        *c.shape[:-1], nq, m.nope_dim + m.v_dim)
    k = jnp.concatenate([kvb[..., :m.nope_dim], jnp.broadcast_to(
        k_pe[..., None, :], (*k_pe.shape[:-1], nq, m.rope_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    out = blockwise_attention(q, k, kvb[..., m.nope_dim:], causal=True)
    with jax.named_scope("out_proj"):
        out = out.reshape(*x.shape[:-1], nq * m.v_dim)
        out = out @ params["wo"].astype(x.dtype)
    return out, _rows(cfg, c, k_pe)


def _absorbed_query(params: Params, cfg: ModelConfig, q_nope, q_pe):
    """[q_lat ‖ q_pe ‖ 0] [B, NQ, cache_row] of one decode token."""
    m, nq = cfg.mla, cfg.num_heads
    with jax.named_scope("mla_absorb"):
        w_uk = params["wkv_b"].reshape(m.kv_rank, nq, m.nope_dim + m.v_dim)[
            ..., :m.nope_dim]
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope,
                           w_uk.astype(q_nope.dtype))
        return _rows(cfg, q_lat, q_pe)


def _decompress(params: Params, cfg: ModelConfig, o_lat: jnp.ndarray,
                dtype) -> jnp.ndarray:
    """o_lat [B, NQ, R] -> the layer's output [B, 1, d]."""
    m, nq = cfg.mla, cfg.num_heads
    with jax.named_scope("out_proj"):
        w_uv = params["wkv_b"].reshape(m.kv_rank, nq, m.nope_dim + m.v_dim)[
            ..., m.nope_dim:]
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dtype), w_uv.astype(dtype))
        o = o.reshape(o.shape[0], 1, nq * m.v_dim)
        return o @ params["wo"].astype(dtype)


def _scale(cfg: ModelConfig) -> float:
    return cfg.mla.qk_dim ** -0.5


def mla_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray,
               rows: jnp.ndarray, pos: jnp.ndarray
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token per row against a contiguous cache: x [B,1,d]; rows
    [B, T, cache_row]; pos [B].  Returns (out [B,1,d], rows)."""
    b, t = rows.shape[:2]
    q_nope, q_pe, c, k_pe = _project(params, cfg, x, pos[:, None])
    slot = jnp.minimum(pos, t - 1)
    with jax.named_scope("kv_write"):
        rows = rows.at[jnp.arange(b), slot].set(
            _rows(cfg, c, k_pe)[:, 0].astype(rows.dtype))
    q = _absorbed_query(params, cfg, q_nope[:, 0], q_pe[:, 0])
    o_lat = ref_mla_decode(q, rows, pos + 1, rank=cfg.mla.kv_rank,
                           scale=_scale(cfg))
    return _decompress(params, cfg, o_lat, x.dtype), rows


def mla_decode_paged(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                     pages: jnp.ndarray, pos: jnp.ndarray,
                     block_tables: jnp.ndarray, active: jnp.ndarray, *,
                     kv_capacity: int, page_offset: jnp.ndarray | int = 0
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token per row against the paged latent pool [P, page,
    cache_row] (several layers' pools back to back, this layer's at
    ``page_offset``): the token's row is written in place (inactive rows
    into the layer's scratch page), then absorbed attention over the
    rows the block tables name — the ``paged_mla_decode`` kernel on the
    TPU, a gather of the pages elsewhere."""
    from repro.models.attention import _paged_kernel

    b = x.shape[0]
    page = pages.shape[1]
    q_nope, q_pe, c, k_pe = _project(params, cfg, x, pos[:, None])
    slot = jnp.minimum(pos, kv_capacity - 1)
    lengths = jnp.where(active, pos + 1, 0)
    pi = jnp.clip(slot // page, 0, block_tables.shape[1] - 1)
    gp = jnp.where(active, block_tables[jnp.arange(b), pi], 0) + page_offset
    block_tables = block_tables + page_offset
    with jax.named_scope("kv_write"):
        flat = pages.reshape(-1, pages.shape[-1]).at[gp * page + slot % page]\
            .set(_rows(cfg, c, k_pe)[:, 0].astype(pages.dtype))
        pages = flat.reshape(pages.shape)
    q = _absorbed_query(params, cfg, q_nope[:, 0], q_pe[:, 0])
    with jax.named_scope("paged_mla"):
        if _paged_kernel():
            from repro.kernels import ops as kops
            o_lat = kops.paged_mla_decode(q, pages, block_tables, lengths,
                                          rank=cfg.mla.kv_rank,
                                          scale=_scale(cfg))
        else:
            rows = gather_rows(pages, block_tables)[:, :kv_capacity]
            o_lat = ref_mla_decode(q, rows, lengths, rank=cfg.mla.kv_rank,
                                   scale=_scale(cfg))
    return _decompress(params, cfg, o_lat, x.dtype), pages
