"""Transformer stack assembly: homogeneous and hybrid block stacks.

The decoder stack is defined by ``cfg.block_pattern`` cycled over
``cfg.num_layers``.  To keep the lowered HLO small (64-layer models must
compile quickly for the 512-device dry-run) the stack is executed as a
``lax.scan`` over *pattern periods* with the (short) period unrolled
inside the body:

    num_layers = n_periods * P + remainder      (P = len(block_pattern))
    params = { "stack": {pos: stacked [n_periods, ...]},
               "rem":   {pos: unstacked} ,
               "shared_attn": tied params }     (zamba2 shared block)

``shared_attention`` positions share one parameter set (tied weights, as
in Zamba2) but keep *per-occurrence* KV caches.

Leading dense layers (``cfg.first_dense``, DeepSeek-V3's
``first_k_dense_replace``) sit unstacked under ``"lead"`` and run before
the scan: attention blocks whose FFN is a dense SwiGLU of ``d_ff`` where
the stacked layers have the MoE.  Attention is multi-head latent
attention (``repro.models.mla``) where ``cfg.mla`` is set; its cache is
one latent row per token (leaf ``"ckv"``) in place of K and V.

Block kinds:
    attention         norm→attn(+cross)→norm→ffn(dense MLP or MoE)
    shared_attention  same, tied weights
    mamba2            norm→mamba2 (no FFN — Zamba2-style)
    rwkv6             norm→time-mix→norm→channel-mix
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import BlockKind, ModelConfig
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import (
    attention_apply,
    attention_decode_apply,
    attention_decode_paged,
    attention_prefill_apply,
    attention_prefill_chunk,
    init_attention,
)
from repro.models.layers import (
    Params,
    init_mlp,
    init_rmsnorm,
    mlp_apply,
    rmsnorm_apply,
)
from repro.models.mla import (
    cache_row,
    init_mla,
    mla_apply,
    mla_decode,
    mla_decode_paged,
)
from repro.models.moe import init_moe, moe_apply, moe_dropless
from repro.sharding.constraints import shard_act

Cache = dict[str, Any]


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: BlockKind, *,
               cross: bool = False, dtype=jnp.float32,
               dense_ffn: bool = False) -> Params:
    """``dense_ffn``: a dense SwiGLU of ``d_ff`` where the config has
    MoE (a leading dense layer)."""
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    if kind in ("attention", "shared_attention"):
        attn = (init_mla(ks[0], cfg, dtype=dtype) if cfg.mla is not None
                else init_attention(ks[0], cfg, dtype=dtype))
        p: Params = {
            "ln1": init_rmsnorm(d, dtype),
            "attn": attn,
            "ln2": init_rmsnorm(d, dtype),
        }
        if cfg.moe is not None and not dense_ffn:
            p["ffn"] = init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = init_mlp(ks[1], d, cfg.d_ff, gated=cfg.gated_mlp,
                                dtype=dtype)
        if cross:
            p["ln_cross"] = init_rmsnorm(d, dtype)
            p["cross"] = init_attention(ks[2], cfg, cross=True, dtype=dtype)
        return p
    if kind == "mamba2":
        return {"ln1": init_rmsnorm(d, dtype),
                "mamba": ssm_mod.init_mamba2(ks[0], cfg, dtype)}
    if kind == "rwkv6":
        return {"ln1": init_rmsnorm(d, dtype),
                "ln2": init_rmsnorm(d, dtype),
                "rwkv": rwkv_mod.init_rwkv6(ks[0], cfg, dtype)}
    raise ValueError(kind)


def _ffn_scope(params: Params, cfg: ModelConfig) -> str:
    if "router" in params:
        return "moe"
    return "dense_mlp" if cfg.moe is not None else "mlp"


def _ffn(params: Params, cfg: ModelConfig, x: jnp.ndarray, *,
         train: bool = False, row_mask: jnp.ndarray | None = None):
    """x [B, S, d] -> (y, MoE aux loss, routed counts or None).  A MoE
    runs dropless (``moe_dropless``; ``routed`` counts its rows that
    ``row_mask`` [B] keeps), except in training with a capacity
    factor."""
    zero = jnp.zeros((), jnp.float32)
    if "router" not in params:
        return mlp_apply(params, x, cfg.act), zero, None
    moe = cfg.moe
    if train and moe.capacity_factor > 0:
        assert moe.held is None and not moe.num_shared, \
            "the capacity path holds every expert and no shared ones"
        y, aux = moe_apply(params, cfg, x)
        return y, aux, None
    b, s, d = x.shape
    mask = None if row_mask is None else jnp.repeat(row_mask, s)
    y, routed = moe_dropless(params, cfg, x.reshape(b * s, d), mask)
    return y.reshape(b, s, d), zero, routed


def block_apply(params: Params, cfg: ModelConfig, kind: BlockKind,
                x: jnp.ndarray, positions: jnp.ndarray,
                enc_memory: jnp.ndarray | None = None,
                causal: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence path. Returns (x, moe_aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attention", "shared_attention"):
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        if cfg.mla is not None:
            x = x + mla_apply(params["attn"], cfg, h, positions)[0]
        else:
            x = x + attention_apply(params["attn"], cfg, h, positions,
                                    causal=causal)
        if "cross" in params and enc_memory is not None:
            h = rmsnorm_apply(params["ln_cross"], x, cfg.norm_eps)
            x = x + attention_apply(params["cross"], cfg, h, positions,
                                    causal=False, kv_input=enc_memory)
        h = rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
        y, aux, _ = _ffn(params["ffn"], cfg, h, train=True)
        return x + y, aux
    if kind == "mamba2":
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        return x + ssm_mod.mamba2_apply(params["mamba"], cfg, h), aux
    if kind == "rwkv6":
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        x = x + rwkv_mod.rwkv6_time_mix_apply(params["rwkv"], cfg, h)
        h = rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
        return x + rwkv_mod.rwkv6_channel_mix_apply(params["rwkv"], cfg, h), aux
    raise ValueError(kind)


def block_prefill_apply(params: Params, cfg: ModelConfig, kind: BlockKind,
                        x: jnp.ndarray, positions: jnp.ndarray,
                        max_len: int,
                        enc_memory: jnp.ndarray | None = None,
                        cache_dtype=jnp.bfloat16,
                        length: jnp.ndarray | None = None
                        ) -> tuple[jnp.ndarray, Cache]:
    """Parallel prefill: full-sequence block + cache capture.

    ``length`` (traced scalar): real token count when the input is
    right-padded to a shape bucket — see ``attention_prefill_apply``."""
    if kind in ("attention", "shared_attention"):
        with jax.named_scope("attn"):
            h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
            if cfg.mla is not None:
                y, rows = mla_apply(params["attn"], cfg, h, positions)
                pad = max_len - rows.shape[1]
                cache = {"ckv": jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
                         .astype(cache_dtype)}
            else:
                y, k_c, v_c = attention_prefill_apply(
                    params["attn"], cfg, h, positions, max_len, cache_dtype,
                    length=length)
                cache = {"k": k_c, "v": v_c}
            x = x + y
        if "cross" in params and enc_memory is not None:
            h = rmsnorm_apply(params["ln_cross"], x, cfg.norm_eps)
            x = x + attention_apply(params["cross"], cfg, h, positions,
                                    causal=False, kv_input=enc_memory)
        with jax.named_scope(_ffn_scope(params["ffn"], cfg)):
            h = rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
            y, _, _ = _ffn(params["ffn"], cfg, h)
            return x + y, cache
    if kind == "mamba2":
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        y, cache = ssm_mod.mamba2_apply(params["mamba"], cfg, h,
                                        return_state=True)
        return x + y, cache
    if kind == "rwkv6":
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        y, wkv_state = rwkv_mod.rwkv6_time_mix_apply(
            params["rwkv"], cfg, h, return_state=True)
        tshift = h[:, -1:]
        x = x + y
        h2 = rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
        y2 = rwkv_mod.rwkv6_channel_mix_apply(params["rwkv"], cfg, h2)
        return x + y2, {"wkv": wkv_state, "tshift": tshift,
                        "cshift": h2[:, -1:]}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# per-block KV / recurrent caches
# ---------------------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, kind: BlockKind, batch: int,
                     max_len: int, dtype=jnp.bfloat16) -> Cache:
    if kind in ("attention", "shared_attention"):
        if cfg.mla is not None:
            return {"ckv": jnp.zeros((batch, max_len, cache_row(cfg)), dtype)}
        h = cfg.resolved_head_dim
        size = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        return {
            "k": jnp.zeros((batch, size, cfg.num_kv_heads, h), dtype),
            "v": jnp.zeros((batch, size, cfg.num_kv_heads, h), dtype),
        }
    if kind == "mamba2":
        return ssm_mod.init_mamba2_cache(cfg, batch, dtype)
    if kind == "rwkv6":
        return rwkv_mod.init_rwkv6_cache(cfg, batch, dtype)
    raise ValueError(kind)


def block_decode_apply(params: Params, cfg: ModelConfig, kind: BlockKind,
                       x: jnp.ndarray, cache: Cache, pos: jnp.ndarray, *,
                       enc_memory: jnp.ndarray | None = None
                       ) -> tuple[jnp.ndarray, Cache]:
    """Single-token decode. x [B,1,d]; pos [B]."""
    if kind in ("attention", "shared_attention"):
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        if cfg.mla is not None:
            y, rows = mla_decode(params["attn"], cfg, h, cache["ckv"], pos)
            cache = {**cache, "ckv": rows}
        else:
            y, k, v = attention_decode_apply(
                params["attn"], cfg, h, cache["k"], cache["v"], pos)
            cache = {**cache, "k": k, "v": v}
        x = x + y
        if "cross" in params and enc_memory is not None:
            h = rmsnorm_apply(params["ln_cross"], x, cfg.norm_eps)
            x = x + attention_apply(params["cross"], cfg, h, pos[:, None],
                                    causal=False, kv_input=enc_memory)
        h = rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
        y, _, _ = _ffn(params["ffn"], cfg, h)
        return x + y, cache
    if kind == "mamba2":
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        y, cache = ssm_mod.mamba2_decode_apply(params["mamba"], cfg, h, cache)
        return x + y, cache
    if kind == "rwkv6":
        h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        y, cache = rwkv_mod.rwkv6_decode_apply(params["rwkv"], cfg, h, cache)
        x = x + y
        h = rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
        y = rwkv_mod._channel_mix(params["rwkv"], cfg, h, cache["cshift"])
        cache = {**cache, "cshift": h}
        return x + y, cache
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack: scan over pattern periods
# ---------------------------------------------------------------------------

def _pattern_layout(cfg: ModelConfig, num_layers: int):
    pattern = cfg.block_pattern
    p = len(pattern)
    return pattern, num_layers // p, num_layers % p


def _lead_keys(tree: Params) -> list[str]:
    """The leading dense layers of a params or cache tree, in order."""
    return sorted(tree.get("lead", {}), key=int)


def init_stack(key, cfg: ModelConfig, *, num_layers: int | None = None,
               cross: bool = False, pattern_override=None,
               dtype=jnp.float32) -> Params:
    num_layers = cfg.num_layers if num_layers is None else num_layers
    lead = 0 if pattern_override else cfg.first_dense
    num_layers -= lead
    cfg_pattern, n_periods, rem = _pattern_layout(cfg, num_layers)
    pattern = pattern_override or cfg_pattern
    if pattern_override:
        pattern, n_periods, rem = pattern_override, num_layers // len(
            pattern_override), num_layers % len(pattern_override)
    keys = jax.random.split(key, len(pattern) * (n_periods + 1) + 1)
    ki = iter(range(len(keys)))
    params: Params = {"stack": {}, "rem": {}}
    has_shared = any(k == "shared_attention" for k in pattern)
    if has_shared:
        params["shared_attn"] = init_block(
            keys[next(ki)], cfg, "shared_attention", cross=cross, dtype=dtype)
    for pos, kind in enumerate(pattern):
        if kind == "shared_attention":
            continue  # tied
        if n_periods > 0:
            stacked = [
                init_block(keys[next(ki)], cfg, kind, cross=cross, dtype=dtype)
                for _ in range(n_periods)
            ]
            params["stack"][str(pos)] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *stacked)
        if pos < rem:
            params["rem"][str(pos)] = init_block(
                keys[next(ki)], cfg, kind, cross=cross, dtype=dtype)
    if lead:
        params["lead"] = {str(i): init_block(
            jax.random.fold_in(key, (1 << 20) + i), cfg, "attention",
            cross=cross, dtype=dtype, dense_ffn=True) for i in range(lead)}
    return params


def stack_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, *, num_layers: int | None = None,
                pattern_override=None, enc_memory: jnp.ndarray | None = None,
                causal: bool = True, remat: bool = False
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence stack. Returns (x, total_moe_aux)."""
    num_layers = cfg.num_layers if num_layers is None else num_layers
    lead = _lead_keys(params)
    num_layers -= len(lead)
    pattern = pattern_override or cfg.block_pattern
    n_periods, rem = num_layers // len(pattern), num_layers % len(pattern)

    block = block_apply
    if remat:
        block = jax.checkpoint(
            block_apply, static_argnums=(1, 2, 6),
            policy=jax.checkpoint_policies.nothing_saveable)

    def period_body(carry, period_params):
        h, aux = carry
        for pos, kind in enumerate(pattern):
            bp = (params["shared_attn"] if kind == "shared_attention"
                  else period_params[str(pos)])
            h = shard_act(h, "batch", None, None)  # pin residual stream
            h, a = block(bp, cfg, kind, h, positions, enc_memory, causal)
            aux = aux + a
        return (h, aux), None

    aux0 = jnp.zeros((), jnp.float32)
    for key in lead:
        x, a = block(params["lead"][key], cfg, "attention", x, positions,
                     enc_memory, causal)
        aux0 = aux0 + a
    if n_periods > 0:
        (x, aux0), _ = jax.lax.scan(period_body, (x, aux0), params["stack"])
    for pos in range(rem):
        kind = pattern[pos]
        bp = (params["shared_attn"] if kind == "shared_attention"
              else params["rem"][str(pos)])
        x, a = block(bp, cfg, kind, x, positions, enc_memory, causal)
        aux0 = aux0 + a
    return x, aux0


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int,
                     *, num_layers: int | None = None,
                     dtype=jnp.bfloat16) -> Cache:
    num_layers = cfg.num_layers if num_layers is None else num_layers
    pattern, n_periods, rem = _pattern_layout(
        cfg, num_layers - cfg.first_dense)
    cache: Cache = {"stack": {}, "rem": {}}
    if cfg.first_dense:
        cache["lead"] = {str(i): init_block_cache(
            cfg, "attention", batch, max_len, dtype)
            for i in range(cfg.first_dense)}
    for pos, kind in enumerate(pattern):
        one = init_block_cache(cfg, kind, batch, max_len, dtype)
        if n_periods > 0:
            cache["stack"][str(pos)] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (n_periods,) + a.shape).copy(), one)
        if pos < rem:
            cache["rem"][str(pos)] = one
    return cache


def stack_prefill(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, max_len: int, *,
                  num_layers: int | None = None,
                  enc_memory: jnp.ndarray | None = None,
                  cache_dtype=jnp.bfloat16,
                  length: jnp.ndarray | None = None
                  ) -> tuple[jnp.ndarray, Cache]:
    """Parallel prefill through the stack, emitting the decode cache."""
    num_layers = cfg.num_layers if num_layers is None else num_layers
    lead = _lead_keys(params)
    pattern, n_periods, rem = _pattern_layout(cfg, num_layers - len(lead))
    lead_cache = {}
    for key in lead:
        x, lead_cache[key] = block_prefill_apply(
            params["lead"][key], cfg, "attention", x, positions, max_len,
            enc_memory, cache_dtype, length)

    def period_body(h, period_params):
        caches = {}
        for p_idx, kind in enumerate(pattern):
            bp = (params["shared_attn"] if kind == "shared_attention"
                  else period_params[str(p_idx)])
            h, caches[str(p_idx)] = block_prefill_apply(
                bp, cfg, kind, h, positions, max_len, enc_memory,
                cache_dtype, length)
        return h, caches

    if n_periods > 0:
        x, stack_cache = jax.lax.scan(period_body, x, params["stack"])
    else:
        stack_cache = {}
    rem_cache = {}
    for p_idx in range(rem):
        kind = pattern[p_idx]
        bp = (params["shared_attn"] if kind == "shared_attention"
              else params["rem"][str(p_idx)])
        x, rem_cache[str(p_idx)] = block_prefill_apply(
            bp, cfg, kind, x, positions, max_len, enc_memory, cache_dtype,
            length)
    out = {"stack": stack_cache, "rem": rem_cache}
    if lead:
        out["lead"] = lead_cache
    return x, out


def stack_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                 cache: Cache, pos: jnp.ndarray, *,
                 num_layers: int | None = None,
                 enc_memory: jnp.ndarray | None = None
                 ) -> tuple[jnp.ndarray, Cache]:
    """Single-token decode through the whole stack."""
    num_layers = cfg.num_layers if num_layers is None else num_layers
    lead = _lead_keys(params)
    pattern, n_periods, rem = _pattern_layout(cfg, num_layers - len(lead))
    new_lead_cache = {}
    for key in lead:
        x, new_lead_cache[key] = block_decode_apply(
            params["lead"][key], cfg, "attention", x, cache["lead"][key],
            pos, enc_memory=enc_memory)

    def period_body(h, inp):
        period_params, period_cache = inp
        new_cache = {}
        for p_idx, kind in enumerate(pattern):
            bp = (params["shared_attn"] if kind == "shared_attention"
                  else period_params.get(str(p_idx)))
            h, new_cache[str(p_idx)] = block_decode_apply(
                bp, cfg, kind, h, period_cache[str(p_idx)], pos,
                enc_memory=enc_memory)
        return h, new_cache

    if n_periods > 0:
        # params["stack"] lacks shared_attention positions; cache has all.
        x, new_stack_cache = jax.lax.scan(
            period_body, x, (params["stack"], cache["stack"]))
    else:
        new_stack_cache = cache["stack"]
    new_rem_cache = {}
    for p_idx in range(rem):
        kind = pattern[p_idx]
        bp = (params["shared_attn"] if kind == "shared_attention"
              else params["rem"][str(p_idx)])
        x, new_rem_cache[str(p_idx)] = block_decode_apply(
            bp, cfg, kind, x, cache["rem"][str(p_idx)], pos,
            enc_memory=enc_memory)
    out = {"stack": new_stack_cache, "rem": new_rem_cache}
    if lead:
        out["lead"] = new_lead_cache
    return x, out


# ---------------------------------------------------------------------------
# paged stack: attention KV in a global page pool, recurrent state per slot
# ---------------------------------------------------------------------------

def attention_only_pattern(cfg: ModelConfig) -> bool:
    """True iff every block in the pattern carries a KV cache (no
    recurrent state) — the precondition for chunked prefill."""
    return all(k in ("attention", "shared_attention")
               for k in cfg.block_pattern)


def init_block_cache_paged(cfg: ModelConfig, kind: BlockKind, slots: int,
                           num_pages: int, page_size: int,
                           dtype=jnp.bfloat16) -> Cache:
    """Per-block cache for the paged engine: attention kinds get a global
    page pool ``[P, NK, page, H]`` shared by all slots (page 0 reserved
    as write scratch) — latent attention one pool ``[P, page, row]`` of
    latent rows; recurrent kinds keep per-slot state rows."""
    if kind in ("attention", "shared_attention"):
        if cfg.mla is not None:
            return {"ckv": jnp.zeros((num_pages, page_size, cache_row(cfg)),
                                     dtype)}
        h = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((num_pages, cfg.num_kv_heads, page_size, h), dtype),
            "v": jnp.zeros((num_pages, cfg.num_kv_heads, page_size, h), dtype),
        }
    if kind == "mamba2":
        return ssm_mod.init_mamba2_cache(cfg, slots, dtype)
    if kind == "rwkv6":
        return rwkv_mod.init_rwkv6_cache(cfg, slots, dtype)
    raise ValueError(kind)


def init_stack_cache_paged(cfg: ModelConfig, slots: int, num_pages: int,
                           page_size: int, *, num_layers: int | None = None,
                           dtype=jnp.bfloat16) -> Cache:
    """The paged cache; with a MoE also ``moe_routed`` [E_held] int32,
    the (token, held expert) pairs of the decode steps' active rows,
    summed over layers and steps."""
    num_layers = cfg.num_layers if num_layers is None else num_layers
    pattern, n_periods, rem = _pattern_layout(
        cfg, num_layers - cfg.first_dense)
    cache: Cache = {"stack": {}, "rem": {}}
    if cfg.first_dense:
        cache["lead"] = {str(i): init_block_cache_paged(
            cfg, "attention", slots, num_pages, page_size, dtype)
            for i in range(cfg.first_dense)}
    if cfg.moe is not None:
        cache["moe_routed"] = jnp.zeros((cfg.moe.held_range[1],), jnp.int32)
    for pos, kind in enumerate(pattern):
        one = init_block_cache_paged(cfg, kind, slots, num_pages, page_size,
                                     dtype)
        if n_periods > 0:
            cache["stack"][str(pos)] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (n_periods,) + a.shape).copy(), one)
        if pos < rem:
            cache["rem"][str(pos)] = one
    return cache


def _mask_recurrent(new: Cache, old: Cache, active: jnp.ndarray) -> Cache:
    """Freeze inactive slots' recurrent state (batch axis 0 per leaf):
    attention writes self-redirect to the scratch page, but recurrent
    blocks mutate their whole state row every step."""
    def leaf(n, o):
        m = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(leaf, new, old)


def block_decode_paged(params: Params, cfg: ModelConfig, kind: BlockKind,
                       x: jnp.ndarray, cache: Cache, pos: jnp.ndarray,
                       block_tables: jnp.ndarray, active: jnp.ndarray, *,
                       max_len: int, page_offset: jnp.ndarray | int = 0
                       ) -> tuple[jnp.ndarray, Cache, jnp.ndarray | None]:
    """Single-token decode with paged attention KV. x [B,1,d]; pos [B];
    block_tables [B,NP]; active [B] bool; ``page_offset`` locates the
    layer's pages in a pool that holds several layers.  Returns (x, the
    block's cache, the MoE's routed counts of the active rows or
    None)."""
    if kind in ("attention", "shared_attention"):
        w = cfg.sliding_window
        cap = min(max_len, w) if w > 0 else max_len
        with jax.named_scope("attn"):
            h = rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
            if cfg.mla is not None:
                y, rows = mla_decode_paged(
                    params["attn"], cfg, h, cache["ckv"], pos, block_tables,
                    active, kv_capacity=cap, page_offset=page_offset)
                new_cache = {"ckv": rows}
            else:
                y, pk, pv = attention_decode_paged(
                    params["attn"], cfg, h, cache["k"], cache["v"], pos,
                    block_tables, active, kv_capacity=cap,
                    page_offset=page_offset)
                new_cache = {"k": pk, "v": pv}
            x = x + y
        with jax.named_scope(_ffn_scope(params["ffn"], cfg)):
            h = rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
            y, _, routed = _ffn(params["ffn"], cfg, h, row_mask=active)
            return x + y, new_cache, routed
    x, new_cache = block_decode_apply(params, cfg, kind, x, cache, pos)
    return x, _mask_recurrent(new_cache, cache, active), None


def stack_decode_paged(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                       cache: Cache, pos: jnp.ndarray,
                       block_tables: jnp.ndarray, active: jnp.ndarray, *,
                       max_len: int, num_layers: int | None = None
                       ) -> tuple[jnp.ndarray, Cache]:
    """Single-token decode through the stack against paged KV pools.

    Every layer shares one block table per request: tables index each
    layer's own pool with identical page ids, so admit/evict move O(1)
    table rows instead of O(layers) cache slices.

    The layer scan carries each stacked pool ``[n_periods, P, ...]``
    whole, viewed as ``[n_periods * P, ...]``, and layer ``i`` reads and
    writes it at page offset ``i * P``: the new token's K/V row is
    scattered into the carried buffer in place, where passing the pools
    as scan inputs and outputs would slice, copy and restack every
    layer's pool on every step.  Recurrent state rows stay scan inputs
    and outputs; they are small.  Leading dense layers run first, each on
    its own pool; a MoE's routed counts add into ``moe_routed``."""
    num_layers = cfg.num_layers if num_layers is None else num_layers
    lead = _lead_keys(params)
    pattern, n_periods, rem = _pattern_layout(cfg, num_layers - len(lead))
    paged = {str(i) for i, k in enumerate(pattern)
             if k in ("attention", "shared_attention")}
    routed = cache.get("moe_routed")

    def count(total, new):
        return total if new is None else total + new

    new_lead_cache = {}
    for key in lead:
        x, new_lead_cache[key], r = block_decode_paged(
            params["lead"][key], cfg, "attention", x, cache["lead"][key],
            pos, block_tables, active, max_len=max_len)
        routed = count(routed, r)

    def period_body(carry, inp):
        h, pools, routed = carry
        layer, period_params, period_state = inp
        pools, new_state = dict(pools), {}
        for p_idx, kind in enumerate(pattern):
            key = str(p_idx)
            bp = (params["shared_attn"] if kind == "shared_attention"
                  else period_params.get(key))
            if key in paged:
                n_pages = jax.tree.leaves(pools[key])[0].shape[0] // n_periods
                h, pools[key], r = block_decode_paged(
                    bp, cfg, kind, h, pools[key], pos, block_tables,
                    active, max_len=max_len, page_offset=layer * n_pages)
            else:
                h, new_state[key], r = block_decode_paged(
                    bp, cfg, kind, h, period_state[key], pos,
                    block_tables, active, max_len=max_len)
            routed = count(routed, r)
        return (h, pools, routed), new_state

    if n_periods > 0:
        stack = cache["stack"]
        pools = {key: jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), stack[key])
            for key in paged}
        state = {key: c for key, c in stack.items() if key not in paged}
        (x, pools, routed), new_stack_cache = jax.lax.scan(
            period_body, (x, pools, routed),
            (jnp.arange(n_periods, dtype=jnp.int32), params["stack"],
             state))
        for key in paged:
            new_stack_cache[key] = jax.tree.map(
                lambda a: a.reshape((n_periods, -1) + a.shape[1:]),
                pools[key])
    else:
        new_stack_cache = cache["stack"]
    new_rem_cache = {}
    for p_idx in range(rem):
        kind = pattern[p_idx]
        bp = (params["shared_attn"] if kind == "shared_attention"
              else params["rem"][str(p_idx)])
        x, new_rem_cache[str(p_idx)], r = block_decode_paged(
            bp, cfg, kind, x, cache["rem"][str(p_idx)], pos,
            block_tables, active, max_len=max_len)
        routed = count(routed, r)
    out = {"stack": new_stack_cache, "rem": new_rem_cache}
    if lead:
        out["lead"] = new_lead_cache
    if routed is not None:
        out["moe_routed"] = routed
    return x, out


def stack_prefill_chunk(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                        cache: Cache, block_table: jnp.ndarray,
                        ctx_len: jnp.ndarray, n_valid: jnp.ndarray, *,
                        num_layers: int | None = None) -> tuple[jnp.ndarray, Cache]:
    """One prompt chunk through an attention-only stack, scattering K/V
    straight into the request's pages.  x [1,C,d]; block_table [NP];
    ctx_len/n_valid scalars.  Dense attention only (asserted upstream),
    no leading dense layers."""
    assert not _lead_keys(params)
    num_layers = cfg.num_layers if num_layers is None else num_layers
    pattern, n_periods, rem = _pattern_layout(cfg, num_layers)

    def chunk_block(bp, h, blk_cache):
        hn = rmsnorm_apply(bp["ln1"], h, cfg.norm_eps)
        y, pk, pv = attention_prefill_chunk(
            bp["attn"], cfg, hn, blk_cache["k"], blk_cache["v"],
            block_table, ctx_len, n_valid)
        h = h + y
        hn = rmsnorm_apply(bp["ln2"], h, cfg.norm_eps)
        y, _, _ = _ffn(bp["ffn"], cfg, hn)
        return h + y, {"k": pk, "v": pv}

    def period_body(h, inp):
        period_params, period_cache = inp
        new_cache = {}
        for p_idx, kind in enumerate(pattern):
            bp = (params["shared_attn"] if kind == "shared_attention"
                  else period_params.get(str(p_idx)))
            h, new_cache[str(p_idx)] = chunk_block(
                bp, h, period_cache[str(p_idx)])
        return h, new_cache

    if n_periods > 0:
        x, new_stack_cache = jax.lax.scan(
            period_body, x, (params["stack"], cache["stack"]))
    else:
        new_stack_cache = cache["stack"]
    new_rem_cache = {}
    for p_idx in range(rem):
        kind = pattern[p_idx]
        bp = (params["shared_attn"] if kind == "shared_attention"
              else params["rem"][str(p_idx)])
        x, new_rem_cache[str(p_idx)] = chunk_block(
            bp, x, cache["rem"][str(p_idx)])
    return x, {"stack": new_stack_cache, "rem": new_rem_cache}
