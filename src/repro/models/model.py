"""Public model API: build any assigned architecture from its config.

``build_model(cfg)`` returns a ``Model`` with four pure functions:

    init(rng)                                   -> params
    loss_fn(params, batch)                      -> (loss, metrics)
    prefill(params, batch, max_len)             -> (last_logits, cache)
    decode_step(params, cache, token, pos, ...) -> (logits, cache)

Batch layout (all arrays are *global*; sharding is applied by the caller):

    decoder-only:      {tokens [B,S], labels [B,S], mask [B,S]}
    + frontend (vlm):  {"frontend": [B,F,D]} prefix embeddings
    enc-dec (audio):   {"frontend": [B,F,D]} encoder input; tokens decode side
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    Params,
    cross_entropy_loss,
    embed_apply,
    init_embedding,
    init_rmsnorm,
    lm_head_apply,
    rmsnorm_apply,
)
from repro.models.transformer import (
    Cache,
    attention_only_pattern,
    init_stack,
    init_stack_cache,
    init_stack_cache_paged,
    stack_apply,
    stack_decode,
    stack_decode_paged,
    stack_prefill,
    stack_prefill_chunk,
)


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable[..., Params]
    loss_fn: Callable[..., tuple[jnp.ndarray, dict]]
    forward: Callable[..., jnp.ndarray]
    prefill: Callable[..., tuple[jnp.ndarray, Cache]]
    decode_step: Callable[..., tuple[jnp.ndarray, Cache]]
    init_cache: Callable[..., Cache]
    # paged serving surface (continuous batching engine)
    init_paged_cache: Callable[..., Cache]
    decode_step_paged: Callable[..., tuple[jnp.ndarray, Cache]]
    prefill_chunk: Callable[..., tuple[jnp.ndarray, Cache]]


def _compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def build_model(cfg: ModelConfig) -> Model:
    dtype = _compute_dtype(cfg)
    is_encdec = cfg.kind == "encoder_decoder"
    has_frontend = cfg.frontend != "none"

    # ---------------- init ----------------
    def init(rng) -> Params:
        k_emb, k_enc, k_dec = jax.random.split(rng, 3)
        params: Params = {
            "embed": init_embedding(
                k_emb, cfg.vocab_size, cfg.d_model, tie=cfg.tie_embeddings),
            "final_ln": init_rmsnorm(cfg.d_model),
            "decoder": init_stack(k_dec, cfg, cross=is_encdec),
        }
        if is_encdec:
            params["encoder"] = init_stack(
                k_enc, cfg, num_layers=cfg.enc_num_layers,
                pattern_override=("attention",))
            params["enc_ln"] = init_rmsnorm(cfg.d_model)
        return params

    # ---------------- encoder ----------------
    def encode(params: Params, enc_input: jnp.ndarray) -> jnp.ndarray:
        """enc_input [B,F,D] (frontend stub embeddings)."""
        b, f, _ = enc_input.shape
        positions = jnp.broadcast_to(jnp.arange(f)[None], (b, f))
        h, _ = stack_apply(
            params["encoder"], cfg, enc_input.astype(dtype), positions,
            num_layers=cfg.enc_num_layers, pattern_override=("attention",),
            causal=False)
        return rmsnorm_apply(params["enc_ln"], h, cfg.norm_eps)

    # ---------------- full forward (train / prefill body) ----------------
    def forward(params: Params, batch: dict, *, remat: bool = False
                ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
        """Returns (hidden [B,S',D], aux, text_offset).

        S' = S (+ frontend prefix for decoder-prefix frontends)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_apply(params["embed"], tokens, dtype)
        enc_memory = None
        offset = 0
        if is_encdec:
            enc_memory = encode(params, batch["frontend"].astype(dtype))
        elif has_frontend:
            prefix = batch["frontend"].astype(dtype)
            x = jnp.concatenate([prefix, x], axis=1)
            offset = prefix.shape[1]
        s_total = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s_total)[None], (b, s_total))
        h, aux = stack_apply(params["decoder"], cfg, x, positions,
                             enc_memory=enc_memory, remat=remat)
        h = rmsnorm_apply(params["final_ln"], h, cfg.norm_eps)
        return h, aux, offset

    # ---------------- loss ----------------
    def loss_fn(params: Params, batch: dict, *, remat: bool = True
                ) -> tuple[jnp.ndarray, dict]:
        h, aux, offset = forward(params, batch, remat=remat)
        h = h[:, offset:]
        logits = lm_head_apply(params["embed"], h, cfg.vocab_size)
        loss = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
        total = loss + aux
        return total, {"loss": loss, "moe_aux": aux,
                       "tokens": jnp.asarray(batch["tokens"].size, jnp.float32)}

    # ---------------- serving ----------------
    def init_cache(batch: int, max_len: int) -> Cache:
        return init_stack_cache(cfg, batch, max_len, dtype=dtype)

    def prefill(params: Params, batch: dict, max_len: int,
                length: jnp.ndarray | None = None
                ) -> tuple[jnp.ndarray, Cache]:
        """Parallel prefill: one full-sequence pass that computes the last
        token's logits AND captures the decode cache (KV / SSM / WKV
        states) — the production prefill dataflow.

        ``length`` (traced scalar): real token count when ``tokens`` is
        right-padded to a shape bucket.  The last-token logits are read
        at the real end and the SWA rolling capture arranges by the real
        length, so one trace serves every prompt in the bucket."""
        with jax.named_scope("prefill"):
            tokens = batch["tokens"]
            b, s = tokens.shape
            x = embed_apply(params["embed"], tokens, dtype)
            enc_memory = None
            offset = 0
            if is_encdec:
                enc_memory = encode(params, batch["frontend"].astype(dtype))
            elif has_frontend:
                prefix = batch["frontend"].astype(dtype)
                x = jnp.concatenate([prefix, x], axis=1)
                offset = prefix.shape[1]
            s_total = x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(s_total)[None],
                                         (b, s_total))
            total_len = None if length is None else offset + length
            h, cache = stack_prefill(params["decoder"], cfg, x, positions,
                                     max_len, enc_memory=enc_memory,
                                     cache_dtype=dtype, length=total_len)
            if total_len is None:
                h_last = h[:, -1:]
            else:
                h_last = jax.lax.dynamic_slice_in_dim(h, total_len - 1, 1,
                                                      axis=1)
            with jax.named_scope("final_norm"):
                h_last = rmsnorm_apply(params["final_ln"], h_last,
                                       cfg.norm_eps)
            logits = lm_head_apply(params["embed"], h_last[:, 0],
                                   cfg.vocab_size)
            return logits, cache

    def decode_step(params: Params, cache: Cache, token: jnp.ndarray,
                    pos: jnp.ndarray, enc_memory: jnp.ndarray | None = None
                    ) -> tuple[jnp.ndarray, Cache]:
        """token [B] int32; pos [B] absolute positions."""
        x = embed_apply(params["embed"], token[:, None], dtype)
        h, cache = stack_decode(params["decoder"], cfg, x, cache, pos,
                                enc_memory=enc_memory)
        h = rmsnorm_apply(params["final_ln"], h, cfg.norm_eps)
        logits = lm_head_apply(params["embed"], h[:, 0], cfg.vocab_size)
        return logits, cache

    # ---------------- paged serving (continuous batching) ----------------
    def init_paged_cache(slots: int, num_pages: int, page_size: int) -> Cache:
        return init_stack_cache_paged(cfg, slots, num_pages, page_size,
                                      dtype=dtype)

    def decode_step_paged(params: Params, cache: Cache, token: jnp.ndarray,
                          pos: jnp.ndarray, block_tables: jnp.ndarray,
                          active: jnp.ndarray, *, max_len: int
                          ) -> tuple[jnp.ndarray, Cache]:
        """token/pos [B]; block_tables [B,NP]; active [B] bool.  Inactive
        rows compute but write only the reserved scratch page (attention)
        or freeze their state row (recurrent)."""
        with jax.named_scope("decode"):
            x = embed_apply(params["embed"], token[:, None], dtype)
            h, cache = stack_decode_paged(params["decoder"], cfg, x, cache,
                                          pos, block_tables, active,
                                          max_len=max_len)
            with jax.named_scope("final_norm"):
                h = rmsnorm_apply(params["final_ln"], h, cfg.norm_eps)
            logits = lm_head_apply(params["embed"], h[:, 0], cfg.vocab_size)
            return logits, cache

    def prefill_chunk(params: Params, cache: Cache, tokens: jnp.ndarray,
                      block_table: jnp.ndarray, ctx_len: jnp.ndarray,
                      n_valid: jnp.ndarray) -> tuple[jnp.ndarray, Cache]:
        """One prompt chunk [1, C] for a single request: scatter its K/V
        into the request's pages and return the logits at the chunk's
        last *real* token (meaningful only on the final chunk).  Dense
        attention-only decoder stacks (no SWA / frontend / enc-dec)."""
        assert not is_encdec and not has_frontend
        assert cfg.sliding_window == 0 and attention_only_pattern(cfg)
        x = embed_apply(params["embed"], tokens, dtype)
        h, cache = stack_prefill_chunk(params["decoder"], cfg, x, cache,
                                       block_table, ctx_len, n_valid)
        h_last = jax.lax.dynamic_slice_in_dim(
            h, jnp.maximum(n_valid - 1, 0), 1, axis=1)
        h_last = rmsnorm_apply(params["final_ln"], h_last, cfg.norm_eps)
        logits = lm_head_apply(params["embed"], h_last[:, 0], cfg.vocab_size)
        return logits, cache

    return Model(cfg, init, loss_fn, forward, prefill, decode_step,
                 init_cache, init_paged_cache, decode_step_paged,
                 prefill_chunk)
