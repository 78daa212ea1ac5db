"""Shared layers: norms, gated MLP, rotary embedding, token embedding.

Everything is a pure function over explicit parameter pytrees (nested
dicts of jnp arrays).  ``init_*`` functions build parameters; ``*_apply``
functions are jit-safe and shard-agnostic.  Compute dtype is bf16 by
default with fp32 accumulation at numerically sensitive points (norm
statistics, softmax, loss).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.sharding.constraints import shard_act

Params = dict[str, Any]

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def activation(name: str):
    return _ACTS[name]


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32) -> jnp.ndarray:
    scale = 1.0 / jnp.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm_apply(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm with fp32 statistics. The canonical near-bank value chain:
    one read of x, one write of y, trivial FLOPs — memory bound."""
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU family)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, *, gated: bool = True,
             dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "up": dense_init(k2, d_model, d_ff, dtype),
        "down": dense_init(k3, d_ff, d_model, dtype),
    }
    if gated:
        p["gate"] = dense_init(k1, d_model, d_ff, dtype)
    return p


def mlp_apply(params: Params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    u = x @ params["up"].astype(x.dtype)
    u = shard_act(u, "batch", None, "dff")
    if "gate" in params:
        g = x @ params["gate"].astype(x.dtype)
        g = shard_act(g, "batch", None, "dff")
        h = activation(act)(g) * u
    else:
        h = activation(act)(u)
    out = h @ params["down"].astype(x.dtype)
    return shard_act(out, "batch", None, None)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim//2]


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: [..., seq] (int32)."""
    freqs = rope_frequencies(x.shape[-1], theta)  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Token embedding + LM head
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, *, pad_to: int = 256,
                   tie: bool = False, dtype=jnp.float32) -> Params:
    """Embedding table padded to ``pad_to`` for clean vocab sharding."""
    padded = round_up(vocab, pad_to)
    k1, k2 = jax.random.split(key)
    params: Params = {"table": embed_init(k1, padded, d_model, dtype)}
    if not tie:
        params["head"] = dense_init(k2, d_model, padded, dtype)
    return params


def embed_apply(params: Params, tokens: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    with jax.named_scope("embed"):
        return params["table"].astype(dtype)[tokens]


def lm_head_apply(params: Params, x: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Returns fp32 logits truncated to the logical vocab size."""
    with jax.named_scope("lm_head"):
        if "head" in params:
            w = params["head"].astype(x.dtype)
            logits = x @ w
        else:
            logits = x @ params["table"].astype(x.dtype).T
        logits = shard_act(logits, "batch", *((None,) * (logits.ndim - 2)),
                           "vocab")
        return logits[..., :vocab].astype(jnp.float32)


def cross_entropy_loss(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Mean token cross-entropy in fp32. logits [B,S,V], labels [B,S]."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
