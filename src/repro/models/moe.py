"""Mixture-of-experts FFN: a dropless path for serving and a
fixed-capacity path for training.

``moe_dropless`` (prefill, decode, and training where
``capacity_factor`` is 0) routes every token over all experts and
computes the experts this chip holds (``MoEConfig.held``) for every
token routed to them: the (token, expert) pairs sort by held expert,
each expert's rows pad to whole tiles, and one grouped SwiGLU
(``repro.kernels.ops.moe_experts``) runs over the tiles in use.  No
token is dropped, so pad tokens never change real tokens' outputs.
Pairs routed to experts held elsewhere add nothing here: on a chip of
an expert-parallel deployment that is the chip's part of the layer.
Shared experts run for every token.  The router is float32 at the
highest matmul precision (sigmoid or softmax scores, an optional
correction bias that picks the top-k but does not weight them,
normalised and scaled top-k weights).

``moe_apply`` is the fixed-capacity path of training (every expert
held, softmax routing).  Routing works on *grouped tokens* ``[G, N, d]``
(G = batch rows, N = seq).  Dispatch builds per-expert
buffers ``[G, E, C, d]`` via argsort + gather — no [tokens, E, C] one-hot
tensor is ever materialized, so the dispatch scales to 64-expert configs.

Sharding (see repro.sharding.specs): expert weights are sharded over the
``model`` axis on the expert dim when E % model == 0 (expert parallelism;
the dispatch reshard lowers to an all-to-all), otherwise on d_ff
(tensor parallelism within every expert).

The router epilogue (softmax → top-k → normalize → scatter of combine
weights) is a memory-bound value chain — a near-bank offload target
(see repro.core.offload).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import (
    Params,
    activation,
    dense_init,
    init_mlp,
    mlp_apply,
    round_up,
)
from repro.sharding.constraints import shard_act


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Router over all experts, the held experts' matrices ([E_held, d,
    f] gate and up, [E_held, f, d] down), the correction bias and the
    shared experts where the config has them."""
    moe = cfg.moe
    assert moe is not None
    d, f, e = cfg.d_model, moe.expert_width(cfg.d_ff), moe.num_experts
    n = moe.held_range[1]
    ks = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, e, dtype),
        "gate": (jax.random.normal(ks[1], (n, d, f)) * scale).astype(dtype),
        "up": (jax.random.normal(ks[2], (n, d, f)) * scale).astype(dtype),
        "down": (jax.random.normal(ks[3], (n, f, d)) / jnp.sqrt(f)).astype(dtype),
    }
    if moe.score_bias:
        p["router_bias"] = jnp.zeros((e,), dtype)
    if moe.num_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), d, moe.num_shared * f, dtype=dtype)
    return p


def capacity(n_tokens: int, moe: MoEConfig) -> int:
    c = int(n_tokens * moe.top_k * moe.capacity_factor / moe.num_experts)
    return max(moe.top_k, min(n_tokens, max(1, c)))


def route(logits: jnp.ndarray, moe: MoEConfig):
    """logits [..., E] -> (weights [..., k], experts [..., k], aux_loss)."""
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topw, topi = jax.lax.top_k(gates, moe.top_k)
    topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * mean(fraction routed) . mean(gate)
    e = moe.num_experts
    onehot = jax.nn.one_hot(topi[..., 0], e)  # primary assignment
    density = jnp.mean(onehot.reshape(-1, e), axis=0)
    mean_gate = jnp.mean(gates.reshape(-1, e), axis=0)
    aux = e * jnp.sum(density * mean_gate)
    return topw, topi, aux


def _dispatch_indices(topi: jnp.ndarray, e: int, cap: int):
    """topi [N, k] -> (src_token [E*C] (=N for empty), slot_of [N, k], valid [N, k]).

    Pure index computation (the MPU 'address chain' — annotated far-bank
    by the locator; see DESIGN.md §2).
    """
    n, k = topi.shape
    flat_e = topi.reshape(-1)  # [N*k]
    order = jnp.argsort(flat_e, stable=True)  # token-slots grouped by expert
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(n * k) - starts[sorted_e]
    # invert the permutation: rank of each original (token, slot)
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    valid = rank < cap
    # scatter source token ids into [E*C]; dropped slots write nowhere
    dst = jnp.where(valid, flat_e * cap + rank, e * cap)  # overflow -> dropped
    src_token = jnp.full((e * cap + 1,), n, jnp.int32)  # default: pad token
    token_ids = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    src_token = src_token.at[dst].set(token_ids)[: e * cap]
    slot_of = jnp.where(valid, flat_e * cap + rank, e * cap).reshape(n, k)
    return src_token, slot_of, valid.reshape(n, k)


def _model_n() -> int:
    from repro.sharding.constraints import model_axis_size
    return model_axis_size()


def moe_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x [G, N, d] -> (y [G, N, d], aux_loss scalar)."""
    moe = cfg.moe
    assert moe is not None
    g, n, d = x.shape
    e = moe.num_experts
    cap = capacity(n, moe)

    logits = x @ params["router"].astype(x.dtype)  # [G, N, E]
    topw, topi, aux = route(logits, moe)  # [G,N,k] fp32, int, scalar

    src_token, slot_of, valid = jax.vmap(
        lambda t: _dispatch_indices(t, e, cap)
    )(topi)  # [G, E*C], [G, N, k], [G, N, k]

    xpad = jnp.concatenate([x, jnp.zeros((g, 1, d), x.dtype)], axis=1)
    xd = jnp.take_along_axis(
        xpad, src_token[..., None], axis=1
    ).reshape(g, e, cap, d)  # [G, E, C, d]
    ep = moe.num_experts % max(_model_n(), 1) == 0
    # EP: experts over model; TP fallback: d_ff over model (SPerf iter 3:
    # pin the dispatch buffers so the expert matmuls never replicate)
    xd = shard_act(xd, "batch", "experts" if ep else None, None, None)

    act = activation(cfg.act)
    wg = params["gate"].astype(x.dtype)
    wu = params["up"].astype(x.dtype)
    wd = params["down"].astype(x.dtype)
    h = act(jnp.einsum("gecd,edf->gecf", xd, wg)) * jnp.einsum(
        "gecd,edf->gecf", xd, wu)
    h = shard_act(h, "batch", "experts" if ep else None, None,
                  None if ep else "dff")
    yd = jnp.einsum("gecf,efd->gecd", h, wd)  # [G, E, C, d]
    yd = shard_act(yd, "batch", "experts" if ep else None, None, None)

    # combine: gather each token-slot's expert output, weight, and sum over k
    yflat = jnp.concatenate(
        [yd.reshape(g, e * cap, d), jnp.zeros((g, 1, d), yd.dtype)], axis=1)
    taken = jnp.take_along_axis(
        yflat, slot_of.reshape(g, n * moe.top_k)[..., None], axis=1
    ).reshape(g, n, moe.top_k, d)
    w = (topw * valid).astype(x.dtype)
    y = jnp.einsum("gnkd,gnk->gnd", taken, w)
    return y, aux.astype(jnp.float32) * moe.aux_loss_weight


def select_experts(params: Params, moe: MoEConfig, x: jnp.ndarray):
    """x [T, d] -> (weights [T, k] f32, experts [T, k]) over all
    ``num_experts``, the router in float32."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores
    if moe.score_bias:
        choice = scores + params["router_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, moe.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * moe.routed_scale, idx


def tile_rows(n_tokens: int, moe: MoEConfig) -> int:
    """Rows of one ``moe_experts`` tile: the pow2 at or above the rows a
    held expert gets on average, from 16 (a bf16 sublane tile) to 256."""
    mean = -(-n_tokens * moe.top_k // moe.num_experts)
    return min(256, max(16, 1 << max(0, mean - 1).bit_length()))


def moe_dropless(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                 row_mask: jnp.ndarray | None = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x [T, d] -> (y [T, d], routed [E_held] int32): the held experts'
    weighted part of the layer for every token routed to them, plus the
    shared experts; ``routed`` counts the (token, held expert) pairs of
    the rows ``row_mask`` keeps (every row without one)."""
    moe = cfg.moe
    t, d = x.shape
    k = moe.top_k
    first, n = moe.held_range
    with jax.named_scope("router"):
        w, idx = select_experts(params, moe, x)
    with jax.named_scope("dispatch"):
        local = idx - first
        held = (local >= 0) & (local < n)
        key = jnp.where(held, local, n).reshape(-1)         # [T*k]
        sizes = jnp.bincount(key, length=n + 1).astype(jnp.int32)
        tm = tile_rows(t, moe)
        padded = round_up(sizes[:n], tm)
        ends = jnp.cumsum(padded)
        starts_pad = jnp.concatenate([ends - padded,
                                      jnp.zeros((1,), jnp.int32)])
        starts = jnp.cumsum(sizes) - sizes
        order = jnp.argsort(key, stable=True)
        sorted_key = key[order]
        rank = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_key]
        m = round_up(t * min(k, n) + n * (tm - 1), tm)
        dest_sorted = jnp.where(sorted_key < n,
                                starts_pad[sorted_key] + rank, m)
        dest = jnp.zeros((t * k,), jnp.int32).at[order].set(dest_sorted)
        tokens = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        src = jnp.full((m + 1,), t, jnp.int32).at[dest].set(tokens)[:m]
        xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[src]
        n_tiles = (ends[-1] // tm).reshape(1)
        tile_group = jnp.minimum(jnp.searchsorted(
            ends // tm, jnp.arange(m // tm, dtype=jnp.int32), side="right"),
            n - 1).astype(jnp.int32)
    with jax.named_scope("experts"):
        from repro.kernels import ops as kops
        ys = kops.moe_experts(xs, tile_group, n_tiles, params["gate"],
                              params["up"], params["down"], tm=tm,
                              act=cfg.act)
    with jax.named_scope("combine"):
        # float32 products summed on the vector unit: a matmul would
        # round the routing weights to its input precision
        yk = ys[jnp.minimum(dest, m - 1)].reshape(t, k, d)
        yk = jnp.where(held[..., None], yk.astype(jnp.float32), 0.0)
        y = jnp.sum(yk * jnp.where(held, w, 0.0)[..., None],
                    axis=1).astype(x.dtype)
    if "shared" in params:
        with jax.named_scope("shared"):
            y = y + mlp_apply(params["shared"], x, cfg.act)
    keep = held if row_mask is None else held & row_mask[:, None]
    routed = jnp.bincount(jnp.where(keep, local, n).reshape(-1),
                          length=n + 1)[:n].astype(jnp.int32)
    return y, routed


def reference_moe(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Dense oracle: every token through every expert, weighted by the
    (capacity-unlimited) router — used by tests with cap >= N."""
    moe = cfg.moe
    logits = x @ params["router"].astype(x.dtype)
    topw, topi, _ = route(logits, moe)
    act = activation(cfg.act)
    h = act(jnp.einsum("gnd,edf->gnef", x, params["gate"].astype(x.dtype))) * \
        jnp.einsum("gnd,edf->gnef", x, params["up"].astype(x.dtype))
    y_all = jnp.einsum("gnef,efd->gned", h, params["down"].astype(x.dtype))
    k_onehot = jax.nn.one_hot(topi, moe.num_experts, dtype=jnp.float32)  # [G,N,k,E]
    w_e = jnp.einsum("gnk,gnke->gne", topw, k_onehot).astype(x.dtype)
    return jnp.einsum("gned,gne->gnd", y_all, w_e)
