"""Flash attention (forward) as a Pallas TPU kernel.

Near-bank adaptation (DESIGN.md §2): softmax statistics and the output
accumulator live in VMEM scratch — MPU's "near-bank shared memory" — so
the [S, T] score matrix never exists in HBM; each KV block streams
through VMEM exactly once per query block (one "activated row-buffer"
per stream, multi-buffered by the Pallas pipeline).

Grid: (batch, kv_head, q_blocks, kv_blocks); the kv axis is the innermost
(sequential) dimension, accumulating online-softmax partials in scratch.
Causal/windowed blocks that are fully masked are skipped with ``pl.when``.

Layouts: q [B, NK, G*Qb..., H] is blocked per (batch, kv-head) so GQA
groups share the streamed KV block — the MXU matmul is [G*Qb, H]x[H, Kb].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                 l_ref, *, scale: float, causal: bool, window: int,
                 q_block: int, kv_block: int, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = qi * q_block
    k_start = ki * kv_block

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, Qb, H] -> fold G
        g, qb, h = q.shape
        q2 = q.reshape(g * qb, h)
        k = k_ref[0, 0].astype(jnp.float32)  # [Kb, H]
        v = v_ref[0, 0].astype(jnp.float32)  # [Kb, H]
        s = jax.lax.dot_general(
            q2, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G*Qb, Kb]
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (g, qb, kv_block), 1).reshape(g * qb, kv_block)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (g * qb, kv_block), 1)
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]  # [G*Qb]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [G*Qb, H]
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    # block-level relevance (skip fully-masked causal/window blocks)
    if causal or window > 0:
        relevant = jnp.asarray(True)
        q_last = q_start + q_block - 1
        if causal:
            relevant = jnp.logical_and(relevant, k_start <= q_last)
        if window > 0:
            relevant = jnp.logical_and(
                relevant, k_start + kv_block - 1 > q_start - window)
        pl.when(relevant)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        g, qb, h = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
        l = jnp.maximum(l_ref[...], 1e-37)[:, None]
        o_ref[0, 0] = (acc_ref[...] / l).reshape(g, qb, h).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = (m_ref[...] + jnp.log(l[:, 0])).reshape(g, qb)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "q_block", "kv_block",
                     "interpret", "return_lse"))
def flash_attention(
    q: jnp.ndarray,  # [B, S, NQ, H]
    k: jnp.ndarray,  # [B, T, NK, H]
    v: jnp.ndarray,  # [B, T, NK, H]
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    q_block: int = 256,
    kv_block: int = 256,
    interpret: bool = False,
    return_lse: bool = False,
):
    b, s, nq, h = q.shape
    t, nk = k.shape[1], k.shape[2]
    g = nq // nk
    q_block = min(q_block, s)
    kv_block = min(kv_block, t)
    s_pad, t_pad = (-s) % q_block, (-t) % kv_block
    qp = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    sq, st = s + s_pad, t + t_pad
    # [B, NK, G, S, H] / [B, NK, T, H]
    qr = qp.reshape(b, sq, nk, g, h).transpose(0, 2, 3, 1, 4)
    kr = kp.transpose(0, 2, 1, 3)
    vr = vp.transpose(0, 2, 1, 3)
    grid = (b, nk, sq // q_block, st // kv_block)

    out_specs = [pl.BlockSpec((1, 1, g, q_block, h),
                              lambda bb, kh, qi, ki: (bb, kh, 0, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, nk, g, sq, h), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec(
            (1, 1, g, q_block), lambda bb, kh, qi, ki: (bb, kh, 0, qi)))
        out_shape.append(jax.ShapeDtypeStruct((b, nk, g, sq), jnp.float32))
    kernel = functools.partial(
        _attn_kernel,
        scale=(1.0 / (h ** 0.5)) if scale is None else scale, causal=causal,
        window=window, q_block=q_block, kv_block=kv_block, kv_len=t)
    if not return_lse:
        kernel = functools.partial(_no_lse_adapter, kernel)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, q_block, h),
                         lambda bb, kh, qi, ki: (bb, kh, 0, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, h),
                         lambda bb, kh, qi, ki: (bb, kh, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, h),
                         lambda bb, kh, qi, ki: (bb, kh, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g * q_block, h), jnp.float32),
            pltpu.VMEM((g * q_block,), jnp.float32),
            pltpu.VMEM((g * q_block,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(qr, kr, vr)
    out = res[0] if return_lse else res[0]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, nq, h)[:, :s]
    if return_lse:
        lse = res[1].transpose(0, 3, 1, 2).reshape(b, sq, nq)[:, :s]
        return out, lse
    return out


def _no_lse_adapter(kernel, q_ref, k_ref, v_ref, o_ref, acc, m, l):
    kernel(q_ref, k_ref, v_ref, o_ref, None, acc, m, l)
