"""Flash attention backward pass as Pallas TPU kernels + custom VJP.

Two kernels, both recomputing the probability blocks from (q, k, lse)
instead of storing [S, T] probabilities (the memory-bound insight again —
recompute in VMEM beats streaming from HBM):

  dkv kernel: grid (B, NK, kv_blocks, q_blocks) — dk/dv accumulate in
              VMEM scratch across the sequential q axis.
  dq  kernel: grid (B, NK, q_blocks, kv_blocks) — dq accumulates across
              the sequential kv axis.

Inputs per block: q, k, v, dO, lse (=m + log l from the forward), and
D = rowsum(dO * O) (computed outside, one fused elementwise pass).

    dP = dO @ V^T;  dS = P * (dP - D);  dV += P^T dO;
    dK += dS^T Q;   dQ += dS K
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.kernels.flash_attention import flash_attention as _fwd_kernel_call

NEG_INF = -1e30


def _masks(q_start, k_start, g, qb, kb, *, causal, window, kv_len):
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (g, qb, kb), 1).reshape(g * qb, kb)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g * qb, kb), 1)
    ok = k_pos < kv_len
    if causal:
        ok = jnp.logical_and(ok, k_pos <= q_pos)
    if window > 0:
        ok = jnp.logical_and(ok, k_pos > q_pos - window)
    return ok


def _p_block(q2, k, lse, scale, ok):
    s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok, s, NEG_INF)
    return jnp.exp(s - lse[:, None])  # [G*Qb, Kb]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, window, q_block, kv_block, kv_len):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # [G, Qb, H]
        g, qb, h = q.shape
        q2 = q.reshape(g * qb, h)
        k = k_ref[0, 0].astype(jnp.float32)       # [Kb, H]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32).reshape(g * qb, h)
        lse = lse_ref[0, 0].reshape(g * qb)
        dvec = dvec_ref[0, 0].reshape(g * qb)
        ok = _masks(qi * q_block, ki * kv_block, g, qb, kv_block,
                    causal=causal, window=window, kv_len=kv_len)
        p = _p_block(q2, k, lse, scale, ok)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [Kb, H]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q2, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [Kb, H]

    if causal or window > 0:
        relevant = jnp.asarray(True)
        if causal:
            relevant = jnp.logical_and(
                relevant, ki * kv_block <= qi * q_block + q_block - 1)
        if window > 0:
            relevant = jnp.logical_and(
                relevant,
                ki * kv_block + kv_block - 1 > qi * q_block - window)
        pl.when(relevant)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
               dq_ref, dq_acc, *,
               scale, causal, window, q_block, kv_block, kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        g, qb, h = q.shape
        q2 = q.reshape(g * qb, h)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32).reshape(g * qb, h)
        lse = lse_ref[0, 0].reshape(g * qb)
        dvec = dvec_ref[0, 0].reshape(g * qb)
        ok = _masks(qi * q_block, ki * kv_block, g, qb, kv_block,
                    causal=causal, window=window, kv_len=kv_len)
        p = _p_block(q2, k, lse, scale, ok)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [G*Qb, H]

    if causal or window > 0:
        relevant = jnp.asarray(True)
        if causal:
            relevant = jnp.logical_and(
                relevant, ki * kv_block <= qi * q_block + q_block - 1)
        if window > 0:
            relevant = jnp.logical_and(
                relevant,
                ki * kv_block + kv_block - 1 > qi * q_block - window)
        pl.when(relevant)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        g, qb, h = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
        dq_ref[0, 0] = dq_acc[...].reshape(g, qb, h).astype(dq_ref.dtype)


def flash_attention_bwd(
    q, k, v, o, lse, do, *,
    causal=True, window=0, q_block=256, kv_block=256, interpret=False,
):
    """q [B,S,NQ,H]; k/v [B,T,NK,H]; o/do like q; lse [B,S,NQ] (natural log).
    Returns (dq, dk, dv)."""
    b, s, nq, h = q.shape
    t, nk = k.shape[1], k.shape[2]
    g = nq // nk
    q_block = min(q_block, s)
    kv_block = min(kv_block, t)
    s_pad, t_pad = (-s) % q_block, (-t) % kv_block
    pad4 = lambda x, p: jnp.pad(x, ((0, 0), (0, p), (0, 0), (0, 0)))
    qp, dop, op = pad4(q, s_pad), pad4(do, s_pad), pad4(o, s_pad)
    kp, vp = pad4(k, t_pad), pad4(v, t_pad)
    lsep = jnp.pad(lse, ((0, 0), (0, s_pad), (0, 0)),
                   constant_values=0.0)
    sq, st = s + s_pad, t + t_pad

    # D = rowsum(dO * O)  — one fused elementwise+reduce pass
    dvec = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32), axis=-1)

    # layouts: q-like [B, NK, G, S, H]; kv [B, NK, T, H]; vec [B, NK, G, S]
    ql = qp.reshape(b, sq, nk, g, h).transpose(0, 2, 3, 1, 4)
    dol = dop.reshape(b, sq, nk, g, h).transpose(0, 2, 3, 1, 4)
    kl = kp.transpose(0, 2, 1, 3)
    vl = vp.transpose(0, 2, 1, 3)
    lsel = lsep.reshape(b, sq, nk, g).transpose(0, 2, 3, 1)
    dvecl = dvec.reshape(b, sq, nk, g).transpose(0, 2, 3, 1)

    common = dict(scale=1.0 / (h ** 0.5), causal=causal, window=window,
                  q_block=q_block, kv_block=kv_block, kv_len=t)
    qspec = pl.BlockSpec((1, 1, g, q_block, h),
                         lambda bb, kh, a, bq: (bb, kh, 0, a, 0))
    qspec_dkv = pl.BlockSpec((1, 1, g, q_block, h),
                             lambda bb, kh, ki, qi: (bb, kh, 0, qi, 0))
    kspec_dkv = pl.BlockSpec((1, 1, kv_block, h),
                             lambda bb, kh, ki, qi: (bb, kh, ki, 0))
    vecspec_dkv = pl.BlockSpec((1, 1, g, q_block),
                               lambda bb, kh, ki, qi: (bb, kh, 0, qi))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(b, nk, st // kv_block, sq // q_block),
        in_specs=[qspec_dkv, kspec_dkv, kspec_dkv, qspec_dkv, vecspec_dkv,
                  vecspec_dkv],
        out_specs=[kspec_dkv, kspec_dkv],
        out_shape=[jax.ShapeDtypeStruct((b, nk, st, h), k.dtype),
                   jax.ShapeDtypeStruct((b, nk, st, h), v.dtype)],
        scratch_shapes=[pltpu.VMEM((kv_block, h), jnp.float32),
                        pltpu.VMEM((kv_block, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention_dkv",
        interpret=interpret,
    )(ql, kl, vl, dol, lsel, dvecl)

    qspec_dq = pl.BlockSpec((1, 1, g, q_block, h),
                            lambda bb, kh, qi, ki: (bb, kh, 0, qi, 0))
    kspec_dq = pl.BlockSpec((1, 1, kv_block, h),
                            lambda bb, kh, qi, ki: (bb, kh, ki, 0))
    vecspec_dq = pl.BlockSpec((1, 1, g, q_block),
                              lambda bb, kh, qi, ki: (bb, kh, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(b, nk, sq // q_block, st // kv_block),
        in_specs=[qspec_dq, kspec_dq, kspec_dq, qspec_dq, vecspec_dq,
                  vecspec_dq],
        out_specs=qspec_dq,
        out_shape=jax.ShapeDtypeStruct((b, nk, g, sq, h), q.dtype),
        scratch_shapes=[pltpu.VMEM((g * q_block, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention_dq",
        interpret=interpret,
    )(ql, kl, vl, dol, lsel, dvecl)

    dq = dq.transpose(0, 3, 1, 2, 4).reshape(b, sq, nq, h)[:, :s]
    dk = dk.transpose(0, 2, 1, 3)[:, :t]
    dv = dv.transpose(0, 2, 1, 3)[:, :t]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_diff(q, k, v, causal=True, window=0, q_block=256,
                         kv_block=256, interpret=False):
    """Differentiable flash attention (fwd + bwd Pallas kernels)."""
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, window=window,
                           q_block=q_block, kv_block=kv_block,
                           interpret=interpret)


def _diff_fwd(q, k, v, causal, window, q_block, kv_block, interpret):
    from repro.kernels.flash_attention import flash_attention
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             q_block=q_block, kv_block=kv_block,
                             interpret=interpret, return_lse=True)
    return o, (q, k, v, o, lse)


def _diff_bwd(causal, window, q_block, kv_block, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret)
    return dq, dk, dv


flash_attention_diff.defvjp(_diff_fwd, _diff_bwd)
