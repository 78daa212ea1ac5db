"""RWKV6 WKV recurrence as a chunked Pallas TPU kernel.

Same near-bank pattern as ssd_scan: grid (batch, heads, chunks), the
[K, V] wkv state persists in VMEM scratch across the sequential chunk
axis.  Per-channel data-dependent decay makes the intra-chunk term a
decay-weighted matmul in log space.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv6_kernel(r_ref, k_ref, v_ref, logw_ref, u_ref, y_ref, state_ref,
                 *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, :, 0].astype(jnp.float32)        # [Q, K]
    k = k_ref[0, :, 0].astype(jnp.float32)        # [Q, K]
    v = v_ref[0, :, 0].astype(jnp.float32)        # [Q, V]
    logw = logw_ref[0, :, 0].astype(jnp.float32)  # [Q, K]
    u = u_ref[0].astype(jnp.float32)              # [K]

    cum = jnp.cumsum(logw, axis=0)                # E_t (log), inclusive
    cum_prev = cum - logw                         # E_{t-1}
    r_dec = r * jnp.exp(cum_prev)                 # [Q, K]
    k_inc = k * jnp.exp(-cum)                     # [Q, K]
    scores = jax.lax.dot_general(
        r_dec, k_inc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # [Q, Q]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(tri, scores, 0.0)          # strict lower-tri
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True)  # [Q, 1]
    y += diag * v
    state = state_ref[...]                        # [K, V]
    y += jax.lax.dot_general(r_dec, state, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    e_end = jnp.exp(cum[-1])[:, None]             # [K, 1]
    kscale = k * jnp.exp(cum[-1][None, :] - cum)  # [Q, K]
    outer = jax.lax.dot_general(kscale, v, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    state_ref[...] = state * e_end + outer


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r: jnp.ndarray,     # [B, S, H, K]
    k: jnp.ndarray,     # [B, S, H, K]
    v: jnp.ndarray,     # [B, S, H, V]
    w: jnp.ndarray,     # [B, S, H, K] decay in (0, 1)
    u: jnp.ndarray,     # [H, K]
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, kk = r.shape
    vv = v.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    logw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-20))
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = z(r), z(k), z(v)
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sq = s + pad
    nc = sq // chunk
    grid = (b, h, nc)
    y = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, kk), lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, chunk, 1, kk), lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, chunk, 1, vv), lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, chunk, 1, kk), lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, kk), lambda bb, hh, cc: (hh, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, vv),
                               lambda bb, hh, cc: (bb, cc, hh, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, vv), r.dtype),
        scratch_shapes=[pltpu.VMEM((kk, vv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="wkv6",
        interpret=interpret,
    )(r, k, v, logw, u)
    return y[:, :s]
