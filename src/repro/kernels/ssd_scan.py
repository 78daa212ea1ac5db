"""Mamba2 SSD chunk scan as a Pallas TPU kernel.

Grid: (batch, heads, chunks) with chunks sequential ("arbitrary"); the
[P, N] SSM state lives in VMEM scratch across chunk steps — the
near-bank shared memory of DESIGN.md §2: within a (batch, head) stream
the state never touches HBM.  Each chunk does four dense matmuls
(MXU-aligned when P, N are multiples of 128 — production configs use
P=64..128, padded by Mosaic).

Inputs are pre-projected (the projections stay in the far-bank XLA
graph): x [B,S,H,P], logd/dt [B,S,H], B/C [B,S,N].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, logd_ref, dt_ref, b_ref, c_ref, y_ref, state_ref,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)        # [Q, P]
    logd = logd_ref[0, :, 0].astype(jnp.float32)  # [Q]
    dt = dt_ref[0, :, 0].astype(jnp.float32)      # [Q]
    bm = b_ref[0].astype(jnp.float32)             # [Q, N]
    cm = c_ref[0].astype(jnp.float32)             # [Q, N]

    csum = jnp.cumsum(logd)                       # [Q]
    # intra-chunk decay matrix: exp(csum_i - csum_j) lower-tri (i >= j)
    diff = csum[:, None] - csum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(tri, jnp.exp(diff), 0.0)    # [Q, Q]
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * decay          # [Q, Q]
    xw = x * dt[:, None]                                     # dt_j * x_j
    y_intra = jax.lax.dot_general(
        scores, xw, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [Q, P]
    dfront = jnp.exp(csum)[:, None]                          # [Q, 1]
    state = state_ref[...]                                   # [P, N]
    y_inter = jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * dfront         # [Q, P]
    y_ref[0, :, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    total = jnp.exp(csum[-1])
    dback = jnp.exp(csum[-1] - csum)[:, None]                # [Q, 1]
    outer = jax.lax.dot_general(
        xw * dback, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [P, N]
    state_ref[...] = state * total + outer


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jnp.ndarray,     # [B, S, H, P]
    logd: jnp.ndarray,  # [B, S, H] (= dt * a, fp32)
    dt: jnp.ndarray,    # [B, S, H]
    bmat: jnp.ndarray,  # [B, S, N]
    cmat: jnp.ndarray,  # [B, S, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        logd = jnp.pad(logd, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    sq = s + pad
    nc = sq // chunk
    grid = (b, h, nc)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bb, hh, cc: (bb, cc, hh)),
            pl.BlockSpec((1, chunk, 1), lambda bb, hh, cc: (bb, cc, hh)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p),
                               lambda bb, hh, cc: (bb, cc, hh, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="ssd_scan",
        interpret=interpret,
    )(x, logd, dt, bmat, cmat)
    return y[:, :s]
