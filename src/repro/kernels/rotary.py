"""Rotary position embedding as a fused Pallas kernel.

Memory-bound (1 read + 1 write per element + a handful of transcendental
ops); fusing sin/cos generation into the kernel avoids materializing the
[S, H/2] angle tables in HBM — the tables are "near-bank registers"
computed in VMEM from the position scalar stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rope_kernel(pos_ref, x_ref, o_ref, *, theta: float):
    x = x_ref[...].astype(jnp.float32)  # [Rb, N, H]
    rb, n, h = x.shape
    freqs = 1.0 / (theta ** (
        jax.lax.broadcasted_iota(jnp.float32, (1, h // 2), 1) * 2.0 / h))
    pos = pos_ref[...].astype(jnp.float32).reshape(rb, 1)
    ang = pos * freqs  # [Rb, H/2]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1 = x[..., : h // 2]
    x2 = x[..., h // 2:]
    o_ref[...] = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("theta", "rows_block", "interpret"))
def rotary(x: jnp.ndarray, positions: jnp.ndarray, *, theta: float = 10000.0,
           rows_block: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x [R, N, H] (rows = flattened batch*seq); positions [R] int32."""
    r, n, h = x.shape
    rows_block = min(rows_block, r)
    pad = (-r) % rows_block
    xp = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(positions, (0, pad))
    out = pl.pallas_call(
        functools.partial(_rope_kernel, theta=theta),
        grid=((r + pad) // rows_block,),
        in_specs=[pl.BlockSpec((rows_block,), lambda i: (i,)),
                  pl.BlockSpec((rows_block, n, h), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((rows_block, n, h), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        name="rotary",
        interpret=interpret,
    )(pp.astype(jnp.int32), xp)
    return out[:r]
