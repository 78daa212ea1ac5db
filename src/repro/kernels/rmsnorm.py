"""RMSNorm as a Pallas TPU kernel with a custom VJP.

The textbook memory-bound value chain (§II of the paper: low arithmetic
density, regular access): 2 passes over x at ~3 FLOPs/element.  Fused
near-bank execution reads each row once, keeps the rsqrt statistic in
VMEM ("near-bank register"), writes once.  The backward kernel fuses the
two row-reductions dx needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _fwd_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _bwd_kernel(x_ref, s_ref, g_ref, dx_ref, ds_ref, *, eps: float):
    ri = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    d = x.shape[-1]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = x * inv
    gs = g * s
    # dx = inv * (gs - xhat * mean(gs * xhat))
    dot = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (inv * (gs - xhat * dot)).astype(dx_ref.dtype)

    @pl.when(ri == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    ds_ref[...] += jnp.sum(g * xhat, axis=0).astype(ds_ref.dtype)


def _call_fwd(x2, scale, eps, rows_block, interpret):
    rows, d = x2.shape
    grid = (rows // rows_block,)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows_block, d), lambda r: (r, 0)),
                  pl.BlockSpec((d,), lambda r: (0,))],
        out_specs=pl.BlockSpec((rows_block, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(x2, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x2, scale, eps, rows_block, interpret):
    return _call_fwd(x2, scale, eps, rows_block, interpret)


def _rmsnorm_fwd(x2, scale, eps, rows_block, interpret):
    return _call_fwd(x2, scale, eps, rows_block, interpret), (x2, scale)


def _rmsnorm_bwd(eps, rows_block, interpret, res, g2):
    x2, scale = res
    rows, d = x2.shape
    grid = (rows // rows_block,)
    dx, ds = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows_block, d), lambda r: (r, 0)),
                  pl.BlockSpec((d,), lambda r: (0,)),
                  pl.BlockSpec((rows_block, d), lambda r: (r, 0))],
        out_specs=[pl.BlockSpec((rows_block, d), lambda r: (r, 0)),
                   pl.BlockSpec((d,), lambda r: (0,))],
        out_shape=[jax.ShapeDtypeStruct((rows, d), x2.dtype),
                   jax.ShapeDtypeStruct((d,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),  # ds accumulates across steps
        name="rmsnorm_bwd",
        interpret=interpret,
    )(x2, scale, g2)
    return dx, ds.astype(scale.dtype)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "rows_block", "interpret"))
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-5,
            rows_block: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x [..., D]; scale [D]."""
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    rows_block = min(rows_block, rows)
    pad = (-rows) % rows_block
    x2 = x.reshape(rows, d)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = _rmsnorm(x2, scale, eps, rows_block, interpret)
    return y[:rows].reshape(shape)
