"""Grouped SwiGLU over the experts a chip holds, as a Pallas TPU kernel.

The rows are tokens already sorted by expert, each expert's rows padded
to a whole number of ``tm``-row tiles (``tile_group[t]`` names tile
``t``'s expert), so every tile belongs to one expert and no routed
token is dropped.  A grid step takes one tile and one ``tf``-wide slice
of that expert's gate, up and down matrices:

    acc[tile] += (act(x @ gate[:, f]) * (x @ up[:, f])) @ down[f, :]

with the f32 accumulator in VMEM over the slices.  The grid runs over
the tiles in use only (a scalar-prefetched count), and the weights are
read in the dtype they are stored in and cast to the rows' dtype in
VMEM.  Rows of tiles past the count are left unwritten.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, VMEM_LIMIT_BYTES

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def _moe_kernel(tile_group_ref, n_tiles_ref, x_ref, g_ref, u_ref, d_ref,
                o_ref, acc_ref, *, act: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    dims = (((1,), (0,)), ((), ()))
    g = jax.lax.dot_general(x, g_ref[0].astype(x.dtype), dims,
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, u_ref[0].astype(x.dtype), dims,
                            preferred_element_type=jnp.float32)
    h = (_ACTS[act](g) * u).astype(x.dtype)
    acc_ref[...] += jax.lax.dot_general(h, d_ref[0].astype(x.dtype), dims,
                                        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def f_block(f: int) -> int:
    """The expert-width slice of a grid step: one lane tile where the
    width has whole tiles, else the whole width."""
    return LANE if f % LANE == 0 else f


@functools.partial(jax.jit, static_argnames=("tm", "act", "interpret"))
def moe_experts(x, tile_group, n_tiles, w_gate, w_up, w_down, *, tm: int,
                act: str = "silu", interpret: bool = False):
    """x [M, d] (M a multiple of ``tm``); tile_group [M // tm] int32;
    n_tiles [1] int32, the tiles in use; w_gate / w_up [E, d, f];
    w_down [E, f, d].  Returns [M, d] in x's dtype."""
    m, d = x.shape
    f = w_gate.shape[-1]
    tf = f_block(f)
    grid = (n_tiles[0], f // tf)
    return pl.pallas_call(
        functools.partial(_moe_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, d), lambda t, j, G, N: (t, 0)),
                pl.BlockSpec((1, d, tf), lambda t, j, G, N: (G[t], 0, j)),
                pl.BlockSpec((1, d, tf), lambda t, j, G, N: (G[t], 0, j)),
                pl.BlockSpec((1, tf, d), lambda t, j, G, N: (G[t], j, 0)),
            ],
            out_specs=pl.BlockSpec((tm, d), lambda t, j, G, N: (t, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="moe_experts",
        interpret=interpret,
    )(tile_group.astype(jnp.int32), n_tiles.astype(jnp.int32), x, w_gate,
      w_up, w_down)
