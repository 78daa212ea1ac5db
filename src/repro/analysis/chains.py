"""The planner's fusion contract: fifteen representative chains and the
plan each must produce.

The chains are the Table-I workloads' value chains and real transformer
epilogues: elementwise chains, matmul-anchored GEMM epilogues,
lane-reduction chains, the grad-time contractions (``*_BWD``,
``MLP_GRAD``, ``TRAIN_STEP``) and the batched anchors (``ATTN_PREFILL``
as one flash-shaped segment, ``BATCHED_GEMM_BWD``).

``MUST_FUSE`` maps each chain to (segments, traffic floor,
anchored-backward floor) under ``POLICY``: the plan has exactly that
many segments, a ``traffic_reduction`` at or above the floor, and at
least that many anchored backward (``dlhs``/``drhs``) segments.  Each
traffic floor is 0.98 x the chain's recorded traffic reduction, rounded
down to two decimals, or the older fixed floor where that is higher
(TRAIN_STEP).
``tests/test_offload_chains.py`` holds every chain to it, and
``python -m repro.analysis.lint --chains`` runs each plan through the
static verifier.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.policy import OffloadPolicy

# the policy the contract was recorded under
POLICY = OffloadPolicy(bulk_threshold=4096)

MUST_FUSE = {
    "AXPY": (1, 1.63, 0),
    "BIAS_GELU_RES": (1, 7.51, 0),
    "SWIGLU_EPI": (1, 2.61, 0),
    "RMS_SCALE_RES": (1, 2.94, 0),
    "ADAM_CHAIN": (1, 9.47, 0),
    "MLP_RESIDUAL": (1, 8.41, 0),
    "GEMM_BIAS_GELU": (1, 7.01, 0),
    "GEMM_SWIGLU": (1, 5.59, 0),
    "RMSNORM_CHAIN": (1, 3.93, 0),
    "SOFTMAX_CHAIN": (1, 4.92, 0),
    "GEMM_BWD": (2, 2.34, 2),
    "MLP_GRAD": (4, 3.12, 1),
    "TRAIN_STEP": (5, 3.0, 1),
    # one flash-shaped segment: the [S, T] score matrix never touches HBM
    "ATTN_PREFILL": (1, 12.78, 0),
    "BATCHED_GEMM_BWD": (2, 2.83, 2),
}


def chains() -> list[tuple[str, Callable, tuple, tuple]]:
    """(name, fn, args, donate_argnums) for every ``MUST_FUSE`` chain."""
    k = jax.random.PRNGKey(0)
    n = 1 << 20
    x = jax.random.normal(k, (n // 256, 256))
    y = jax.random.normal(jax.random.fold_in(k, 1), (n // 256, 256))
    b = jax.random.normal(jax.random.fold_in(k, 2), (256,))
    s = jnp.ones((256,))
    w = jax.random.normal(jax.random.fold_in(k, 3), (256, 256)) * 0.05
    wgu = jax.random.normal(jax.random.fold_in(k, 4), (256, 512)) * 0.05

    def axpy(x, y):
        return 2.5 * x + y

    def bias_gelu_residual(x, y, b):
        return jax.nn.gelu(x + b) + y

    def swiglu_epilogue(x, y):
        # cross-shape segment: silu's pjit body is flattened into the
        # caller so the whole epilogue is one fused launch
        return jax.nn.silu(x) * y

    def rms_scale_residual(x, y, s):
        return jnp.tanh(x) * s + y * 0.5

    def adam_like(x, y):
        m = 0.9 * x + 0.1 * y
        v = 0.95 * x + 0.05 * y * y
        return x - 1e-3 * m / (jnp.sqrt(v) + 1e-8)

    def mlp_residual(x, w, b, y):
        # matmul-anchored segment: the dot opens the segment and the
        # epilogue runs on the accumulator — h never round-trips HBM
        h = x @ w
        h = jax.nn.gelu(h + b)
        h = h * jax.nn.sigmoid(h)
        return h + y

    def gemm_bias_gelu(x, w, b, y):
        return jax.nn.gelu(x @ w + b) + y

    def gemm_swiglu(x, wgu):
        # fused gate+up projection: the [R, 2C] product is lane-split
        # and gated inside the anchored kernel; only [R, C] is stored
        hw = x @ wgu
        return jax.nn.silu(hw[:, :256]) * hw[:, 256:]

    def rmsnorm_chain(x, s):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-5) * s

    def softmax_chain(x):
        return jax.nn.softmax(x * 0.125, axis=-1)

    # --- backward chains (the grad-time contraction forms) ------------
    g = jax.random.normal(jax.random.fold_in(k, 5), (n // 256, 256))

    def gemm_bwd(g, x, w):
        # handwritten backward of a projection: the activation gradient
        # anchors the dlhs kernel (weight read column-major, activation
        # backward as epilogue) and the weight gradient anchors the
        # drhs kernel (M-innermost accumulation, weight-decay epilogue)
        dx = jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())))
        dx = jnp.tanh(dx) * 0.5 + x * 0.1
        dw = jax.lax.dot_general(x, g, (((0,), (0,)), ((), ())))
        dw = dw + 0.01 * w
        return dx, dw

    xg = jax.random.normal(jax.random.fold_in(k, 6), (2048, 256))
    w1g = jax.random.normal(jax.random.fold_in(k, 7), (256, 512)) * 0.05
    b1g = jax.random.normal(jax.random.fold_in(k, 8), (512,))
    w2g = jax.random.normal(jax.random.fold_in(k, 9), (512, 256)) * 0.05
    yg = jax.random.normal(jax.random.fold_in(k, 10), (2048, 256))

    def mlp_grad(x, w1, b1, w2, y):
        # the realistic post-grad trace: jax.grad emits the transposed
        # contractions, and the activation gradient (dlhs) fuses with
        # the previous layer's activation-backward chain
        def loss(w1, b1, w2, x):
            h = jax.nn.gelu(x @ w1 + b1)
            o = h @ w2 + y
            return jnp.sum(o * o)
        return jax.grad(loss, argnums=(0, 1, 2))(w1, b1, w2, x)

    m1g = jnp.zeros_like(w1g)
    m2g = jnp.zeros_like(w2g)

    def train_step(x, w1, b1, w2, m1, m2):
        # loss -> grads -> momentum-SGD update in ONE planned program:
        # forward anchors, a dlhs activation-gradient anchor, a drhs
        # weight-gradient anchor feeding the update math, and the
        # optimizer elementwise chains all fuse
        def loss(w1, b1, w2):
            h = jax.nn.gelu(x @ w1 + b1)
            return jnp.sum((h @ w2) ** 2)
        _, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(w1, b1, w2)
        g1, gb, g2 = grads
        m1n = 0.9 * m1 + g1
        w1n = w1 - 1e-3 * m1n - 1e-4 * w1
        m2n = 0.9 * m2 + g2
        w2n = w2 - 1e-3 * m2n - 1e-4 * w2
        b1n = b1 - 1e-3 * gb
        return w1n, w2n, b1n, m1n, m2n

    # --- batched-anchor chains (N-D grids, outer batch axes) ----------
    qb = jax.random.normal(jax.random.fold_in(k, 11), (4, 8, 256, 64))
    kb = jax.random.normal(jax.random.fold_in(k, 12), (4, 8, 256, 64))
    vb = jax.random.normal(jax.random.fold_in(k, 13), (4, 8, 256, 64))

    def attn_prefill(q, kk, vv):
        # QK^T -> scale -> row-softmax -> PV recognized as ONE
        # flash-shaped anchored segment: the [S, T] score matrix lives
        # entirely in the accumulator and contributes zero HBM bytes
        scale = jnp.sqrt(jnp.float32(q.shape[-1])).astype(q.dtype)
        s = jnp.einsum("bhsd,bhtd->bhst", q, kk) / scale
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", p, vv)

    xb = jax.random.normal(jax.random.fold_in(k, 14), (8, 256, 128))
    wb = jax.random.normal(jax.random.fold_in(k, 15), (8, 128, 64)) * 0.1
    gb2 = jax.random.normal(jax.random.fold_in(k, 16), (8, 256, 64))

    def batched_gemm_bwd(g, x, w):
        # handwritten backward of a BATCHED projection (the per-head
        # attention-projection shape): both grad contractions keep the
        # batch dim as the outer grid axis — dx anchors the batched
        # dlhs kernel, dw the batched drhs kernel, and the update math
        # rides each grad accumulator as an epilogue
        dx = jax.lax.dot_general(g, w, (((2,), (2,)), ((0,), (0,))))
        dx = jnp.tanh(dx) * 0.5 + x * 0.1
        dw = jax.lax.dot_general(x, g, (((1,), (1,)), ((0,), (0,))))
        dw = dw + 0.01 * w
        return dx, dw

    # donate_argnums: the optimizer update overwrites the parameter
    # buffer in place (the classic near-bank in-place update)
    return [
        ("AXPY", axpy, (x, y), ()),
        ("BIAS_GELU_RES", bias_gelu_residual, (x, y, b), ()),
        ("SWIGLU_EPI", swiglu_epilogue, (x, y), ()),
        ("RMS_SCALE_RES", rms_scale_residual, (x, y, s), ()),
        ("ADAM_CHAIN", adam_like, (x, y), (0,)),
        ("MLP_RESIDUAL", mlp_residual, (x, w, b, y), ()),
        ("GEMM_BIAS_GELU", gemm_bias_gelu, (x, w, b, y), ()),
        ("GEMM_SWIGLU", gemm_swiglu, (x, wgu), ()),
        ("RMSNORM_CHAIN", rmsnorm_chain, (x, s), ()),
        ("SOFTMAX_CHAIN", softmax_chain, (x,), ()),
        ("GEMM_BWD", gemm_bwd, (g, x, w), ()),
        ("MLP_GRAD", mlp_grad, (xg, w1g, b1g, w2g, yg), ()),
        ("TRAIN_STEP", train_step, (xg, w1g, b1g, w2g, m1g, m2g), ()),
        ("ATTN_PREFILL", attn_prefill, (qb, kb, vb), ()),
        ("BATCHED_GEMM_BWD", batched_gemm_bwd, (gb2, xb, wb), ()),
    ]
