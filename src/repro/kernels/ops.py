"""Jit'd dispatch wrappers for every kernel.

``impl`` resolution: "pallas" (TPU target), "interpret" (Pallas kernel
body executed on CPU — used by tests to validate kernels against the
ref.py oracles), "ref" (pure-jnp fallback; what the dry-run lowers, so
compiled HLO never contains Mosaic custom-calls the CPU backend cannot
build).  "auto" picks pallas on TPU and ref elsewhere.

Every entry point routes through the process-wide ``KernelGuard``
(``repro.kernels.guard``): a launch/lowering failure demotes down the
``pallas -> interpret -> ref`` chain instead of propagating, and after
K consecutive failures a (kernel, impl) pair is quarantined so future
traces skip it.  The ref branch is the far pipeline — plain jnp that
always runs — so a guarded dispatch can only fail if the program itself
is broken.  Dispatch happens at trace time; compiled executables are
unaffected.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.adamw_update import adamw_update as _adamw_pallas
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.decode_attention import (
    paged_decode_attention as _paged_decode_pallas,
)
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.fused_elementwise import fused_elementwise as _fused_pallas
from repro.kernels.fused_elementwise import fused_segment as _fused_seg_pallas
from repro.kernels.fused_elementwise import (
    fused_segment_grid as _fused_seg_grid_pallas,
)
from repro.kernels.fused_matmul import (
    fused_matmul_segment as _fused_mm_pallas,
)
from repro.kernels.fused_matmul_bwd import (
    fused_matmul_dlhs_segment as _fused_dlhs_pallas,
)
from repro.kernels.fused_matmul_bwd import (
    fused_matmul_drhs_segment as _fused_drhs_pallas,
)
from repro.kernels.guard import kernel_guard
from repro.kernels.guard import resolve_impl as _resolve
from repro.kernels.mla_decode import paged_mla_decode as _paged_mla_pallas
from repro.kernels.moe_experts import moe_experts as _moe_experts_pallas
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas
from repro.kernels.rotary import rotary as _rotary_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas
from repro.kernels.wkv6 import wkv6 as _wkv6_pallas

Impl = Literal["auto", "pallas", "interpret", "ref"]


def flash_attention(q, k, v, *, causal=True, window=0, impl: Impl = "auto",
                    **kw):
    def attempt(im):
        if im == "ref":
            return _ref.ref_flash_attention(q, k, v, causal=causal,
                                            window=window)
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             interpret=(im == "interpret"), **kw)
    return kernel_guard().run("flash_attention", impl, attempt)


def decode_attention(q, k_cache, v_cache, lengths, *, impl: Impl = "auto",
                     head_major: bool = False, **kw):
    def attempt(im):
        if im == "ref":
            kc, vc = k_cache, v_cache
            if head_major:                  # ref oracle is token-major
                kc = kc.transpose(0, 2, 1, 3)
                vc = vc.transpose(0, 2, 1, 3)
            return _ref.ref_decode_attention(q, kc, vc, lengths)
        return _decode_pallas(q, k_cache, v_cache, lengths,
                              head_major=head_major,
                              interpret=(im == "interpret"), **kw)
    return kernel_guard().run("decode_attention", impl, attempt)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           impl: Impl = "auto", **kw):
    """Decode attention over a paged KV pool (block-table indexed)."""
    def attempt(im):
        if im == "ref":
            return _ref.ref_paged_decode_attention(
                q, k_pages, v_pages, block_tables, lengths)
        return _paged_decode_pallas(q, k_pages, v_pages, block_tables,
                                    lengths, interpret=(im == "interpret"),
                                    **kw)
    return kernel_guard().run("paged_decode_attention", impl, attempt)


def paged_mla_decode(q, pages, block_tables, lengths, *, rank: int,
                     scale: float, impl: Impl = "auto", **kw):
    """Absorbed latent attention over a paged latent pool."""
    def attempt(im):
        if im == "ref":
            return _ref.ref_paged_mla_decode(q, pages, block_tables, lengths,
                                             rank=rank, scale=scale)
        return _paged_mla_pallas(q, pages, block_tables, lengths, rank=rank,
                                 scale=scale, interpret=(im == "interpret"),
                                 **kw)
    return kernel_guard().run("paged_mla_decode", impl, attempt)


def moe_experts(x, tile_group, n_tiles, w_gate, w_up, w_down, *, tm: int,
                act: str = "silu", impl: Impl = "auto", **kw):
    """Grouped SwiGLU of expert-sorted, tile-padded rows over the held
    experts' weights."""
    def attempt(im):
        if im == "ref":
            return _ref.ref_moe_experts(x, tile_group, n_tiles, w_gate,
                                        w_up, w_down, tm=tm, act=act)
        return _moe_experts_pallas(x, tile_group, n_tiles, w_gate, w_up,
                                   w_down, tm=tm, act=act,
                                   interpret=(im == "interpret"), **kw)
    return kernel_guard().run("moe_experts", impl, attempt)


def rmsnorm(x, scale, *, eps: float = 1e-5, impl: Impl = "auto", **kw):
    def attempt(im):
        if im == "ref":
            return _ref.ref_rmsnorm(x, scale, eps)
        return _rmsnorm_pallas(x, scale, eps=eps,
                               interpret=(im == "interpret"), **kw)
    return kernel_guard().run("rmsnorm", impl, attempt)


def rotary(x, positions, *, theta: float = 10000.0, impl: Impl = "auto", **kw):
    def attempt(im):
        if im == "ref":
            return _ref.ref_rotary(x, positions, theta)
        return _rotary_pallas(x, positions, theta=theta,
                              interpret=(im == "interpret"), **kw)
    return kernel_guard().run("rotary", impl, attempt)


def ssd_scan(x, logd, dt, bmat, cmat, *, impl: Impl = "auto", **kw):
    def attempt(im):
        if im == "ref":
            y, _ = _ref.ref_ssd_scan(x, logd, dt, bmat, cmat)
            return y
        return _ssd_pallas(x, logd, dt, bmat, cmat,
                           interpret=(im == "interpret"), **kw)
    return kernel_guard().run("ssd_scan", impl, attempt)


def wkv6(r, k, v, w, u, *, impl: Impl = "auto", **kw):
    def attempt(im):
        if im == "ref":
            y, _ = _ref.ref_wkv6(r, k, v, w, u)
            return y
        return _wkv6_pallas(r, k, v, w, u, interpret=(im == "interpret"),
                            **kw)
    return kernel_guard().run("wkv6", impl, attempt)


def adamw_update(p, g, m, v, hyper, *, impl: Impl = "auto", **kw):
    def attempt(im):
        if im == "ref":
            lr, b1, b2, eps, wd, bc1, bc2 = (hyper[i] for i in range(7))
            pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
            m_new = b1 * m + (1 - b1) * gf
            v_new = b2 * v + (1 - b2) * gf * gf
            upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) + wd * pf
            return (pf - lr * upd).astype(p.dtype), m_new, v_new
        return _adamw_pallas(p, g, m, v, hyper,
                             interpret=(im == "interpret"), **kw)
    return kernel_guard().run("adamw_update", impl, attempt)


def fused_elementwise(fn, bulk, params=(), *, impl: Impl = "auto", **kw):
    def attempt(im):
        if im == "ref":
            full_params = [jnp.asarray(p) for p in params]
            return fn(*bulk, *full_params)
        return _fused_pallas(fn, bulk, params,
                             interpret=(im == "interpret"), **kw)
    return kernel_guard().run("fused_elementwise", impl, attempt)


def fused_segment(fn, bulk, params=(), *, out_dtypes, impl: Impl = "auto",
                  **kw):
    """Multi-output near-bank segment (legacy single-shape entry point).
    Always returns a tuple with one array per ``out_dtypes`` entry."""
    def attempt(im):
        if im == "ref":
            res = fn(*bulk, *[jnp.asarray(p) for p in params])
            if not isinstance(res, (tuple, list)):
                res = (res,)
            return tuple(r.astype(dt) for r, dt in zip(res, out_dtypes))
        return _fused_seg_pallas(fn, bulk, params, out_dtypes=out_dtypes,
                                 interpret=(im == "interpret"), **kw)
    return kernel_guard().run("fused_segment", impl, attempt)


def _full_view(spec, v, rows):
    """Materialize one operand's [rows, c] broadcast view for ref paths.

    ``spec`` is a (role, op_rows, c) triple or an interior-broadcast
    5-tuple ("bcast", op_rows, c, lead, out_lead)."""
    role, op_rows, c = spec[0], spec[1], spec[2]
    v = jnp.asarray(v)
    if role == "param":
        return v.reshape(1, c)
    if role == "rep":
        return jnp.repeat(v.reshape(op_rows, c), rows // op_rows, axis=0)
    if role == "tile":
        return jnp.tile(v.reshape(op_rows, c), (rows // op_rows, 1))
    if role == "bcast":
        op_lead, out_lead = spec[3], spec[4]
        return jnp.broadcast_to(
            v.reshape(op_lead + (c,)), out_lead + (c,)).reshape(rows, c)
    return v.reshape(rows, c)


def fused_segment_grid(fn, operands, specs, *, rows, out_cols, out_dtypes,
                       donate=(), impl: Impl = "auto", **kw):
    """Cross-shape near-bank segment with per-operand block views (what
    the offload rewriter emits).  ``specs`` are (role, op_rows, cols)
    triples — or ("bcast", op_rows, cols, lead, out_lead) 5-tuples for
    interior broadcasts; ``donate`` pairs become Pallas
    ``input_output_aliases``.  Returns one [rows, out_cols[j]] array per
    output.  The "ref" path materializes the broadcast views and runs
    ``fn`` as one full-array pass (donation is XLA's problem there)."""
    def attempt(im):
        if im == "ref":
            full = [_full_view(s, v, rows) for s, v in zip(specs, operands)]
            outs = fn(*full, block_rows=rows)
            return tuple(o.astype(dt) for o, dt in zip(outs, out_dtypes))
        return _fused_seg_grid_pallas(fn, operands, specs, rows=rows,
                                      out_cols=out_cols,
                                      out_dtypes=out_dtypes, donate=donate,
                                      interpret=(im == "interpret"), **kw)
    return kernel_guard().run("fused_segment_grid", impl, attempt)


def _epi_full_views(epi_specs, epi_operands, rows):
    """Materialize the epilogue operands' broadcast views for ref paths."""
    return [_full_view(s, v, rows)
            for s, v in zip(epi_specs, epi_operands)]


def fused_matmul_segment(pro_fn, rhs_pro_fn, epi_fn, lhs_operands,
                         lhs_specs, rhs_operands, rhs_specs,
                         epi_operands, epi_specs, *, rows, k_dim, n_dim,
                         acc_dtype, out_cols, out_dtypes, donate=(),
                         batch: int = 1, impl: Impl = "auto", **kw):
    """Matmul-anchored near-bank segment (fused GEMM prologue/epilogue —
    what the offload rewriter emits for dot_general-anchored segments).
    The "ref" path materializes the block views and runs prologue ->
    contraction -> epilogue as full-array jnp (one XLA dot; donation is
    XLA's problem there).  ``batch`` > 1 means ``rows`` spans leading
    batch dims shared by both operands; the contraction is per batch
    slice (k_dim/n_dim stay per-batch)."""
    def attempt(im):
        if im == "ref":
            lhs_full = [jnp.asarray(v).reshape(
                (1, c) if role == "param_k" else (rows, k_dim))
                for (role, _, c), v in zip(lhs_specs, lhs_operands)]
            lhs = pro_fn(*lhs_full, block_rows=rows)
            rhs_full = [jnp.asarray(v).reshape(
                (1, c) if role == "param_w" else (batch * k_dim, n_dim))
                for (role, _, c), v in zip(rhs_specs, rhs_operands)]
            rhs = rhs_pro_fn(*rhs_full, block_rows=rows)
            if batch > 1:
                h = jax.lax.dot_general(
                    lhs.reshape(batch, rows // batch, k_dim),
                    rhs.reshape(batch, k_dim, n_dim),
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                ).reshape(rows, n_dim).astype(acc_dtype)
            else:
                h = jnp.dot(lhs, rhs,
                            preferred_element_type=jnp.float32,
                            ).astype(acc_dtype)
            full = [h] + _epi_full_views(epi_specs, epi_operands, rows)
            outs = epi_fn(*full, block_rows=rows)
            return tuple(o.astype(dt) for o, dt in zip(outs, out_dtypes))
        return _fused_mm_pallas(pro_fn, rhs_pro_fn, epi_fn, lhs_operands,
                                lhs_specs, rhs_operands, rhs_specs,
                                epi_operands, epi_specs, rows=rows,
                                k_dim=k_dim, n_dim=n_dim,
                                acc_dtype=acc_dtype, out_cols=out_cols,
                                out_dtypes=out_dtypes, donate=donate,
                                batch=batch, interpret=(im == "interpret"),
                                **kw)
    return kernel_guard().run("fused_matmul", impl, attempt)


def fused_matmul_dlhs_segment(pro_fn, epi_fn, lhs_operands, lhs_specs, rhs,
                              epi_operands, epi_specs, *, rows, k_dim,
                              n_dim, acc_dtype, out_cols, out_dtypes,
                              donate=(), batch: int = 1,
                              impl: Impl = "auto", **kw):
    """dGRAD_LHS-anchored segment: dx[rows, n] = g[rows, k] @ w[n, k]^T
    with the [n, k] forward weight read column-major in-kernel.  The
    "ref" path runs one XLA dot_general contracting both lane axes.
    ``batch`` > 1 contracts per batch slice (attention QK^T is this
    form: q[rows, k] against k[batch, n, k])."""
    def attempt(im):
        if im == "ref":
            lhs_full = [jnp.asarray(v).reshape(
                (1, c) if role == "param_k" else (rows, k_dim))
                for (role, _, c), v in zip(lhs_specs, lhs_operands)]
            g = pro_fn(*lhs_full, block_rows=rows)
            if batch > 1:
                h = jax.lax.dot_general(
                    g.reshape(batch, rows // batch, k_dim),
                    jnp.asarray(rhs).reshape(batch, n_dim, k_dim),
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                ).reshape(rows, n_dim).astype(acc_dtype)
            else:
                h = jax.lax.dot_general(
                    g, jnp.asarray(rhs).reshape(n_dim, k_dim),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(acc_dtype)
            full = [h] + _epi_full_views(epi_specs, epi_operands, rows)
            outs = epi_fn(*full, block_rows=rows)
            return tuple(o.astype(dt) for o, dt in zip(outs, out_dtypes))
        return _fused_dlhs_pallas(pro_fn, epi_fn, lhs_operands, lhs_specs,
                                  rhs, epi_operands, epi_specs, rows=rows,
                                  k_dim=k_dim, n_dim=n_dim,
                                  acc_dtype=acc_dtype, out_cols=out_cols,
                                  out_dtypes=out_dtypes, donate=donate,
                                  batch=batch,
                                  interpret=(im == "interpret"), **kw)
    return kernel_guard().run("fused_matmul_dlhs", impl, attempt)


def fused_matmul_drhs_segment(epi_fn, lhs, rhs, epi_operands, epi_specs, *,
                              m_dim, rows, n_dim, acc_dtype, out_cols,
                              out_dtypes, donate=(), batch: int = 1,
                              impl: Impl = "auto", **kw):
    """dGRAD_RHS-anchored segment: dw[rows, n] = x[m, rows]^T @ g[m, n]
    accumulated over the row (M) axis into an f32 [Kb, Nb] scratch.  The
    "ref" path runs one XLA dot_general contracting both row axes.
    ``batch`` > 1 reduces each batch slice's own m rows only."""
    def attempt(im):
        if im == "ref":
            if batch > 1:
                h = jax.lax.dot_general(
                    jnp.asarray(lhs).reshape(batch, m_dim, rows // batch),
                    jnp.asarray(rhs).reshape(batch, m_dim, n_dim),
                    (((1,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                ).reshape(rows, n_dim).astype(acc_dtype)
            else:
                h = jax.lax.dot_general(
                    jnp.asarray(lhs).reshape(m_dim, rows),
                    jnp.asarray(rhs).reshape(m_dim, n_dim),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(acc_dtype)
            full = [h] + _epi_full_views(epi_specs, epi_operands, rows)
            outs = epi_fn(*full, block_rows=rows)
            return tuple(o.astype(dt) for o, dt in zip(outs, out_dtypes))
        return _fused_drhs_pallas(epi_fn, lhs, rhs, epi_operands, epi_specs,
                                  m_dim=m_dim, rows=rows, n_dim=n_dim,
                                  acc_dtype=acc_dtype, out_cols=out_cols,
                                  out_dtypes=out_dtypes, donate=donate,
                                  batch=batch,
                                  interpret=(im == "interpret"), **kw)
    return kernel_guard().run("fused_matmul_drhs", impl, attempt)


def fused_flash_segment(softmax_fn, q, k, v, *, batch, rows, head_dim,
                        t_dim, n_dim, scale, scores_shape, scores_dtype,
                        out_dtype, donate=(), impl: Impl = "auto", **kw):
    """Flash-shaped anchored segment: QK^T -> scale/row-softmax -> PV as
    ONE launch, the [S, T] score matrix never touching HBM.

    ``softmax_fn`` replays the admitted scale+softmax eqns verbatim on
    the raw scores (ref path only — the Pallas path runs the online
    softmax inside ``flash_attention`` with the extracted ``scale``).
    ``rows`` spans all batch slices; per slice q is [S, head_dim],
    k is [t_dim, head_dim], v is [t_dim, n_dim] with n_dim == head_dim
    (the flash kernel's scratch/PV layout requires it)."""
    s_pb = rows // batch

    def attempt(im):
        if im == "ref":
            q3 = jnp.asarray(q).reshape(batch, s_pb, head_dim)
            k3 = jnp.asarray(k).reshape(batch, t_dim, head_dim)
            v3 = jnp.asarray(v).reshape(batch, t_dim, n_dim)
            s = jax.lax.dot_general(
                q3, k3, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).astype(scores_dtype)
            p = softmax_fn(s.reshape(scores_shape))
            o = jax.lax.dot_general(
                jnp.asarray(p).reshape(batch, s_pb, t_dim), v3,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return (o.reshape(rows, n_dim).astype(out_dtype),)
        q4 = jnp.asarray(q).reshape(batch, s_pb, 1, head_dim)
        k4 = jnp.asarray(k).reshape(batch, t_dim, 1, head_dim)
        v4 = jnp.asarray(v).reshape(batch, t_dim, 1, n_dim)
        o = _flash_pallas(q4, k4, v4, causal=False, window=0, scale=scale,
                          interpret=(im == "interpret"), **kw)
        return (o.reshape(rows, n_dim).astype(out_dtype),)
    return kernel_guard().run("fused_flash", impl, attempt)
