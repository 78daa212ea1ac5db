"""Ahead-of-time compiles for a described TPU v5e, at Qwen3-1.7B widths
and Moonlight-16B-A3B's.

No chip is attached: the TPU compiler compiles for a topology that is
only described, so it refuses here what the chip would refuse (block
tiling, VMEM, memory) — which the Pallas interpreter never does.  The
topology is described inside a module fixture, never at import, and all
of these compiles live in this one file.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.offload import mpu_offload
from repro.core.policy import OffloadPolicy
from repro.kernels import ops as kops
from repro.kernels.guard import kernel_guard

CFG = get_config("qwen3-1.7b")
D, F, H = CFG.d_model, CFG.d_ff, CFG.resolved_head_dim
NQ, NK = CFG.num_heads, CFG.num_kv_heads
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu_paths(monkeypatch):
    """Steer trace-time backend choices onto their TPU branches: the
    paged decode onto its Pallas kernel and "auto" kernels onto pallas
    (both otherwise follow the CPU backend this process runs on)."""
    import repro.kernels.guard as guard
    import repro.models.attention as attention
    monkeypatch.setattr(attention, "_paged_kernel", lambda: True)
    monkeypatch.setattr(guard, "default_impl", lambda: "pallas")


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_paged_decode_attention(one_chip):
    """A tiny table, then the benchmark's dense decode shapes, where the
    kernel's K/V blocks are widest: Qwen3-1.7B's chat (24 rows, 16 / 8
    heads, 32 pages at 8 a step) and DeepSeek-7B's longdoc (6 rows, 32
    / 32 heads, 64 pages at 2 a step), so a VMEM overrun shows here."""
    for b, nq, nk, width in [(4, NQ, NK, 2), (24, 16, 8, 32),
                             (6, 32, 32, 64)]:
        pages = 1 + b * width
        q = _spec(one_chip, (b, nq, H), BF16)
        kv = _spec(one_chip, (pages, nk, 64, H), BF16)
        tables = _spec(one_chip, (b, width), jnp.int32)
        lengths = _spec(one_chip, (b,), jnp.int32)
        compiled = _compile(
            lambda q, k, v, t, n: kops.paged_decode_attention(
                q, k, v, t, n, impl="pallas"), q, kv, kv, tables, lengths)
        assert "tpu_custom_call" in compiled.as_text()


def _mlp(x, ln, w_gate, w_up, w_down):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
    h = (h * ln).astype(BF16)
    g = h @ w_gate.astype(BF16)
    u = h @ w_up.astype(BF16)
    return x + (jax.nn.silu(g) * u) @ w_down.astype(BF16)


@pytest.mark.parametrize("rows", [4, 512])
def test_anchored_mlp(one_chip, rows):
    """rmsnorm -> SwiGLU MLP over f32 weights: the projections anchor
    fused kernels, and every kernel compiles for the chip."""
    args = (_spec(one_chip, (rows, D), BF16), _spec(one_chip, (D,)),
            _spec(one_chip, (D, F)), _spec(one_chip, (D, F)),
            _spec(one_chip, (F, D)))
    wrapped = mpu_offload(_mlp, policy=OffloadPolicy(impl="pallas"))
    report = wrapped.explain(*args)
    assert sum(d.fused and d.tier == "anchor"
               for d in report.all_decisions()) >= 3
    assert "tpu_custom_call" in _compile(wrapped, *args).as_text()


def test_lm_head_declined_and_compiles(one_chip):
    """The tied LM head (K=2048, N=151936) leaves the VMEM clamp no
    lane-aligned k block: the anchor declines to far with the tiling
    rule as its reason, and the program compiles."""
    def head(h, table):
        hn = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
        return jnp.tanh(hn @ table.astype(BF16).T).astype(jnp.float32)

    args = (_spec(one_chip, (4, D), BF16),
            _spec(one_chip, (CFG.vocab_size, D)))
    wrapped = mpu_offload(head, policy=OffloadPolicy(impl="pallas"))
    anchors = [d for d in wrapped.explain(*args).all_decisions()
               if d.tier == "anchor"]
    assert anchors and not any(d.fused for d in anchors)
    assert all(d.reason.startswith("TPU block tiling") for d in anchors)
    _compile(wrapped, *args)


def _identity_pro(*vals, block_rows):
    return vals[0]


def _identity_epi(acc, *vals, block_rows):
    return (acc,)


def test_dlhs_segment(one_chip):
    """dx[512, 2048] = g[512, 6144] @ w_down[2048, 6144]^T."""
    def dlhs(g, w):
        return kops.fused_matmul_dlhs_segment(
            _identity_pro, _identity_epi, [g], (("bulk_k", 512, F),), w,
            [], (), rows=512, k_dim=F, n_dim=D, acc_dtype=jnp.float32,
            out_cols=[D], out_dtypes=[BF16], impl="pallas")[0]

    compiled = _compile(dlhs, _spec(one_chip, (512, F), BF16),
                        _spec(one_chip, (D, F), BF16))
    assert "tpu_custom_call" in compiled.as_text()


def test_drhs_segment(one_chip):
    """dw[2048, 6144] = x[512, 2048]^T @ g[512, 6144]."""
    def drhs(x, g):
        return kops.fused_matmul_drhs_segment(
            _identity_epi, x, g, [], (), m_dim=512, rows=D, n_dim=F,
            acc_dtype=jnp.float32, out_cols=[F], out_dtypes=[jnp.float32],
            impl="pallas")[0]

    compiled = _compile(drhs, _spec(one_chip, (512, D), BF16),
                        _spec(one_chip, (512, F), BF16))
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_segment_grid(one_chip):
    """An rmsnorm-shaped elementwise segment over [512, 2048] bf16 rows
    with a [1, 2048] f32 scale."""
    def body(x, s, *, block_rows):
        h = x.astype(jnp.float32)
        h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
        return ((h * s).astype(BF16),)

    def seg(x, s):
        return kops.fused_segment_grid(
            body, [x, s], (("bulk", 512, D), ("param", 1, D)), rows=512,
            out_cols=[D], out_dtypes=[BF16], impl="pallas")[0]

    compiled = _compile(seg, _spec(one_chip, (512, D), BF16),
                        _spec(one_chip, (1, D)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, BF16, jnp.int8,
                                   jnp.bool_])
@pytest.mark.parametrize("rows,op_rows",
                         [(64, 4), (32, 4), (2048, 2), (4096, 256)])
def test_fused_segment_grid_rep_operand(one_chip, rows, op_rows, dtype):
    """A rotary-shaped segment: [rows, 128] against a rep operand of
    ``op_rows`` rows, each repeated rows/op_rows times.  The kernel
    fetches the rep operand in tile-legal blocks and picks its rows in
    VMEM: the decode step's q and k rotary shapes (one whole-array
    block of 4 repeats), one row per 512-row block (2048 rows), and
    8-row spans out of 128-row blocks of a 256-row operand.  Integer
    and bool rep operands (masks) scale f32 rows."""
    act = dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.float32

    def body(x, m, *, block_rows):
        h = x.astype(jnp.float32) * m.astype(jnp.float32)
        return (h.astype(act),)

    specs = (("bulk", rows, 128), ("rep", op_rows, 128))

    def seg(x, m):
        return kops.fused_segment_grid(
            body, [x, m], specs, rows=rows, out_cols=[128],
            out_dtypes=[act], impl="pallas")[0]

    compiled = _compile(seg, _spec(one_chip, (rows, 128), act),
                        _spec(one_chip, (op_rows, 128), dtype))
    assert "tpu_custom_call" in compiled.as_text()


def _paged_decode_step(one_chip):
    """The serving engine's decode step at full width and depth, with
    the shapes of its arguments placed on one chip."""
    from repro.models import build_model

    model = build_model(CFG)
    slots, page, max_len = 4, 64, 128
    pages = 1 + slots * (max_len // page)

    def place(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: model.init_paged_cache(slots, pages, page)))
    vec = _spec(one_chip, (slots,), jnp.int32)
    args = (params, cache, vec, vec,
            _spec(one_chip, (slots, max_len // page), jnp.int32),
            _spec(one_chip, (slots,), jnp.bool_))

    def paged_decode(params, cache, tok, pos, tables, active):
        return model.decode_step_paged(params, cache, tok, pos, tables,
                                       active, max_len=max_len)

    return paged_decode, args


def test_offloaded_paged_decode_step(one_chip, on_tpu_paths):
    """The serving engine's decode step at full width and depth: paged
    kernel plus the greedy offload plan, compiled for one chip, with
    the LM head declined and no kernel demoted."""
    from repro.models.attention import _paged_kernel

    assert _paged_kernel()
    paged_decode, args = _paged_decode_step(one_chip)
    before = kernel_guard().stats()
    wrapped = mpu_offload(paged_decode, policy=OffloadPolicy(mode="greedy"))
    report = wrapped.explain(*args)
    assert report.policy.mode == "greedy" and report.n_fused >= 1
    declined = [d for d in report.all_decisions() if not d.fused]
    tiling = [d for d in declined if d.reason.startswith("TPU block")]
    assert [d.tier for d in tiling] == ["anchor"], \
        "only the LM head should decline on tiling"
    rotary = [d for d in report.all_decisions()
              if any(r.startswith("rep[") for r in d.roles)]
    assert len(rotary) == 2 and all(d.fused for d in rotary), \
        "the q and k rotary segments should run near"
    compiled = _compile(wrapped, *args)
    assert "tpu_custom_call" in compiled.as_text()
    after = kernel_guard().stats()
    assert after["kernel_failures"] == before["kernel_failures"]
    assert after["kernel_fallbacks"] == before["kernel_fallbacks"]


def test_offloaded_paged_decode_step_writes_pools_in_place(one_chip,
                                                           on_tpu_paths):
    """Compiled as the engine compiles it, with the cache donated, the
    step writes each new K/V row into the stacked pools in place: no
    copy, slice or slice update yields an array shaped like one layer's
    pool, the stacked pools or their flat view."""
    paged_decode, args = _paged_decode_step(one_chip)
    wrapped = mpu_offload(paged_decode, policy=OffloadPolicy())
    compiled = jax.jit(wrapped, donate_argnums=(1,)).lower(*args).compile()
    stack_k = args[1]["stack"]["0"]["k"]
    n_layers, n_pages = stack_k.shape[:2]
    pool = stack_k.shape[2:]
    rows = n_layers * n_pages * pool[0] * pool[1]
    pool_shapes = {(n_pages, *pool), (n_layers, n_pages, *pool),
                   (n_layers * n_pages, *pool), (rows, pool[2])}
    hlo = compiled.as_text()
    instr = re.compile(
        r"%(\S+) = bf16\[([\d,]*)\]\S* "
        r"(copy|dynamic-slice|dynamic-update-slice)\(")
    copies = [(name, dims) for name, dims, _ in instr.findall(hlo)
              if tuple(int(d) for d in dims.split(",") if d != "1")
              in pool_shapes]
    assert copies == [], f"whole-pool copies in the step: {copies}"


# ------------------------------------------------ Moonlight (MLA + MoE)
MOON = dataclasses.replace(get_config("moonshot-v1-16b-a3b-ep8"), num_layers=9)


def test_paged_mla_decode(one_chip):
    """Absorbed queries [B, 16, 640] against a latent pool of 640-wide
    rows (576 padded to whole lanes), 16 pages a row, 8 per grid step."""
    from repro.models.mla import cache_row

    row = cache_row(MOON)
    q = _spec(one_chip, (4, 16, row), BF16)
    pool = _spec(one_chip, (1 + 4 * 16, 64, row), BF16)
    tables = _spec(one_chip, (4, 16), jnp.int32)
    lengths = _spec(one_chip, (4,), jnp.int32)
    compiled = _compile(
        lambda q, p, t, n: kops.paged_mla_decode(
            q, p, t, n, rank=MOON.mla.kv_rank, scale=0.07, impl="pallas"),
        q, pool, tables, lengths)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,tm", [(512, 16), (26624, 256)])
def test_moe_experts(one_chip, rows, tm):
    """The grouped SwiGLU over 8 held experts' float32 matrices at
    Moonlight's widths: a decode step's tiles and a 4096-token admit's."""
    f = MOON.moe.d_expert
    compiled = _compile(
        lambda x, g, n, wg, wu, wd: kops.moe_experts(
            x, g, n, wg, wu, wd, tm=tm, impl="pallas"),
        _spec(one_chip, (rows, D), BF16),
        _spec(one_chip, (rows // tm,), jnp.int32),
        _spec(one_chip, (1,), jnp.int32), _spec(one_chip, (8, D, f)),
        _spec(one_chip, (8, D, f)), _spec(one_chip, (8, f, D)))
    assert "tpu_custom_call" in compiled.as_text()


def test_offloaded_moonlight_decode_step(one_chip, on_tpu_paths):
    """Moonlight's decode step at full width, the benchmark's 9 layers:
    the greedy offload plan, the ``paged_mla_decode`` and
    ``moe_experts`` kernels, no kernel demoted, and with the cache
    donated no copy of a latent pool."""
    from repro.models import build_model

    model = build_model(MOON)
    slots, page, max_len = 4, 64, 128
    pages = 1 + slots * (max_len // page)

    def place(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: model.init_paged_cache(slots, pages, page)))
    vec = _spec(one_chip, (slots,), jnp.int32)
    args = (params, cache, vec, vec,
            _spec(one_chip, (slots, max_len // page), jnp.int32),
            _spec(one_chip, (slots,), jnp.bool_))

    def paged_decode(params, cache, tok, pos, tables, active):
        return model.decode_step_paged(params, cache, tok, pos, tables,
                                       active, max_len=max_len)

    before = kernel_guard().stats()
    wrapped = mpu_offload(paged_decode, policy=OffloadPolicy())
    assert wrapped.explain(*args).n_fused >= 1
    hlo = jax.jit(wrapped, donate_argnums=(1,)).lower(*args).compile() \
        .as_text()
    for kernel in ("paged_mla_decode", "moe_experts"):
        assert re.search(rf"%{kernel}[.\d]* = .*tpu_custom_call", hlo), kernel
    after = kernel_guard().stats()
    assert after["kernel_failures"] == before["kernel_failures"]
    assert after["kernel_fallbacks"] == before["kernel_fallbacks"]
    pool = cache["stack"]["0"]["ckv"].shape
    pool_shapes = {pool[1:], pool, (pool[0] * pool[1], *pool[2:]),
                   (pool[0] * pool[1] * pool[2], pool[3])}
    instr = re.compile(r"%(\S+) = bf16\[([\d,]*)\]\S* "
                       r"(copy|dynamic-slice|dynamic-update-slice)\(")
    copies = [(name, dims) for name, dims, _ in instr.findall(hlo)
              if tuple(int(d) for d in dims.split(",")) in pool_shapes]
    assert copies == [], f"whole-pool copies in the step: {copies}"
