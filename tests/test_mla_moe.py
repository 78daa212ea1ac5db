"""Latent attention and dropless MoE (Moonlight, the DeepSeek-V3 block)
at the reduced CPU size, on seeded random weights with a nonzero router
correction bias (the benchmark draws it as zero).

Tolerances are relative to the logits' (or outputs') largest magnitude.
The program and its references differ here only in float32 summation
order (absorbed against decompressed attention, grouped against dense
experts), which moves results by about 1e-6 of their scale; the bounds
leave room above that, and computing in bfloat16 (8 mantissa bits,
about 4e-3 per rounding) lands far outside them.
"""
import dataclasses
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import transformer as tf_mod
from repro.models.layers import mlp_apply
from repro.serve import Engine, Request
from repro.serve.engine import _scatter_admit

ROOT = pathlib.Path(__file__).resolve().parents[1]
TOL = 1e-4


def _reference_module():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    path = ROOT / "bench" / "reference" / "mla_moe.py"
    spec = importlib.util.spec_from_file_location("ref_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(dtype="float32", **moe_over):
    cfg = dataclasses.replace(reduced(get_config("moonshot-v1-16b-a3b-ep8")),
                              dtype=dtype)
    if moe_over:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **moe_over))
    return cfg


def _params(cfg, seed=0):
    """Random weights with a nonzero correction bias on every layer."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))

    def leaf(path, a):
        if jax.tree_util.keystr(path).endswith("['router_bias']"):
            return 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                           a.shape)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


def _ref_config(cfg):
    m, e = cfg.mla, cfg.moe
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": m.kv_rank, "qk_nope_head_dim": m.nope_dim,
        "qk_rope_head_dim": m.rope_dim, "v_head_dim": m.v_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "vocab_size": cfg.vocab_size,
        "first_k_dense_replace": cfg.first_dense,
        "num_experts_per_tok": e.top_k, "norm_topk_prob": True,
        "routed_scaling_factor": e.routed_scale,
        "n_routed_experts": e.held_range[1],
        "held_experts": list(e.held_range),
    }


def _prefill_then_paged_decode(cfg, params, prompt, n_new, page=4):
    """Prefill, then ``n_new`` greedy steps of the paged decode; returns
    (every position's logits [n_new + 1, V], tokens fed)."""
    model = build_model(cfg)
    max_len = 32
    n_pages = 1 + max_len // page
    table = jnp.arange(1, n_pages, dtype=jnp.int32)
    logits, one = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                                max_len)
    cache = _scatter_admit(model.init_paged_cache(1, n_pages, page), one,
                           table, 0, page=page, n_pr=n_pages - 1)
    out, toks = [logits[0]], []
    pos = len(prompt)
    for _ in range(n_new):
        tok = jnp.argmax(out[-1]).astype(jnp.int32)[None]
        toks.append(int(tok[0]))
        lg, cache = model.decode_step_paged(
            params, cache, tok, jnp.asarray([pos], jnp.int32), table[None],
            jnp.asarray([True]), max_len=max_len)
        out.append(lg[0])
        pos += 1
    return np.stack([np.asarray(o) for o in out]), toks


def test_prefill_then_paged_decode_matches_reference():
    """The program's prefill and paged latent decode against the plain
    float32 reference's full forward pass, position by position."""
    cfg = _cfg()
    params = _params(cfg)
    prompt = np.random.default_rng(0).integers(1, 250, 9).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, toks = _prefill_then_paged_decode(cfg, params, prompt, 8)
    ref = _reference_module().Reference(params, _ref_config(cfg),
                                        max_len=32, max_new=16)
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = ref.logits(seq, np.arange(len(prompt) - 1, len(seq)))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=TOL * scale, rtol=0)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_absorbed_decode_equals_decompressed(monkeypatch, kernel):
    """The last token's attention output through the absorbed form
    (latent rows, contiguous and paged; the paged kernel in interpret
    mode) equals the decompressed full-sequence form."""
    import repro.kernels.guard as guard
    import repro.models.attention as attention

    if kernel:
        monkeypatch.setattr(attention, "_paged_kernel", lambda: True)
        monkeypatch.setattr(guard, "default_impl", lambda: "interpret")
    cfg = _cfg()
    p = mla_mod.init_mla(jax.random.PRNGKey(2), cfg)
    s, page = 11, 4
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, cfg.d_model))
    pos = jnp.arange(s)[None]
    with jax.default_matmul_precision("highest"):
        want, rows = mla_mod.mla_apply(p, cfg, x, pos)
        cache = jnp.pad(rows[:, :s - 1], ((0, 0), (0, 17 - s), (0, 0)))
        got, _ = mla_mod.mla_decode(p, cfg, x[:, -1:], cache,
                                    jnp.asarray([s - 1]))
        pages = jnp.zeros((1 + 4, page, rows.shape[-1]))
        pages = pages.at[1:].set(cache[0].reshape(4, page, -1))
        paged, _ = mla_mod.mla_decode_paged(
            p, cfg, x[:, -1:], pages, jnp.asarray([s - 1]),
            jnp.arange(1, 5, dtype=jnp.int32)[None], jnp.asarray([True]),
            kv_capacity=16)
    scale = np.abs(np.asarray(want)).max()
    for out in (got, paged):
        np.testing.assert_allclose(np.asarray(out)[0, 0],
                                   np.asarray(want)[0, -1],
                                   atol=TOL * scale, rtol=0)


def _dense_oracle(params, cfg, x):
    """Every token through every held expert, weighted by the router."""
    moe = cfg.moe
    first, n = moe.held_range
    w, idx = moe_mod.select_experts(params, moe, x)
    y = mlp_apply(params["shared"], x, cfg.act) if "shared" in params else 0
    for e in range(n):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        ye = mlp_apply({m: params[m][e] for m in ("gate", "up", "down")}, x,
                       cfg.act)
        y = y + we[:, None] * ye
    return y


def test_no_token_dropped_when_all_pick_one_expert():
    """A correction bias that sends every token to expert 3: all 64 of
    them are computed there, as the dense oracle computes them."""
    cfg = _cfg(held=None)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    p["router_bias"] = jnp.zeros_like(p["router_bias"]).at[3].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, routed = moe_mod.moe_dropless(p, cfg, x)
        want = _dense_oracle(p, cfg, x)
    assert int(routed[3]) == 64
    assert int(routed.sum()) == 64 * cfg.moe.top_k
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(y, want, atol=TOL * scale, rtol=0)


def test_expert_shares_add_up_to_the_whole_layer():
    """Each of 8 chips holding one expert computes its part; the parts,
    with the shared experts counted once, add up to the layer that
    holds every expert."""
    cfg = _cfg(held=None)
    n = cfg.moe.num_experts
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (n,))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model))
    shared = mlp_apply(p["shared"], x, cfg.act)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_mod.moe_dropless(p, cfg, x)
        total = shared
        for i in range(n):
            share = _cfg(held=(i, 1))
            pi = {**p, **{m: p[m][i:i + 1] for m in ("gate", "up", "down")}}
            y, _ = moe_mod.moe_dropless(pi, share, x)
            total = total + (y - shared)
    scale = np.abs(np.asarray(whole)).max()
    np.testing.assert_allclose(total, whole, atol=TOL * scale, rtol=0)


def test_bucketed_moe_admit_matches_unbucketed():
    """A prompt right-padded to its pow2 bucket prefills to the same
    logits and cache rows as the prompt alone, and the engine serves the
    same tokens with bucketing on and off."""
    cfg = _cfg()
    params = _params(cfg)
    model = build_model(cfg)
    prompt = np.random.default_rng(1).integers(1, 250, 11).astype(np.int32)
    padded = np.zeros((16,), np.int32)
    padded[:11] = prompt
    a, ca = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]}, 32)
    b, cb = model.prefill(params, {"tokens": jnp.asarray(padded)[None]}, 32,
                          jnp.asarray(11))
    np.testing.assert_allclose(a, b, atol=TOL * np.abs(a).max(), rtol=0)
    for x, y in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
        np.testing.assert_allclose(x[..., :11, :], y[..., :11, :],
                                   atol=TOL * np.abs(x).max(), rtol=0)
    served = []
    for bucket in (True, False):
        eng = Engine(cfg, params, slots=2, max_len=48, bucket_prompts=bucket)
        assert eng.bucket_prompts == bucket
        reqs = [Request(prompt[:n], max_new_tokens=5, rid=n)
                for n in (5, 9, 11)]
        served.append({r: c.tokens for r, c in eng.generate(reqs).items()})
    assert served[0] == served[1]


def test_moe_routed_counts_every_routed_pair(monkeypatch):
    """``serve_stats["moe_routed"]`` equals the (token, held expert)
    pairs the decode steps' active rows routed, recounted from every
    MoE layer's router choices."""
    seen = []

    def record(idx, mask):
        seen.append((np.asarray(idx), np.asarray(mask)))

    def wrapped(params, cfg, x, row_mask=None):
        if row_mask is not None:
            _, idx = moe_mod.select_experts(params, cfg.moe, x)
            jax.debug.callback(record, idx, row_mask)
        return moe_mod.moe_dropless(params, cfg, x, row_mask)

    monkeypatch.setattr(tf_mod, "moe_dropless", wrapped)
    cfg = _cfg()
    eng = Engine(cfg, _params(cfg), slots=3, max_len=48)
    reqs = [Request(np.arange(3, 3 + n, dtype=np.int32), max_new_tokens=m,
                    rid=n) for n, m in ((4, 6), (7, 3), (5, 5), (6, 4))]
    eng.generate(reqs)
    first, n = cfg.moe.held_range
    want = np.zeros((n,), np.int64)
    for idx, mask in seen:
        local = idx[mask] - first
        want += np.bincount(local[(local >= 0) & (local < n)], minlength=n)
    assert want.sum() > 0
    assert eng.serve_stats["moe_routed"] == want.tolist()


def test_engine_refuses_chunked_prefill_for_latent_attention():
    cfg = _cfg()
    with pytest.raises(ValueError, match="latent attention"):
        Engine(cfg, _params(cfg), slots=2, max_len=48, prefill_chunk=8)


def test_bench_config_widths_equal_the_registry_entry():
    """The benchmark's Moonlight file and the program's arch id describe
    the same model: every width as published, the depth and the experts
    held as the file's cut."""
    conf = json.loads((ROOT / "bench" / "configs" /
                       "moonlight-16b-a3b.json").read_text())
    cfg = get_config(conf["arch"])
    m, e = cfg.mla, cfg.moe
    assert (conf["hidden_size"], conf["intermediate_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["vocab_size"]) == (cfg.d_model, cfg.d_ff, cfg.num_heads,
                                    cfg.num_kv_heads, cfg.vocab_size)
    assert (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
            conf["qk_rope_head_dim"], conf["v_head_dim"]) == (
        m.kv_rank, m.nope_dim, m.rope_dim, m.v_dim)
    assert conf["q_lora_rank"] is None
    assert (conf["moe_intermediate_size"], conf["num_experts_per_tok"],
            conf["n_shared_experts"], conf["routed_scaling_factor"]) == (
        e.d_expert, e.top_k, e.num_shared, e.routed_scale)
    assert conf["norm_topk_prob"]          # the program always normalises
    assert conf["scoring_func"] == e.scoring and e.score_bias
    assert (conf["first_k_dense_replace"], conf["rms_norm_eps"],
            conf["rope_theta"], conf["tie_word_embeddings"]) == (
        cfg.first_dense, cfg.norm_eps, cfg.rope_theta, cfg.tie_embeddings)
    assert conf["published"] == {"num_hidden_layers": cfg.num_layers,
                                 "n_routed_experts": e.num_experts}
    assert tuple(conf["held_experts"]) == e.held_range
    assert conf["n_routed_experts"] == e.held_range[1]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("f", [48, 256], ids=["whole-width", "lane-slices"])
def test_moe_experts_kernel_matches_ref(dtype, f):
    """The grouped SwiGLU kernel (interpret mode) against its jnp oracle:
    tiles of four experts in any order, the tiles past the count left
    out, float32 weights cast to the rows' dtype, the expert width whole
    or in 128-lane slices."""
    from repro.kernels import ops

    tm, d, e = 16, 32, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (6 * tm, d)).astype(dtype)
    wg = jax.random.normal(keys[1], (e, d, f)) / 6
    wu = jax.random.normal(keys[2], (e, d, f)) / 6
    wd = jax.random.normal(keys[3], (e, f, d)) / 8
    groups = jnp.asarray([2, 0, 0, 3, 1, 1], jnp.int32)
    n = jnp.asarray([5], jnp.int32)
    got = ops.moe_experts(x, groups, n, wg, wu, wd, tm=tm, impl="interpret")
    want = ops.moe_experts(x, groups, n, wg, wu, wd, tm=tm, impl="ref")
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got[:5 * tm], np.float32),
                               np.asarray(want[:5 * tm], np.float32),
                               atol=tol, rtol=tol)


def test_offload_leaves_a_highest_precision_dot_to_xla():
    """The router's float32 dot at the highest precision stays far: the
    fused kernels would run it at their own precision.  The same dot at
    the default precision anchors a near segment."""
    from repro.core.offload import mpu_offload
    from repro.core.policy import OffloadPolicy

    def router(h, w, precision):
        g = jnp.dot(h.astype(jnp.float32), w, precision=precision)
        return jax.nn.sigmoid(g) * 2.0

    args = (jnp.ones((64, 256), jnp.bfloat16), jnp.ones((256, 64)))
    fused = {}
    for precision in (None, jax.lax.Precision.HIGHEST):
        wrapped = mpu_offload(lambda h, w: router(h, w, precision),
                              policy=OffloadPolicy(bulk_threshold=64))
        fused[precision] = [d.tier for d in
                            wrapped.explain(*args).all_decisions() if d.fused]
    assert "anchor" in fused[None]
    assert "anchor" not in fused[jax.lax.Precision.HIGHEST]
