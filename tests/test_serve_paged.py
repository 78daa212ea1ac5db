"""Paged serving: page pool, paged decode kernel, continuous batching.

Covers the contracts the paged engine is built on:

* ``PagePool`` allocator semantics (page-0 scratch reservation,
  all-or-nothing growth, free/evict);
* the paged decode kernel against its gather-then-attend oracle
  (GQA, ragged lengths, stale/zero block-table entries, f32 + bf16);
* the head-major in-place decode read path;
* paged ``Engine`` == dense ``FixedSlotEngine`` token-for-token across
  page boundaries, under churn, with chunked prefill and preemption;
* the zero-retrace steady state: churning admits/evicts/decodes leave
  ``offload_stats`` at ``plan_misses == traces == 1`` and freeze the
  engine's jit trace counters after one warmup per shape bucket.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import build_model
from repro.serve import (
    Engine,
    FixedSlotEngine,
    PagePool,
    Request,
    bucket_length,
    ceil_pow2,
)

from conftest import tiny


def _rand(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _tol(dtype):
    return (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
            else dict(rtol=2e-5, atol=2e-5))


# ---------------------------------------------------------------- kv_pool
def test_ceil_pow2_and_bucketing():
    assert [ceil_pow2(n) for n in (1, 2, 3, 4, 5, 17, 64)] == \
        [1, 2, 4, 4, 8, 32, 64]
    assert bucket_length(6, 32) == 8
    assert bucket_length(33, 32) == 32      # clamped to capacity
    assert bucket_length(200, 32) == 32
    assert bucket_length(1, 32) == 1


def test_page_pool_alloc_free_cycle():
    pool = PagePool(num_pages=8, page_size=4, table_width=4, slots=2)
    assert pool.free_pages == 7             # page 0 reserved
    assert pool.alloc(0, 3)
    assert pool.allocated(0) == 3
    assert (pool.tables[0, :3] > 0).all()   # never hands out scratch page 0
    assert pool.tables[0, 3] == 0
    assert pool.ensure(0, 2)                # already satisfied
    assert pool.allocated(0) == 3
    assert pool.alloc(1, 4)
    assert not pool.alloc(0, 1)             # exhausted: all-or-nothing
    assert pool.free_pages == 0
    assert pool.free_slot(1) == 4
    assert pool.free_pages == 4
    assert (pool.tables[1] == 0).all()
    assert pool.alloc(0, 1)                 # recycled pages come back
    assert not pool.ensure(0, 5)            # exceeds table_width
    assert pool.pages_for(9) == 3


def test_page_pool_rejects_degenerate():
    with pytest.raises(ValueError):
        PagePool(num_pages=1, page_size=4, table_width=1, slots=1)


# ------------------------------------------------------- paged decode kernel
def _case(b, np_, page, nq, nk, h, lens=None):
    name = "-".join(map(str, (b, np_, page, nq, nk, h)))
    if lens is not None:
        name += "-len" + "_".join(map(str, lens))
    return pytest.param(b, np_, page, nq, nk, h, lens, id=name)


@pytest.mark.parametrize("b,np_,page,nq,nk,h,lens", [
    _case(2, 4, 64, 8, 2, 32),
    _case(3, 3, 32, 4, 4, 16),
    _case(1, 8, 16, 2, 1, 64),
    # G = 2 and G = 1 over widths 8 does not divide (3, 6 and 12 pages
    # a step split as 3, 6 and 6): an empty row, lengths on a page
    # boundary, a full table, a row ending inside a later grid step
    _case(3, 6, 16, 4, 2, 32, (0, 16, 96)),
    _case(4, 3, 16, 8, 8, 16, (0, 32, 48, 17)),
    _case(3, 12, 16, 4, 4, 32, (48, 192, 100)),
    _case(2, 12, 16, 4, 2, 32, (192, 0)),
    # 16 pages at 8 a step: the second step partly and wholly past
    _case(3, 16, 8, 4, 2, 32, (70, 128, 8)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_ref(b, np_, page, nq, nk, h, lens,
                                            dtype):
    pool_pages = 1 + b * np_
    q = _rand(0, (b, nq, h), dtype)
    k_pages = _rand(1, (pool_pages, nk, page, h), dtype)
    v_pages = _rand(2, (pool_pages, nk, page, h), dtype)
    rng = np.random.default_rng(0)
    # permuted non-contiguous page assignment, as the pool produces
    perm = rng.permutation(np.arange(1, pool_pages))
    tables = jnp.asarray(perm.reshape(b, np_).astype(np.int32))
    lengths = jnp.asarray(
        rng.integers(1, np_ * page + 1, size=(b,)) if lens is None
        else lens, jnp.int32)
    out = ops.paged_decode_attention(q, k_pages, v_pages, tables, lengths,
                                     impl="interpret")
    want = ref.ref_paged_decode_attention(q, k_pages, v_pages, tables,
                                          lengths)
    # an empty row (a free slot) attends to nothing and reads zeros; the
    # oracle's softmax over an all-masked row is no answer to compare
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[live],
        np.asarray(want, np.float32)[live], **_tol(dtype))
    np.testing.assert_array_equal(np.asarray(out, np.float32)[~live], 0)
    # the same pool as layer 1 of three, read through the flattened
    # stack at page offset 1 * P as the decode layer scan reads it
    k_stack = jnp.stack([_rand(3, k_pages.shape, dtype), k_pages,
                         _rand(4, k_pages.shape, dtype)])
    v_stack = jnp.stack([_rand(5, v_pages.shape, dtype), v_pages,
                         _rand(6, v_pages.shape, dtype)])
    flat_k = k_stack.reshape(-1, nk, page, h)
    flat_v = v_stack.reshape(-1, nk, page, h)
    for layer in range(3):
        own = out if layer == 1 else ops.paged_decode_attention(
            q, k_stack[layer], v_stack[layer], tables, lengths,
            impl="interpret")
        got = ops.paged_decode_attention(
            q, flat_k, flat_v, tables + layer * pool_pages, lengths,
            impl="interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(own))


def test_paged_decode_ignores_pages_past_length():
    """Entries past ``lengths`` — including unallocated 0 (scratch) ids —
    must not affect the output: the engine relies on this to leave stale
    table tails in place."""
    b, np_, page, nq, nk, h = 2, 4, 16, 4, 2, 32
    q = _rand(0, (b, nq, h), jnp.float32)
    k_pages = _rand(1, (1 + b * np_, nk, page, h), jnp.float32)
    v_pages = _rand(2, (1 + b * np_, nk, page, h), jnp.float32)
    tables = jnp.asarray(
        np.arange(1, 1 + b * np_, dtype=np.int32).reshape(b, np_))
    lengths = jnp.asarray([page + 3, 2 * page], jnp.int32)  # 1-2 live pages
    base = ops.paged_decode_attention(q, k_pages, v_pages, tables, lengths,
                                      impl="interpret")
    # scramble the dead tail: zero ids and garbage ids alike
    scrambled = np.asarray(tables).copy()
    scrambled[0, 2:] = 0
    scrambled[1, 2:] = [b * np_, 1]
    out = ops.paged_decode_attention(q, k_pages, v_pages,
                                     jnp.asarray(scrambled), lengths,
                                     impl="interpret")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


def test_paged_decode_streams_no_page_past_length():
    """The K/V index maps (``streamed_page``) in grid order, as the
    pipeline walks them: an input fetches a page only when its block
    index changes, so every fetch of row b is one of its used pages or
    the pool's page 0 (parked), and each used page is fetched once."""
    from repro.kernels.decode_attention import streamed_page
    page, per, width = 16, 4, 12
    lengths = np.array([0, 16, 17, 100, 192, 64, 1], np.int32)
    b = len(lengths)
    tables = np.arange(1, 1 + b * width, dtype=np.int32).reshape(b, width)
    held = {}
    for bb in range(b):
        last = max(lengths[bb] - 1, 0) // page
        fetched = []
        for i in range(width // per):
            for j in range(per):
                got = int(streamed_page(tables, lengths, bb, i, j,
                                        page=page, per=per))
                if i * per + j <= last:
                    assert got == tables[bb, i * per + j]
                elif j <= last:       # keeps its last page of the row
                    assert got == tables[bb, i * per + j - per * (
                        (i * per + j - last + per - 1) // per)]
                else:                 # never used by this row: parked
                    assert got == 0
                if held.get(j) != got:
                    fetched.append(got)
                    held[j] = got
        used = list(tables[bb, :last + 1])
        assert sorted(p for p in fetched if p) == sorted(used)
    # an empty row reads only its first table entry
    assert int(streamed_page(tables, lengths, 0, 2, 0, page=page,
                             per=per)) == tables[0, 0]


@pytest.mark.parametrize("width,page_bytes,per", [
    (32, 8 * 64 * 128 * 2, 8),       # Qwen3-1.7B's pages, chat
    (128, 8 * 64 * 128 * 2, 8),      # the same, rag's wider table
    (64, 32 * 64 * 128 * 2, 2),      # DeepSeek-7B's MHA pages
    (12, 4096, 6), (3, 4096, 3), (6, 4096, 6),
    (7, 4 << 20, 1),                 # not even one pair fits: 1
])
def test_paged_decode_pages_per_step(width, page_bytes, per):
    from repro.kernels.decode_attention import KV_STEP_BYTES, pages_per_step
    got = pages_per_step(width, page_bytes)
    assert got == per and width % got == 0
    assert got == 1 or 2 * got * page_bytes <= KV_STEP_BYTES


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_head_major_matches_ref(dtype):
    b, t, nq, nk, h = 3, 100, 4, 2, 32
    q = _rand(0, (b, nq, h), dtype)
    kc = _rand(1, (b, nk, t, h), dtype)     # head-major [B,NK,T,H]
    vc = _rand(2, (b, nk, t, h), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, t + 1, size=(b,)), jnp.int32)
    out = ops.decode_attention(q, kc, vc, lengths, impl="interpret",
                               head_major=True, kv_block=64)
    want = ref.ref_decode_attention(q, kc.transpose(0, 2, 1, 3),
                                    vc.transpose(0, 2, 1, 3), lengths)
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), **_tol(dtype))


# ------------------------------------------------- stacked pools in the scan
def _layerwise_stack_decode(params, cfg, x, cache, pos, block_tables,
                            active, *, max_len):
    """The decode stack with each layer's own pool sliced into the layer
    scan and restacked out of it: the layout the carried pools replace."""
    from repro.models.transformer import _pattern_layout, block_decode_paged

    pattern, _, rem = _pattern_layout(cfg, cfg.num_layers)

    def block(bp, kind, h, c):
        bp = params["shared_attn"] if kind == "shared_attention" else bp
        return block_decode_paged(bp, cfg, kind, h, c, pos, block_tables,
                                  active, max_len=max_len)[:2]

    def period(h, inp):
        pp, pc = inp
        out = {}
        for i, kind in enumerate(pattern):
            h, out[str(i)] = block(pp.get(str(i)), kind, h, pc[str(i)])
        return h, out

    x, stack = jax.lax.scan(period, x, (params["stack"], cache["stack"]))
    new_rem = {}
    for i in range(rem):
        x, new_rem[str(i)] = block(params["rem"].get(str(i)), pattern[i],
                                   x, cache["rem"][str(i)])
    return x, {"stack": stack, "rem": new_rem}


def _written_rows(old, new):
    """(page, offset) of every pool row the step changed."""
    diff = np.any(np.asarray(old) != np.asarray(new), axis=(1, 3))
    return set(zip(*map(lambda a: a.tolist(), np.nonzero(diff))))


@pytest.mark.parametrize("arch,over", [
    ("qwen3-1.7b", dict(num_layers=3)),
    ("mixtral-8x7b", dict(num_layers=3, sliding_window=8, moe=None)),
    ("zamba2-1.2b", dict(num_layers=5,
                         block_pattern=("shared_attention", "mamba2"))),
], ids=["attention", "swa", "hybrid-remainder"])
def test_stacked_pool_decode_matches_layerwise(monkeypatch, arch, over):
    """Several decode steps through ``stack_decode_paged`` with the
    stacked pools carried through the layer scan: logits and every
    cache leaf bit-identical to the layer-by-layer pools, and each
    layer's rows (scratch writes of inactive slots included) written
    only inside that layer's own pool."""
    import repro.models.model as model_mod

    cfg = tiny(arch, **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    slots, page, max_len = 3, 4, 16
    n_pages = 1 + slots * (max_len // page)
    cache = jax.tree.map(lambda a: _rand(a.size, a.shape, a.dtype),
                         model.init_paged_cache(slots, n_pages, page))
    perm = np.random.default_rng(1).permutation(np.arange(1, n_pages))
    tables = jnp.asarray(perm.reshape(slots, -1).astype(np.int32))
    tok = jnp.asarray([3, 17, 40], jnp.int32)
    pos = jnp.asarray([5, 9, 11], jnp.int32)
    active = jnp.asarray([True, False, True])
    cap = min(max_len, cfg.sliding_window or max_len)

    def jit_step():     # a function of its own: jit caches by function
        def step(params, cache, tok, pos):
            return model.decode_step_paged(params, cache, tok, pos, tables,
                                           active, max_len=max_len)
        return jax.jit(step)

    carried, layerwise = jit_step(), jit_step()
    got_cache = want_cache = cache
    for _ in range(3):
        with monkeypatch.context() as m:     # traced on the first call
            m.setattr(model_mod, "stack_decode_paged",
                      _layerwise_stack_decode)
            want, want_cache = layerwise(params, want_cache, tok, pos)
        got, new_cache = carried(params, got_cache, tok, pos)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for g, w in zip(jax.tree.leaves(new_cache),
                        jax.tree.leaves(want_cache)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        slot = pos % cap if cfg.sliding_window else np.minimum(pos, cap - 1)
        expect = {(int(tables[b, slot[b] // page]) if active[b] else 0,
                   int(slot[b] % page)) for b in range(slots)}
        pools = [(got_cache[part][key], new_cache[part][key])
                 for part in ("stack", "rem")
                 for key in got_cache[part] if "k" in got_cache[part][key]]
        assert pools
        for old, new in pools:
            for name in ("k", "v"):
                shape = (-1,) + old[name].shape[-4:]    # one pool per layer
                for o, n in zip(old[name].reshape(shape),
                                new[name].reshape(shape)):
                    assert _written_rows(o, n) == expect
        got_cache = new_cache
        # every row moves on, so each step writes rows not yet written
        tok, pos = jnp.argmax(got, -1).astype(jnp.int32), pos + 1


# ------------------------------------------------------------------ engine
def _mk(arch="qwen3-1.7b", **over):
    cfg = tiny(arch, num_layers=2, **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _prompts(n, lo=5, hi=24, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(1, 250, size=rng.integers(lo, hi)).astype(
        np.int32), max_new_tokens=6, rid=i) for i in range(n)]


def test_paged_engine_matches_fixed_slot_across_page_boundaries():
    """page_size=8 with generation crossing several page boundaries —
    tokens must match the dense fixed-slot engine exactly (greedy)."""
    cfg, params = _mk()
    reqs = _prompts(6)
    paged = Engine(cfg, params, slots=2, max_len=48, page_size=8)
    fixed = FixedSlotEngine(cfg, params, slots=2, max_len=48)
    got = paged.generate([dataclasses.replace(r) for r in reqs])
    want = fixed.generate([dataclasses.replace(r) for r in reqs])
    for r in reqs:
        assert got[r.rid].tokens == want[r.rid].tokens, r.rid
        assert len(got[r.rid].tokens) == r.max_new_tokens


def test_paged_engine_swa_matches_fixed_slot():
    """SWA rolling pages: window < prompt + generation, exact match."""
    cfg, params = _mk("mixtral-8x7b", sliding_window=8, moe=None)
    reqs = [Request(np.arange(2, 2 + n, dtype=np.int32), max_new_tokens=8,
                    rid=i) for i, n in enumerate((6, 11, 4))]
    paged = Engine(cfg, params, slots=2, max_len=32, page_size=4)
    fixed = FixedSlotEngine(cfg, params, slots=2, max_len=32)
    got = paged.generate([dataclasses.replace(r) for r in reqs])
    want = fixed.generate([dataclasses.replace(r) for r in reqs])
    for r in reqs:
        assert got[r.rid].tokens == want[r.rid].tokens, r.rid


def test_paged_engine_recurrent_family_matches_fixed_slot():
    """mamba2 blocks carry per-slot state rows, not pages — inactive
    rows must stay frozen batch-wide."""
    cfg, params = _mk("zamba2-1.2b")
    reqs = _prompts(4, lo=4, hi=12, seed=3)
    paged = Engine(cfg, params, slots=2, max_len=32, page_size=8)
    fixed = FixedSlotEngine(cfg, params, slots=2, max_len=32)
    got = paged.generate([dataclasses.replace(r) for r in reqs])
    want = fixed.generate([dataclasses.replace(r) for r in reqs])
    for r in reqs:
        assert got[r.rid].tokens == want[r.rid].tokens, r.rid


def test_zero_retrace_steady_state_single_bucket():
    """100 mixed admit/evict/decode steps in one shape bucket: the
    offloaded decode plans/traces once, admit traces once."""
    cfg, params = _mk()
    eng = Engine(cfg, params, slots=2, max_len=32, page_size=8,
                 offload=True)
    rng = np.random.default_rng(1)
    reqs = [Request(rng.integers(1, 250, size=rng.integers(5, 8)).astype(
        np.int32), max_new_tokens=4, rid=i) for i in range(24)]
    done = eng.generate(reqs)
    assert all(len(done[r.rid].tokens) == 4 for r in reqs)
    st = eng.offload_stats
    assert st["traces"] == 1 and st["plan_misses"] == 1, st
    sv = eng.serve_stats
    assert sv["admit_traces"] == 1 and sv["step_traces"] == 1, sv
    assert sv["pages_used"] == 0                  # all pages recycled


def test_zero_retrace_one_trace_per_bucket():
    """Prompts spanning pow2 buckets: one admit trace per bucket, then
    the counters freeze — repeating the workload adds zero traces."""
    cfg, params = _mk()
    eng = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                 offload=True)

    def run(seed):
        rng = np.random.default_rng(seed)
        lens = [3, 7, 12, 20, 3, 9, 17, 30]       # buckets 4/8/16/32
        reqs = [Request(rng.integers(1, 250, size=n).astype(np.int32),
                        max_new_tokens=3, rid=i) for i, n in enumerate(lens)]
        return eng.generate(reqs)

    run(0)
    warm = dict(eng.serve_counters)
    assert warm["admit_traces"] == 4, warm        # one per pow2 bucket
    run(1)                                        # same buckets again
    assert eng.serve_counters["admit_traces"] == warm["admit_traces"]
    assert eng.serve_counters["step_traces"] == 1
    assert eng.offload_stats["traces"] == 1
    assert eng.offload_stats["plan_misses"] == 1


def test_chunked_prefill_matches_full_prefill():
    cfg, params = _mk(sliding_window=0)
    prompts = [np.arange(3, 3 + n, dtype=np.int32) % 250
               for n in (21, 13, 30)]
    reqs = lambda: [Request(p, max_new_tokens=6, rid=i)
                    for i, p in enumerate(prompts)]
    full = Engine(cfg, params, slots=2, max_len=64, page_size=8)
    chunked = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                     prefill_chunk=8)
    want = full.generate(reqs())
    got = chunked.generate(reqs())
    for i in range(len(prompts)):
        assert got[i].tokens == want[i].tokens, i
    assert chunked.serve_counters["chunk_traces"] == 1


def test_preemption_by_recompute_is_exact():
    """A pool too small for all admitted requests forces preemption;
    preempted requests recompute and still emit identical tokens."""
    cfg, params = _mk(sliding_window=0)
    prompts = [np.arange(3, 3 + n, dtype=np.int32) % 250
               for n in (21, 15, 30)]
    reqs = lambda: [Request(p, max_new_tokens=10, rid=i)
                    for i, p in enumerate(prompts)]
    roomy = Engine(cfg, params, slots=3, max_len=64, page_size=8)
    # 6 free pages: reqs 0+1 admit (4+2), then req 1's growth at the
    # page-16 boundary finds the free list empty and must evict
    tight = Engine(cfg, params, slots=3, max_len=64, page_size=8,
                   num_pages=1 + 6)
    want = roomy.generate(reqs())
    got = tight.generate(reqs())
    assert tight.serve_counters["preemptions"] > 0
    for i in range(len(prompts)):
        assert got[i].tokens == want[i].tokens, i


def test_paged_pool_smaller_than_fixed_cache():
    """The sizing claim behind the bench: at equal concurrency the paged
    pool addresses KV for live tokens, not slots*max_len."""
    cfg, params = _mk()
    eng = Engine(cfg, params, slots=4, max_len=256, page_size=16,
                 num_pages=1 + 24)
    done = eng.generate(_prompts(8, lo=10, hi=40, seed=5))
    assert all(len(c.tokens) == 6 for c in done.values())
    # fixed-slot equivalent would pin 4 * 256 = 1024 positions; the pool
    # held at most 24 pages * 16 = 384
    assert eng.num_pages * eng.page_size < 4 * 256


def test_verify_paged_tables_catches_corruption():
    """The static bounds proof over the live page tables: clean after
    real traffic (padding entries included — the decode kernel gathers
    them on masked grid steps), and a poisoned entry or an impossible
    slot length is reported with its rule id."""
    cfg, params = _mk()
    eng = Engine(cfg, params, slots=2, max_len=32, page_size=8)
    assert eng.verify_paged_tables() == []
    eng.generate(_prompts(3, lo=5, hi=12, seed=7))
    assert eng.verify_paged_tables() == []
    eng.pool.tables[0, 1] = eng.num_pages + 7
    rules = {f.rule for f in eng.verify_paged_tables()}
    assert "page-table-bounds" in rules


def test_offloaded_paged_decode_with_kernel_matches(monkeypatch):
    """The decode step on its paged-kernel branch (the TPU path, here in
    interpret mode) through the offload rewriter: the rewritten program
    re-binds the ``pallas_call`` eqn among its segments, and its logits
    and page pools equal the un-offloaded step's."""
    import repro.kernels.guard as guard
    import repro.models.attention as attention
    from repro.core.offload import mpu_offload
    from repro.core.policy import OffloadPolicy

    monkeypatch.setattr(attention, "_paged_kernel", lambda: True)
    monkeypatch.setattr(guard, "default_impl", lambda: "interpret")
    cfg, params = _mk(dtype="float32")
    model = build_model(cfg)
    slots, page, max_len = 4, 8, 32
    pages = 1 + slots * (max_len // page)
    cache = model.init_paged_cache(slots, pages, page)
    cache = jax.tree.map(
        lambda a: _rand(a.size, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, cache)
    args = (params, cache, jnp.asarray([3, 17, 250, 9], jnp.int32),
            jnp.asarray([5, 8, 15, 30], jnp.int32),
            jnp.arange(1, pages, dtype=jnp.int32).reshape(slots, -1),
            jnp.asarray([True, True, False, True]))

    def paged_decode(params, cache, tok, pos, tables, active):
        return model.decode_step_paged(params, cache, tok, pos, tables,
                                       active, max_len=max_len)

    assert "pallas_call" in str(jax.make_jaxpr(paged_decode)(*args))
    wrapped = mpu_offload(paged_decode, policy=OffloadPolicy(
        bulk_threshold=64, impl="interpret"))
    assert wrapped.explain(*args).n_fused >= 1
    got = jax.jit(wrapped)(*args)
    want = jax.jit(paged_decode)(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
