"""The compile-time offload path: plan cache, jit composition, rewriter.

Covers the acceptance contract of the rewriter:
  * plan-cache hit/miss/eviction accounting (LRU keyed by aval
    signature, bounded by ``max_plans``)
  * ``jax.jit(mpu_offload(fn))`` numerical equivalence vs plain ``fn``
    (including a ``scan`` body and a ``pjit``-nested jaxpr) with no
    tracer leaks
  * zero retraces on a second call with identical avals
  * the rewritten ClosedJaxpr replaces each near segment with a single
    ``pallas_call`` eqn and evaluates to the same values
  * cross-shape fusion: pjit-wrapped elementwise helpers (silu) are
    flattened, broadcast params ([C]/[1,C]/scalar), row-broadcast
    operands ([B,1,D]) and lane splits fuse into one segment across
    dtypes
  * segment-boundary donation: dead boundary buffers appear as Pallas
    ``input_output_aliases`` in the rewritten jaxpr, and donated-invar
    execution stays correct (the aliased buffer is never read after
    the kernel writes it)
  * nested-pjit fidelity: shardings/donated_invars survive the rewrite
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    mpu_offload,
    offload_report,
    rewrite_offload,
)
from repro.kernels import ops as kops


def _chain(x, y):
    h = jnp.tanh(x) * 2.0 + y
    return h * jax.nn.sigmoid(h)


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _pallas_calls(jaxpr):
    """All pallas_call eqns in a jaxpr, descending into call bodies —
    fused segments are wrapped in ``custom_vjp_call`` since the
    grad-through-offload PR, so the kernel eqn sits one level down."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            found.append(e)
        for v in e.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                inner = getattr(u, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    found.extend(_pallas_calls(inner))
                elif hasattr(u, "eqns"):
                    found.extend(_pallas_calls(u))
    return found


def test_plan_cache_hit_miss_counting():
    fn = mpu_offload(_chain, bulk_threshold=64, impl="interpret")
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    fn(x, y)
    assert fn.stats.plan_misses == 1 and fn.stats.plan_hits == 0
    fn(x, y)
    assert fn.stats.plan_misses == 1 and fn.stats.plan_hits == 1
    # a new aval signature compiles a second entry; the old one stays
    x2, y2 = _rand((128, 32)), _rand((128, 32), 1)
    fn(x2, y2)
    assert fn.stats.plan_misses == 2 and fn.cache_size() == 2
    fn(x, y)
    assert fn.stats.plan_hits == 2 and fn.stats.plan_misses == 2


def test_zero_retraces_on_repeated_call():
    fn = mpu_offload(_chain, bulk_threshold=64, impl="interpret")
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    fn(x, y)
    traces_after_first = fn.stats.traces
    assert traces_after_first == 1
    for _ in range(5):
        fn(x, y)
    assert fn.stats.traces == traces_after_first  # zero re-planning/tracing


def test_jit_of_offloaded_matches_plain():
    fn = mpu_offload(_chain, bulk_threshold=64, impl="interpret")
    jitted = jax.jit(fn)
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    got = jitted(x, y)          # must not leak tracers
    want = _chain(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    got2 = jitted(x + 1.0, y)   # second call through the jit cache
    np.testing.assert_allclose(np.asarray(got2),
                               np.asarray(_chain(x + 1.0, y)),
                               rtol=1e-5, atol=1e-5)


def test_offload_scan_body_compiled_once():
    w = _rand((64, 64), 2) * 0.1

    def f(x):
        def body(c, _):
            h = c @ w
            h = jax.nn.gelu(h) * 1.5 + c
            return h, jnp.sum(h)
        return jax.lax.scan(body, x, None, length=4)

    x = _rand((128, 64), 3)
    fn = mpu_offload(f, bulk_threshold=512, impl="interpret")
    got = jax.jit(fn)(x)
    want = f(x)
    for g, wv in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                   rtol=1e-5, atol=1e-6)
    # the scan body was planned at rewrite time, and only once
    assert fn.stats.traces == 1
    plan = fn.plan_for(x)
    assert plan.total_segments > len(plan.segments), \
        "expected near segments inside the scan body"


def test_offload_pjit_nested_jaxpr():
    inner = jax.jit(lambda h: jax.nn.gelu(h) * 1.5 + h)

    def f(x, y):
        h = inner(x * 0.5 + y)
        return h + x

    x, y = _rand((128, 64)), _rand((128, 64), 1)
    fn = mpu_offload(f, bulk_threshold=64, impl="interpret")
    got = jax.jit(fn)(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(f(x, y)),
                               rtol=1e-5, atol=1e-5)
    plan = fn.plan_for(x, y)
    assert plan.total_segments >= 1
    assert fn.stats.traces == 1


def test_rewritten_jaxpr_fuses_segment_to_single_eqn():
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    closed = jax.make_jaxpr(_chain)(x, y)
    rewritten, plan = rewrite_offload(closed, bulk_threshold=64,
                                      impl="interpret")
    assert len(plan.segments) == 1
    # 5 elementwise eqns -> ONE fused launch (wrapped in its custom VJP)
    assert len(rewritten.jaxpr.eqns) == 1, rewritten.jaxpr
    assert len(_pallas_calls(rewritten.jaxpr)) == 1
    out = jax.core.eval_jaxpr(rewritten.jaxpr, rewritten.consts, x, y)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(_chain(x, y)),
                               rtol=1e-5, atol=1e-5)


def test_fused_segment_multi_output():
    def seg(x, y):
        h = jnp.tanh(x) + y
        return h * 2.0, h * h

    x, y = _rand((64, 32)), _rand((64, 32), 1)
    outs = kops.fused_segment(seg, [x, y],
                              out_dtypes=[x.dtype, x.dtype],
                              impl="interpret")
    assert isinstance(outs, tuple) and len(outs) == 2
    want = seg(x, y)
    for g, w in zip(outs, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_compiled_matches_plain_function():
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    compiled = mpu_offload(_chain, bulk_threshold=64, impl="interpret")
    np.testing.assert_allclose(np.asarray(compiled(x, y)),
                               np.asarray(_chain(x, y)),
                               rtol=1e-5, atol=1e-5)


def test_offload_report_still_exposes_plan():
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    plan = offload_report(_chain, x, y, bulk_threshold=64)
    assert plan.segments and plan.traffic_reduction >= 1.0
    # same plan shape as the one the compiled wrapper caches
    fn = mpu_offload(_chain, bulk_threshold=64, impl="interpret")
    cached = fn.plan_for(x, y)
    assert len(cached.segments) == len(plan.segments)
    assert cached.segments[0].eqn_idx == plan.segments[0].eqn_idx


# ---------------------------------------------------------------------------
# cross-shape fusion
# ---------------------------------------------------------------------------

def test_swiglu_pjit_body_flattened_and_fused():
    """jax.nn.silu's pjit wrapper must not cut the segment: the whole
    epilogue is one fused launch with a real traffic reduction."""
    def swiglu(x, y):
        return jax.nn.silu(x) * y

    x, y = _rand((128, 64)), _rand((128, 64), 1)
    plan = offload_report(swiglu, x, y, bulk_threshold=64)
    assert len(plan.segments) == 1
    assert plan.traffic_reduction > 1.5
    got = mpu_offload(swiglu, bulk_threshold=64, impl="interpret")(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(swiglu(x, y)),
                               rtol=1e-5, atol=1e-5)


def test_broadcast_fusion_numerics_vs_ref_dtypes():
    """[C] / [1,C] / scalar broadcast operands fuse into the segment and
    match the pure-jnp reference across dtypes."""
    def chain(x, y, s, b):
        h = jnp.tanh(x) * s + b          # [C] scale and bias
        h = h + y * 0.5                  # scalar literal
        return h * jax.nn.sigmoid(h)

    for dtype, rtol in ((jnp.float32, 1e-5), (jnp.bfloat16, 5e-2)):
        x = _rand((64, 32)).astype(dtype)
        y = _rand((64, 32), 1).astype(dtype)
        s = (jnp.ones((32,)) * 1.1).astype(dtype)
        b = _rand((32,), 2).astype(dtype)
        plan = offload_report(chain, x, y, s, b, bulk_threshold=64)
        assert len(plan.segments) == 1, (dtype, plan.segments)
        got = mpu_offload(chain, bulk_threshold=64, impl="interpret")(
            x, y, s, b)
        want = chain(x, y, s, b)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=rtol, atol=rtol)


def test_row_broadcast_rep_operand_fuses():
    """[B,1,D] against [B,S,D] fuses via a rep index map instead of
    ending the segment.  Each block reads one operand row, fetched in a
    block that meets the TPU tiling rule (here the whole [B, D] array)
    and picked in VMEM."""
    def gated(a, m):
        return jnp.tanh(a) * m + a * 0.5

    a = _rand((4, 64, 32))
    m = _rand((4, 1, 32), 1)
    plan = offload_report(gated, a, m, bulk_threshold=1024)
    assert len(plan.segments) == 1
    roles = {sp.role for sp in plan.segments[0].operand_specs}
    assert "rep" in roles
    assert not plan.segments[0].tiling_violations()
    got = mpu_offload(gated, bulk_threshold=1024, impl="interpret")(a, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gated(a, m)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s", [(2, 1024), (4, 8), (256, 16)])
def test_rep_operand_row_spans(b, s, dtype):
    """A [B,1,D] rep operand against [B,S,D] under each way a row block
    can meet it: one operand row per block (S=1024), a whole-array
    block of several repeats (S=8), and 8-row spans fetched in 128-row
    blocks of a 256-row operand (S=16).  f32 rows are loaded with a
    dynamic one-row slice, bf16 rows picked by a masked sum; both equal
    the un-offloaded function."""
    def gated(a, m):
        return jnp.tanh(a) * m + a * 0.5

    a = _rand((b, s, 128)).astype(dtype)
    m = _rand((b, 1, 128), 1).astype(dtype)
    plan = offload_report(gated, a, m, bulk_threshold=1024)
    (seg,) = plan.segments
    assert "rep" in {sp.role for sp in seg.operand_specs}
    assert not seg.tiling_violations()
    got = mpu_offload(gated, bulk_threshold=1024, impl="interpret")(a, m)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(gated(a, m), np.float32),
                               rtol=tol, atol=tol)


def test_lane_split_swiglu_fuses():
    """The real swiglu shape: [R,2C] lane-split into two [R,C] halves
    stays one segment (slice absorbed as a block-column remap)."""
    def swiglu_split(xw):
        a, g = xw[:, :32], xw[:, 32:]
        return jax.nn.silu(a) * g

    xw = _rand((128, 64))
    plan = offload_report(swiglu_split, xw, bulk_threshold=1024)
    assert len(plan.segments) == 1
    got = mpu_offload(swiglu_split, bulk_threshold=1024,
                      impl="interpret")(xw)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(swiglu_split(xw)),
                               rtol=1e-5, atol=1e-5)


def test_rank1_bulk_broadcast_fuses():
    """Rank-1 [N] values are bulk columns (N, 1), not [1, N] params —
    a jnp.full-style scalar->[N] broadcast inside a rank-1 segment must
    not be misclassified (vacuous all-leading-dims-1)."""
    def f(x):
        y = jnp.tanh(x)
        return y * jnp.full(x.shape, 0.5) + y

    x = jnp.linspace(-1.0, 1.0, 4096)
    w = mpu_offload(f, bulk_threshold=1024, impl="interpret")
    np.testing.assert_allclose(np.asarray(w(x)), np.asarray(f(x)),
                               rtol=1e-6, atol=1e-6)
    assert len(w.plan_for(x).segments) == 1


# ---------------------------------------------------------------------------
# segment-boundary donation
# ---------------------------------------------------------------------------

def _two_seg(x, y):
    h = jnp.tanh(x) * 2.0 + y
    h2 = jax.lax.sort(h, dimension=1)       # far: hard segment boundary
    return jax.nn.silu(h2) * 0.5 + 1.0


def test_two_segment_chain_shows_input_output_aliases():
    """A segment input that dies at the segment (here the sort output
    feeding the second segment — sort is far and not anchorable) is
    donated: the fused pallas_call in the rewritten jaxpr carries a
    non-empty ``input_output_aliases``."""
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    closed = jax.make_jaxpr(_two_seg)(x, y)
    rewritten, plan = rewrite_offload(closed, bulk_threshold=64,
                                      impl="interpret")
    assert len(plan.segments) == 2
    assert plan.donated_hbm_bytes > 0
    aliases = [e.params.get("input_output_aliases", ())
               for e in _pallas_calls(rewritten.jaxpr)]
    assert len(aliases) == 2
    assert any(a for a in aliases), aliases   # at least one real alias
    out = jax.core.eval_jaxpr(rewritten.jaxpr, rewritten.consts, x, y)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(_two_seg(x, y)),
                               rtol=1e-5, atol=1e-5)
    assert plan.effective_hbm_bytes < plan.fused_hbm_bytes


def test_matmul_chain_fuses_to_single_anchored_kernel():
    """The PR-2 shape of this chain was two segments around a far
    matmul; the anchored planner now absorbs the prologue AND epilogue
    into one kernel around the dot — one pallas_call, less traffic."""
    def chain(x, y, w):
        h = jnp.tanh(x) * 2.0 + y
        h2 = h @ w
        return jax.nn.silu(h2) * 0.5 + 1.0

    x, y, w = _rand((64, 32)), _rand((64, 32), 1), _rand((32, 32), 2) * 0.1
    closed = jax.make_jaxpr(chain)(x, y, w)
    rewritten, plan = rewrite_offload(closed, bulk_threshold=64,
                                      impl="interpret")
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and seg.matmul.pro_eqns
    assert len(rewritten.jaxpr.eqns) == 1, rewritten.jaxpr
    assert len(_pallas_calls(rewritten.jaxpr)) == 1
    out = jax.core.eval_jaxpr(rewritten.jaxpr, rewritten.consts, x, y, w)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(chain(x, y, w)),
                               rtol=1e-5, atol=1e-5)


def test_donated_invar_not_read_after_write():
    """``donate_argnums`` threads user buffers into the kernels'
    aliases; results must match values computed before donation, and
    repeated calls with fresh buffers stay correct."""
    def adam_like(p, g):
        m = 0.9 * p + 0.1 * g
        v = 0.95 * p + 0.05 * g * g
        return p - 1e-3 * m / (jnp.sqrt(v) + 1e-8)

    fn = mpu_offload(adam_like, bulk_threshold=64, impl="interpret",
                     donate_argnums=(0,))
    p, g = _rand((64, 32)), _rand((64, 32), 1)
    plan = fn.plan_for(p, g)
    assert plan.donated_hbm_bytes > 0
    want = np.asarray(adam_like(p, g))       # before the buffer is donated
    got = fn(p, g)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    p2 = _rand((64, 32), 3)
    want2 = np.asarray(adam_like(p2, g))     # p2 is donated by fn below
    np.testing.assert_allclose(np.asarray(fn(p2, g)), want2,
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# LRU plan cache
# ---------------------------------------------------------------------------

def test_stats_hit_rate_and_repr():
    fn = mpu_offload(_chain, bulk_threshold=64, impl="interpret")
    assert fn.stats.hit_rate == 0.0          # no calls yet
    x, y = _rand((64, 32)), _rand((64, 32), 1)
    fn(x, y)
    assert fn.stats.hit_rate == 0.0          # one miss
    fn(x, y)
    fn(x, y)
    assert abs(fn.stats.hit_rate - 2 / 3) < 1e-9
    assert fn.stats.as_dict()["hit_rate"] == fn.stats.hit_rate
    r = repr(fn.stats)
    assert "plan_evictions=0" in r and "hit_rate=0.667" in r


def test_plan_cache_lru_eviction_accounting():
    fn = mpu_offload(_chain, bulk_threshold=64, impl="interpret",
                     max_plans=2)
    shapes = [(64, 32), (128, 32), (256, 32)]
    for s in shapes:
        fn(_rand(s), _rand(s, 1))
    assert fn.stats.plan_misses == 3
    assert fn.stats.evictions == 1           # first signature evicted
    assert fn.cache_size() == 2
    # most-recent signatures still hit...
    fn(_rand(shapes[2]), _rand(shapes[2], 1))
    assert fn.stats.plan_hits == 1
    # ...but the evicted one recompiles (and evicts the LRU survivor)
    fn(_rand(shapes[0]), _rand(shapes[0], 1))
    assert fn.stats.plan_misses == 4 and fn.stats.evictions == 2
    # hitting keeps an entry warm: touch shapes[0], insert a new shape,
    # and shapes[0] must survive while the untouched one is evicted
    fn(_rand(shapes[0]), _rand(shapes[0], 1))
    fn(_rand((512, 32)), _rand((512, 32), 1))
    fn(_rand(shapes[0]), _rand(shapes[0], 1))
    assert fn.stats.plan_misses == 5         # shapes[0] was not evicted


def test_scan_carry_donated_inside_body():
    """A scan carry that dies at a body segment is aliased into the
    segment's output (donation inside rewritten scan bodies): the
    rewritten body's pallas_call carries input_output_aliases, the
    inner plan reports donated bytes, and execution stays correct."""
    def fn(x, ys):
        def body(c, y):
            c2 = jnp.tanh(c) * 2.0 + y      # c dies here
            return c2, jnp.sum(c2)
        c, outs = jax.lax.scan(body, x, ys)
        return c, outs

    x, ys = _rand((64, 32)), _rand((4, 64, 32), 1)
    closed = jax.make_jaxpr(fn)(x, ys)
    rewritten, plan = rewrite_offload(closed, bulk_threshold=64,
                                      impl="interpret")
    assert plan.inner_plans and plan.inner_plans[0].donated_hbm_bytes > 0
    aliases = [e.params.get("input_output_aliases", ())
               for e in _pallas_calls(rewritten.jaxpr)]
    assert any(a for a in aliases), aliases
    got = jax.core.eval_jaxpr(rewritten.jaxpr, rewritten.consts, x, ys)
    want = fn(x, ys)
    for g, w in zip(got, jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_scan_passthrough_carry_not_donated():
    """A carry that is ALSO returned from the body (pass-through) must
    not be donated — the planner's outvar check guards it."""
    def fn(x, ys):
        def body(c, y):
            h = jnp.tanh(c) * 2.0 + y
            return c, h                     # c lives on as the carry
        c, outs = jax.lax.scan(body, x, ys)
        return c, outs

    x, ys = _rand((64, 32)), _rand((4, 64, 32), 1)
    closed = jax.make_jaxpr(fn)(x, ys)
    rewritten, plan = rewrite_offload(closed, bulk_threshold=64,
                                      impl="interpret")
    got = jax.core.eval_jaxpr(rewritten.jaxpr, rewritten.consts, x, ys)
    want = fn(x, ys)
    for g, w in zip(got, jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# nested-pjit fidelity
# ---------------------------------------------------------------------------

def test_pjit_donated_invars_survive_rewrite():
    """A non-trivial inner jit (matmul body, donation) is re-emitted as
    a pjit eqn with its donated_invars instead of being inlined away."""
    inner = jax.jit(lambda a, b: (a @ b) * 2.0, donate_argnums=(0,))

    def f(x, w):
        return inner(x, w) + 1.0

    x, w = _rand((32, 32)), _rand((32, 32), 1) * 0.1
    fn = mpu_offload(f, bulk_threshold=64, impl="interpret")
    rewritten = fn.rewritten(x, w)
    pjits = [e for e in rewritten.jaxpr.eqns if e.primitive.name == "jit"]
    assert pjits, "pjit eqn was dropped by the rewrite"
    assert any(any(e.params.get("donated_invars", ())) for e in pjits)
    got = fn(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(f(x, w)),
                               rtol=1e-5, atol=1e-5)


def test_offload_train_and_eval_step_switch():
    import dataclasses
    from repro.configs import get_config, reduced
    from repro.configs.base import TrainConfig
    from repro.models import build_model
    from repro.train.step import init_train_state, make_train_step

    cfg = dataclasses.replace(reduced(get_config("qwen3-1.7b")),
                              dtype="float32", num_layers=2)
    model = build_model(cfg)
    # remat=True is the launcher default and the harder path: the
    # post-grad jaxpr contains closed_call/remat eqns, which have no
    # generic re-bind and must be inlined by the flatten pass
    tcfg = TrainConfig(total_steps=2, remat=True, checkpoint_every=0)
    state = init_train_state(model, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    _, m_plain = make_train_step(model, tcfg)(state, batch)
    step_off = make_train_step(model, tcfg, offload=True)
    _, m_off = step_off(state, batch)
    np.testing.assert_allclose(float(m_plain["loss"]), float(m_off["loss"]),
                               rtol=1e-5)
    step_off(state, batch)
    # the UN-differentiated loss is planned once (the grad trace and the
    # second step both hit the cached plan), as is the update program
    assert step_off.stats.plan_misses == 1 and step_off.stats.traces == 1
    assert step_off.update_stats.plan_misses == 1
