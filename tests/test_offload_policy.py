"""The OffloadPolicy surface: mode registry, decision backends, the
policy-keyed plan cache, the legacy-kwarg shim, and explain().

Covers the acceptance contract of the policy redesign:
  * one mode vocabulary for planner and simulator (registry-validation:
    ``apply_policy`` accepts every registry name and nothing else, so
    the two cannot drift)
  * ``cost`` mode makes the §IV-B1 decision from modeled near/far time:
    it declines a bare grad-dot anchor (fusing would only add rhs
    re-streaming) while keeping GEMM_BIAS_GELU-style chains fused, and
    it matches greedy's segment count on every committed MUST_FUSE-like
    chain
  * the plan cache keys on the policy: same avals under a different
    policy (``with offload_policy(...):``) miss and recompile — never a
    stale hit
  * legacy kwargs (``mpu_offload(bulk_threshold=...)``,
    ``Engine(offload_bulk_threshold=...)``,
    ``TrainConfig.offload_bulk_threshold``) still work, warn, and build
    the equivalent policy
  * ``explain()`` reports every candidate (fused AND declined) with a
    rationale, and ``all_near``/``all_far`` behave as bounds
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OFFLOAD_MODES,
    PLANNER_MODES,
    SIMULATOR_MODES,
    DecisionReport,
    OffloadPolicy,
    apply_policy,
    current_policy,
    mpu_offload,
    offload_explain,
    offload_policy,
    offload_report,
    simulator_mode,
)
from repro.core.machine import MPU
from repro.core.workloads import PROGRAMS


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _gemm_bias_gelu(x, w, b, y):
    return jax.nn.gelu(x @ w + b) + y


def _bare_dlhs(g, w):
    # the standalone grad-time dx = g @ wT with nothing fusable around
    # it — the case the anchor tier's hard-coded rule declines and the
    # cost model must decline on its own
    return jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())))


# ---------------------------------------------------------------------------
# Mode registry: single source of truth, simulator cannot drift.
# ---------------------------------------------------------------------------

def test_mode_registry_covers_planner_and_simulator():
    assert set(PLANNER_MODES) <= set(OFFLOAD_MODES)
    assert set(SIMULATOR_MODES) <= set(OFFLOAD_MODES)
    # every registry name projects onto a simulator mode
    for mode in OFFLOAD_MODES:
        assert simulator_mode(mode) in SIMULATOR_MODES
    # shared names mean the same thing on both sides
    assert simulator_mode("all_near") == "all_near"
    assert simulator_mode("all_far") == "all_far"
    # planner backends execute as Algorithm-1 annotated locations
    assert simulator_mode("greedy") == "annotated"
    assert simulator_mode("cost") == "annotated"
    assert simulator_mode(OffloadPolicy(mode="cost")) == "annotated"
    with pytest.raises(ValueError):
        simulator_mode("bogus")


def test_apply_policy_accepts_registry_and_rejects_drift():
    prog = PROGRAMS["AXPY"]()
    n = len(prog.full_body())
    for mode in OFFLOAD_MODES:
        locs = apply_policy(prog, mode)
        assert len(locs) == n
    locs = apply_policy(prog, OffloadPolicy(mode="greedy"))
    assert locs == apply_policy(prog, "annotated")
    with pytest.raises(ValueError):
        apply_policy(prog, "not_a_mode")


def test_policy_validates_mode_and_knobs():
    with pytest.raises(ValueError):
        OffloadPolicy(mode="annotated")   # simulator-only: not a backend
    with pytest.raises(ValueError):
        OffloadPolicy(mode="nope")
    with pytest.raises(ValueError):
        OffloadPolicy(max_plans=0)
    with pytest.raises(ValueError):
        OffloadPolicy(min_segment=0)
    # frozen + hashable: usable as a plan-cache key component
    assert hash(OffloadPolicy()) == hash(OffloadPolicy())
    assert OffloadPolicy() != OffloadPolicy(mode="cost")


# ---------------------------------------------------------------------------
# The cost backend: §IV-B1 decisions from modeled near/far time.
# ---------------------------------------------------------------------------

def test_cost_declines_bare_grad_dot_keeps_gemm_fused():
    g, w = _rand((4096, 256)), _rand((256, 256), 1) * 0.05
    cost = OffloadPolicy(mode="cost")

    bare = offload_report(_bare_dlhs, g, w, policy=cost)
    assert len(bare.segments) == 0
    assert len(bare.decisions) == 1
    d = bare.decisions[0]
    assert d.tier == "anchor" and d.form == "dlhs" and not d.fused
    assert d.near_us >= d.far_us        # the modeled rationale
    assert d.near_bytes >= d.far_bytes

    x, b, y = _rand((4096, 256), 2), _rand((256,), 3), _rand((4096, 256), 4)
    fused = offload_report(_gemm_bias_gelu, x, w, b, y, policy=cost)
    assert len(fused.segments) == 1
    assert fused.segments[0].matmul is not None
    d = fused.decisions[0]
    assert d.fused and d.near_us < d.far_us


def test_cost_prices_batched_attention_anchor_near_below_far():
    """Cost mode on an [8,8,512,64] attention prefill: the flash-shaped
    segment's modeled near bytes (score matrix never in HBM) price
    strictly below the far chain's per-eqn round-trips, so the cost
    backend FUSES the batched anchor."""
    def attn(q, k, v):
        s = jnp.einsum("bhsd,bhtd->bhst", q, k) * 0.125
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", p, v)

    q = _rand((8, 8, 512, 64))
    k = _rand((8, 8, 512, 64), 1)
    v = _rand((8, 8, 512, 64), 2)
    plan = offload_report(attn, q, k, v, policy=OffloadPolicy(mode="cost"))
    assert len(plan.segments) == 1
    mm = plan.segments[0].matmul
    assert mm is not None and mm.flash is not None
    assert mm.batch == 64 and mm.batch_shape == (8, 8)
    d = [d for d in plan.decisions if d.fused][0]
    assert d.form == "flash" and d.batch == (8, 8)
    assert d.near_bytes < d.far_bytes and d.near_us < d.far_us
    assert plan.traffic_reduction >= 4.0


def test_cost_matches_greedy_segment_counts_on_fusing_chains():
    x = _rand((4096, 256))
    y = _rand((4096, 256), 1)
    w = _rand((256, 256), 2) * 0.05
    b = _rand((256,), 3)
    s = jnp.ones((256,))

    def axpy(x, y):
        return 2.5 * x + y

    def rmsnorm_chain(x, s):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-5) * s

    def softmax_chain(x):
        return jax.nn.softmax(x * 0.125, axis=-1)

    def mlp_grad(x, w, b, y):
        def loss(w, b):
            h = jax.nn.gelu(x @ w + b)
            return jnp.sum((h + y) ** 2)
        return jax.grad(loss, argnums=(0, 1))(w, b)

    chains = [
        (axpy, (x, y)),
        (_gemm_bias_gelu, (x, w, b, y)),
        (rmsnorm_chain, (x, s)),
        (softmax_chain, (x,)),
        (mlp_grad, (x, w, b, y)),
    ]
    for fn, args in chains:
        pg = offload_report(fn, *args)
        pc = offload_report(fn, *args, policy=OffloadPolicy(mode="cost"))
        assert len(pc.segments) == len(pg.segments), fn.__name__
        # the cost model only ever declines unprofitable fusions, so
        # its modeled traffic can never regress vs greedy
        assert pc.fused_hbm_bytes <= pg.fused_hbm_bytes, fn.__name__


def test_cost_numerics_match_plain_function():
    x = _rand((2048, 256))
    w = _rand((256, 256), 1) * 0.05
    b = _rand((256,), 2)
    y = _rand((2048, 256), 3)
    wrapped = mpu_offload(_gemm_bias_gelu, policy=OffloadPolicy(mode="cost"))
    np.testing.assert_allclose(
        np.asarray(wrapped(x, w, b, y)),
        np.asarray(_gemm_bias_gelu(x, w, b, y)), rtol=2e-5, atol=2e-5)


def test_all_far_plans_nothing_all_near_fuses_singletons():
    x, y = _rand((2048, 256)), _rand((2048, 256), 1)

    def single(x, y):
        return x + y                      # 1 ALU eqn: below min_segment

    assert len(offload_report(single, x, y).segments) == 0
    far = offload_report(single, x, y,
                         policy=OffloadPolicy(mode="all_far"))
    assert len(far.segments) == 0
    assert all(not d.fused and "all_far" in d.reason
               for d in far.decisions)
    near = offload_report(single, x, y,
                          policy=OffloadPolicy(mode="all_near"))
    assert len(near.segments) == 1
    wrapped = mpu_offload(single, policy=OffloadPolicy(mode="all_near"))
    np.testing.assert_allclose(np.asarray(wrapped(x, y)),
                               np.asarray(x + y), rtol=1e-6)


def test_machine_bandwidths_steer_the_decision():
    # on MPU the near path is ~8x the TSV far path, so modeled near
    # time shrinks relative to far for the same byte counts
    pol_tpu = OffloadPolicy(mode="cost")
    pol_mpu = OffloadPolicy(mode="cost", machine=MPU)
    assert pol_mpu.near_gbps > pol_mpu.far_gbps
    n_tpu, f_tpu = pol_tpu.modeled_us(1 << 20, 1 << 20)
    n_mpu, f_mpu = pol_mpu.modeled_us(1 << 20, 1 << 20)
    assert n_tpu == f_tpu                 # same HBM both ways on TPU
    assert n_mpu < f_mpu                  # near-bank bandwidth advantage


def test_vmem_budget_threads_into_plan_and_kernels():
    x = _rand((4096, 512))
    w = _rand((512, 512), 1) * 0.05
    b = _rand((512,), 2)

    def gemm(x, w, b):
        h = x @ w + b
        return jax.nn.gelu(h)

    big = offload_report(gemm, x, w, b)
    small = offload_report(
        gemm, x, w, b, policy=OffloadPolicy(vmem_budget=256 * 1024))
    assert len(big.segments) == len(small.segments) == 1
    # a tighter accumulator budget shrinks row blocks, so the [K,N]
    # weight re-streams more often — modeled traffic must go UP
    assert small.fused_hbm_bytes > big.fused_hbm_bytes
    assert small.segments[0].vmem_bytes == 256 * 1024
    # and the kernel path (interpret impl) still runs correctly
    wrapped = mpu_offload(gemm, policy=OffloadPolicy(
        vmem_budget=256 * 1024, impl="interpret"))
    np.testing.assert_allclose(np.asarray(wrapped(x, w, b)),
                               np.asarray(gemm(x, w, b)),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Policy-keyed plan cache + the scoped override.
# ---------------------------------------------------------------------------

def test_same_avals_different_policy_is_a_miss_not_a_stale_hit():
    x, y = _rand((2048, 256)), _rand((2048, 256), 1)

    def chain(x, y):
        h = jnp.tanh(x) * 2.0 + y
        return h * jax.nn.sigmoid(h)

    wrapped = mpu_offload(chain)
    ref = chain(x, y)
    np.testing.assert_allclose(np.asarray(wrapped(x, y)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert wrapped.stats.plan_misses == 1 and wrapped.cache_size() == 1

    with offload_policy(OffloadPolicy(mode="all_far")):
        # same avals, different policy: must compile a fresh (far) plan
        np.testing.assert_allclose(np.asarray(wrapped(x, y)),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)
        assert wrapped.explain(x, y).n_fused == 0
    assert wrapped.stats.plan_misses == 2 and wrapped.cache_size() == 2

    # back outside the scope: the original plan hits, nothing recompiles
    wrapped(x, y)
    assert wrapped.stats.plan_hits == 1
    assert wrapped.stats.plan_misses == 2
    assert wrapped.explain(x, y).n_fused == 1


def test_scoped_override_nests_and_restores():
    base = current_policy()
    with offload_policy(OffloadPolicy(mode="cost")) as p1:
        assert current_policy() is p1
        with offload_policy(OffloadPolicy(mode="all_far")) as p2:
            assert current_policy() is p2
        assert current_policy() is p1
    assert current_policy() == base


def test_scoped_override_wins_over_pinned_policy():
    x, y = _rand((2048, 256)), _rand((2048, 256), 1)

    def chain(x, y):
        return jnp.tanh(x) * 2.0 + y

    wrapped = mpu_offload(chain, policy=OffloadPolicy(mode="greedy"))
    assert wrapped.explain(x, y).n_fused == 1
    with offload_policy(OffloadPolicy(mode="all_far")):
        assert wrapped.explain(x, y).n_fused == 0


# ---------------------------------------------------------------------------
# Legacy-kwarg shims.
# ---------------------------------------------------------------------------

def test_mpu_offload_legacy_kwargs_warn_and_build_equivalent_policy():
    x, y = _rand((2048, 256)), _rand((2048, 256), 1)

    def chain(x, y):
        h = jnp.tanh(x) * 2.0 + y
        return h * jax.nn.sigmoid(h)

    with pytest.warns(DeprecationWarning, match="policy=OffloadPolicy"):
        wrapped = mpu_offload(chain, bulk_threshold=4096, max_plans=7)
    assert wrapped.policy == OffloadPolicy(bulk_threshold=4096, max_plans=7)
    np.testing.assert_allclose(np.asarray(wrapped(x, y)),
                               np.asarray(chain(x, y)),
                               rtol=2e-5, atol=2e-5)
    # the shimmed policy and the explicit policy produce the same plan
    explicit = mpu_offload(
        chain, policy=OffloadPolicy(bulk_threshold=4096, max_plans=7))
    assert len(wrapped.plan_for(x, y).segments) == \
        len(explicit.plan_for(x, y).segments)


def test_trainconfig_legacy_fields_warn_and_fold():
    from repro.configs.base import TrainConfig

    with pytest.warns(DeprecationWarning, match="offload_policy"):
        tcfg = TrainConfig(offload=True, offload_bulk_threshold=2048,
                           offload_max_plans=9)
    pol = tcfg.resolved_offload_policy()
    assert pol == OffloadPolicy(bulk_threshold=2048, max_plans=9)
    # the new surface: a policy object, no warning, min_segment exposed
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        tcfg2 = TrainConfig(
            offload=True,
            offload_policy=OffloadPolicy(mode="cost", min_segment=3))
    assert tcfg2.resolved_offload_policy().min_segment == 3


# ---------------------------------------------------------------------------
# explain(): the plan-inspection API.
# ---------------------------------------------------------------------------

def test_explain_reports_fused_and_declined_with_rationale():
    x = _rand((2048, 256))
    w = _rand((256, 256), 1) * 0.05
    b = _rand((256,), 2)
    y = _rand((2048, 256), 3)

    def gemm_then_bare_dot(x, w, b, y):
        h = jax.nn.gelu(x @ w + b) + y
        # a second dot with nothing fusable after it: a bare anchor
        return jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())))

    wrapped = mpu_offload(gemm_then_bare_dot)
    report = wrapped.explain(x, w, b, y)
    assert isinstance(report, DecisionReport)
    assert report.n_fused == 1 and report.n_declined == 1
    fused = [d for d in report.all_decisions() if d.fused]
    declined = [d for d in report.all_decisions() if not d.fused]
    assert fused[0].tier == "anchor" and fused[0].form == "fwd"
    assert declined[0].tier == "anchor" and declined[0].form == "dlhs"
    assert declined[0].reason            # every verdict carries a why
    text = str(report)
    assert "FUSE" in text and "decline" in text
    assert "near_us" in text and "far_us" in text
    assert "mode=greedy" in text

    # the functional entry point agrees without wrapping
    report2 = offload_explain(gemm_then_bare_dot, x, w, b, y)
    assert report2.n_fused == 1 and report2.n_declined == 1


def test_explain_modeled_times_consistent_with_bytes():
    x, y = _rand((2048, 256)), _rand((2048, 256), 1)

    def chain(x, y):
        h = jnp.tanh(x) * 2.0 + y
        return h * jax.nn.sigmoid(h)

    pol = OffloadPolicy(mode="cost")
    report = offload_explain(chain, x, y, policy=pol)
    d = report.all_decisions()[0]
    n_us, f_us = pol.modeled_us(d.near_bytes, d.far_bytes)
    assert d.near_us == pytest.approx(n_us)
    assert d.far_us == pytest.approx(f_us)
    assert d.fused and d.near_bytes < d.far_bytes


def test_engine_legacy_kwargs_warn_and_policy_threads(rng):
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.serve.engine import Engine

    cfg = dataclasses.replace(reduced(get_config("qwen3-1.7b")),
                              dtype="float32")
    model_params = None
    from repro.models import build_model
    model_params = build_model(cfg).init(rng)

    with pytest.warns(DeprecationWarning, match="offload_policy"):
        eng = Engine(cfg, model_params, slots=2, max_len=32,
                     offload=True, offload_bulk_threshold=2048)
    assert eng.offload_policy == OffloadPolicy(bulk_threshold=2048)

    # a policy alone implies offload; explain_decode renders a report
    eng2 = Engine(cfg, model_params, slots=2, max_len=32,
                  offload_policy=OffloadPolicy(mode="cost"))
    assert eng2.offload
    report = eng2.explain_decode()
    assert isinstance(report, DecisionReport)
    assert report.policy.mode == "cost"


@pytest.mark.parametrize("platform,kind,ok", [
    ("cpu", "cpu", True), ("tpu", "TPU v5 lite", True),
    ("tpu", "TPU v4", False), ("gpu", "NVIDIA H100", False)])
def test_cost_mode_refuses_undescribed_device(platform, kind, ok):
    """mode="cost" prices with the machine model's constants: on an
    accelerator they do not describe it refuses instead of pricing the
    wrong chip (a CPU host plans for the modelled machine)."""
    from types import SimpleNamespace

    device = SimpleNamespace(platform=platform, device_kind=kind)
    policy = OffloadPolicy(mode="cost")
    if ok:
        policy.check_cost_target(device)
    else:
        with pytest.raises(ValueError, match=kind):
            policy.check_cost_target(device)
