"""Guarded kernel dispatch + fault injection: fallback chain,
quarantine, offload plan invalidation / all_far degradation, recovery.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.offload import mpu_offload
from repro.core.policy import OffloadPolicy
from repro.kernels import ops
from repro.kernels.guard import (
    FALLBACK_CHAIN,
    KernelGuard,
    kernel_guard,
    resolve_impl,
)
from repro.serve.faults import FaultConfig, FaultInjected, FaultInjector, inject


@pytest.fixture(autouse=True)
def clean_guard():
    """Every test starts and ends with a healthy, injector-free guard."""
    g = kernel_guard()
    g.reset()
    thr = g.threshold
    yield g
    g.injector = None
    g.threshold = thr
    g.reset()


# -- injector determinism ---------------------------------------------------

def test_injector_streams_are_deterministic():
    cfg = FaultConfig(kernel_fail_rate=0.5, nan_logit_rate=0.5,
                      page_fail_rate=0.5, seed=42)
    a, b = FaultInjector(cfg), FaultInjector(cfg)
    act = np.array([True, True, False, True])
    for _ in range(50):
        fa = fb = False
        try:
            a.kernel_launch("k", "interpret")
        except FaultInjected:
            fa = True
        try:
            b.kernel_launch("k", "interpret")
        except FaultInjected:
            fb = True
        assert fa == fb
        assert (a.poison_slots(act) == b.poison_slots(act)).all()
        assert a.page_alloc() == b.page_alloc()
    assert a.counters == b.counters


def test_injector_classes_are_independent():
    """Enabling one fault class must not perturb another's schedule."""
    base = FaultConfig(page_fail_rate=0.5, seed=7)
    both = FaultConfig(page_fail_rate=0.5, kernel_fail_rate=0.9, seed=7)
    a, b = FaultInjector(base), FaultInjector(both)
    for _ in range(30):
        try:
            b.kernel_launch("k", "interpret")
        except FaultInjected:
            pass
        assert a.page_alloc() == b.page_alloc()


def test_injector_never_faults_ref():
    inj = FaultInjector(FaultConfig(kernel_fail_rate=1.0))
    inj.kernel_launch("anything", "ref")   # must not raise
    with pytest.raises(FaultInjected):
        inj.kernel_launch("anything", "interpret")


def test_nan_limit_and_one_slot_per_step():
    inj = FaultInjector(FaultConfig(nan_logit_rate=1.0, nan_logit_limit=2))
    act = np.ones((4,), bool)
    total = 0
    for _ in range(10):
        m = inj.poison_slots(act)
        assert m.sum() <= 1
        total += int(m.sum())
    assert total == 2


# -- guard mechanics --------------------------------------------------------

def test_fallback_chain_orders():
    # a compiled kernel never demotes to the Pallas interpreter
    assert FALLBACK_CHAIN["pallas"] == ("pallas", "ref")
    assert FALLBACK_CHAIN["interpret"] == ("interpret", "ref")
    assert FALLBACK_CHAIN["ref"] == ("ref",)


def test_guard_run_demotes_on_failure():
    g = KernelGuard()
    calls = []

    def attempt(im):
        calls.append(im)
        if im != "ref":
            raise RuntimeError("boom")
        return "ok"

    assert g.run("k", "interpret", attempt) == "ok"
    assert calls == ["interpret", "ref"]
    assert g.kernel_failures == 1 and g.kernel_fallbacks == 1


def test_quarantine_after_consecutive_failures_and_reset():
    g = KernelGuard(threshold=3)
    for i in range(3):
        assert not g.is_quarantined("k", "interpret")
        tripped = g.record_failure("k", "interpret")
    assert tripped and g.is_quarantined("k", "interpret")
    assert g.epoch == 1 and g.quarantines == 1
    assert g.chain("k", "interpret") == ("ref",)
    # success elsewhere resets the consecutive count
    g.record_failure("j", "interpret")
    g.record_success("j", "interpret")
    g.record_failure("j", "interpret")
    assert not g.is_quarantined("j", "interpret")
    g.reset()
    assert not g.is_quarantined("k", "interpret")
    assert g.epoch == 2      # reset bumps the epoch too (re-plan near)


def test_ref_never_quarantines():
    g = KernelGuard(threshold=1)
    assert g.record_failure("k", "ref") is False
    assert not g.is_quarantined("k", "ref")
    assert g.chain("k", "ref") == ("ref",)


def test_guarded_ops_fall_back_to_ref(clean_guard):
    x = jnp.ones((8, 128), jnp.float32) * 0.5
    s = jnp.ones((128,), jnp.float32)
    y_ref = ops.rmsnorm(x, s, impl="ref")
    inj = FaultInjector(FaultConfig(kernel_fail_rate=1.0))
    with inject(inj):
        y = ops.rmsnorm(x, s, impl="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref))
    assert clean_guard.kernel_fallbacks == 1
    assert inj.counters["kernel_faults"] == 1


# -- offload degradation ----------------------------------------------------

def _seg_fn(x, w):
    h = jnp.tanh(x) * 2.0 + 1.0
    return jax.nn.relu(h @ w) + 0.5


def test_quarantine_invalidates_plan_and_replans_all_far(clean_guard):
    clean_guard.threshold = 1
    x = jnp.full((256, 512), 0.25, jnp.float32)
    w = jnp.full((512, 512), 0.01, jnp.float32)
    pol = OffloadPolicy(impl="interpret", bulk_threshold=128)

    baseline = mpu_offload(_seg_fn, policy=pol)
    assert baseline.plan_for(x, w).total_segments > 0
    y0 = np.asarray(baseline(x, w))

    wrapped = mpu_offload(_seg_fn, policy=pol)
    inj = FaultInjector(FaultConfig(kernel_fail_rate=1.0))
    with inject(inj):
        # first trace: every segment launch faults -> ref fallback, and
        # (threshold=1) the kernel quarantines mid-trace
        y1 = np.asarray(wrapped(x, w))
        assert clean_guard.quarantines >= 1
        assert clean_guard.degraded_for("interpret")
        # next call sees the epoch change: stale plan dropped, policy
        # degraded to all_far, fresh plan has zero segments
        y2 = np.asarray(wrapped(x, w))
        assert wrapped.stats.plan_invalidations >= 1
        assert wrapped.stats.plan_misses == 2
        assert wrapped.plan_for(x, w).total_segments == 0
        # steady state: the all_far plan is a cache hit
        y3 = np.asarray(wrapped(x, w))
        assert wrapped.stats.plan_misses == 2

    np.testing.assert_allclose(y0, y1)
    np.testing.assert_allclose(y0, y2)
    np.testing.assert_allclose(y0, y3)


def test_guard_reset_recovers_near_planning(clean_guard):
    clean_guard.threshold = 1
    x = jnp.full((256, 512), 0.25, jnp.float32)
    w = jnp.full((512, 512), 0.01, jnp.float32)
    pol = OffloadPolicy(impl="interpret", bulk_threshold=128)
    wrapped = mpu_offload(_seg_fn, policy=pol)
    inj = FaultInjector(FaultConfig(kernel_fail_rate=1.0))
    with inject(inj):
        y_deg = np.asarray(wrapped(x, w))
        wrapped(x, w)
        assert wrapped.plan_for(x, w).total_segments == 0
    clean_guard.reset()   # quarantine lifted, epoch bumped
    y_rec = np.asarray(wrapped(x, w))
    assert wrapped.plan_for(x, w).total_segments > 0   # near again
    np.testing.assert_allclose(y_deg, y_rec)


def test_unquarantined_wrapper_unaffected(clean_guard):
    """A wrapper whose policy impl is not quarantined keeps its plans
    when an unrelated impl is quarantined (no cross-impl degradation)."""
    x = jnp.full((256, 512), 0.25, jnp.float32)
    w = jnp.full((512, 512), 0.01, jnp.float32)
    pol = OffloadPolicy(impl="ref", bulk_threshold=128)
    wrapped = mpu_offload(_seg_fn, policy=pol)
    wrapped(x, w)
    assert wrapped.stats.plan_misses == 1
    # unrelated quarantine at interpret
    for _ in range(kernel_guard().threshold):
        clean_guard.record_failure("fused_segment_grid", "interpret")
    assert clean_guard.degraded_for("interpret")
    assert not clean_guard.degraded_for("ref")
    wrapped(x, w)
    # ref-impl plans DO get invalidated by the epoch bump (conservative:
    # any segment-bearing plan is dropped), but the policy stays
    # undegraded, so it re-plans near at the same key
    assert wrapped.plan_for(x, w).total_segments > 0
