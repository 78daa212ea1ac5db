"""Serving robustness under injected faults.

The contract (MUST_SURVIVE, held as a whole by
``test_fault_storm_survives``): faults may cancel/abort individual
requests, but every request that completes with status ``ok`` emits
tokens identical to a fault-free run, cancelled requests release their
pages immediately, the engine never wedges or leaks pool pages, and a
corrupted persistent plan cache reads as a counted ``disk_corrupt``,
never an exception.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.artifacts import set_disk_injector
from repro.core.policy import OffloadPolicy
from repro.kernels.guard import kernel_guard
from repro.models import build_model
from repro.serve import (
    Engine,
    FaultConfig,
    FaultInjector,
    PagePool,
    Request,
)

from conftest import tiny


@pytest.fixture(autouse=True)
def clean_guard():
    g = kernel_guard()
    g.reset()
    yield g
    g.injector = None
    g.reset()


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("qwen3-1.7b", num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, size=5 + i).astype(np.int32)
               for i in range(4)]
    return cfg, params, prompts


def _reqs(prompts, **over):
    return [Request(p, max_new_tokens=6, rid=i, **over)
            for i, p in enumerate(prompts)]


@pytest.fixture(scope="module")
def baseline(setup):
    cfg, params, prompts = setup
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8)
    return eng.generate(_reqs(prompts))


# ------------------------------------------------------------- deadlines
def test_midflight_deadline_cancel_reclaims_pages(setup, baseline):
    """Slow steps push one request past its deadline mid-decode: it is
    cancelled, its pages return to the pool, survivors stay exact."""
    cfg, params, prompts = setup
    inj = FaultInjector(FaultConfig(slow_step_rate=1.0, slow_step_s=0.05))
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8,
                 fault_injector=inj)
    reqs = _reqs(prompts)
    reqs[1] = dataclasses.replace(reqs[1], deadline_s=0.12)
    done = eng.generate(reqs)
    assert done[1].status == "cancelled" and done[1].reason == "deadline"
    assert len(done[1].tokens) < 6
    assert eng.serve_counters["deadline_cancels"] == 1
    assert eng.pool.used_pages == 0
    for i in (0, 2, 3):
        assert done[i].status == "ok"
        assert done[i].tokens == baseline[i].tokens, i


def test_expired_deadline_rejected_at_submit(setup):
    cfg, params, prompts = setup
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8)
    req = Request(prompts[0], max_new_tokens=6, rid=0, deadline_s=1e-9)
    assert eng.submit(req) == "rejected_deadline"
    (c,) = eng.pop_finished()
    assert c.status == "rejected" and c.reason == "deadline"
    assert c.tokens == []
    assert eng.serve_counters["reject_deadline"] == 1


# ------------------------------------------------------------- NaN logits
def test_nan_logits_abort_only_poisoned_request(setup, baseline):
    cfg, params, prompts = setup
    inj = FaultInjector(FaultConfig(nan_logit_rate=1.0, nan_logit_limit=1,
                                    seed=3))
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8,
                 fault_injector=inj)
    done = eng.generate(_reqs(prompts))
    aborted = [r for r, c in done.items() if c.status == "aborted"]
    assert len(aborted) == 1
    assert done[aborted[0]].reason == "nan_logits"
    # already-emitted tokens (pre-poison) are kept and match baseline
    kept = done[aborted[0]].tokens
    assert kept == baseline[aborted[0]].tokens[:len(kept)]
    for r, c in done.items():
        if r not in aborted:
            assert c.status == "ok"
            assert c.tokens == baseline[r].tokens, r
    assert eng.serve_counters["nan_aborts"] == 1
    assert eng.pool.used_pages == 0


# ------------------------------------------------------------ page faults
def test_transient_page_faults_pause_and_resume_exactly(setup, baseline):
    """Injected allocation failures pause the slot (pages kept, state
    frozen) and resume later — final tokens are unaffected."""
    cfg, params, prompts = setup
    inj = FaultInjector(FaultConfig(page_fail_rate=0.5, seed=4))
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8,
                 fault_injector=inj)
    done = eng.generate(_reqs(prompts))
    assert inj.counters["page_faults_injected"] > 0
    assert eng.serve_counters["page_faults"] > 0
    for i in range(4):
        assert done[i].status == "ok"
        assert done[i].tokens == baseline[i].tokens, i
    assert eng.pool.used_pages == 0


# ----------------------------------------------------------- backpressure
def test_bounded_queue_rejects_overflow(setup):
    cfg, params, prompts = setup
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8,
                 max_queue=2)
    outcomes = [eng.submit(Request(prompts[i % 4], max_new_tokens=4, rid=i))
                for i in range(4)]
    assert outcomes == ["queued", "queued",
                        "rejected_queue_full", "rejected_queue_full"]
    assert eng.serve_counters["reject_queue_full"] == 2
    rejected = {c.rid: c for c in eng.pop_finished()}
    assert set(rejected) == {2, 3}
    assert all(c.status == "rejected" and c.reason == "queue_full"
               for c in rejected.values())


# ------------------------------------------------- preemption budget/aging
def test_preemption_budget_and_aging_still_exact(setup, baseline):
    """Contention forces preemption; the retry budget + aged-requeue
    priority guarantee completion with exact tokens."""
    cfg, params, prompts = setup
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8,
                 num_pages=1 + 5, max_preempts=3)
    done = eng.generate(_reqs(prompts))
    assert eng.serve_counters["preemptions"] > 0
    assert eng.serve_counters["preemption_retries"] > 0
    for i in range(4):
        assert done[i].status == "ok"
        assert done[i].tokens == baseline[i].tokens, i
    assert eng.pool.used_pages == 0


# ------------------------------------------------------ pool double-ops
def test_pool_double_free_raises():
    pool = PagePool(num_pages=8, page_size=4, table_width=4, slots=2)
    assert pool.alloc(0, 2) and pool.alloc(1, 1)
    # simulate corrupted ownership: slot 1's table points at slot 0's page
    pool.tables[1, 0] = pool.tables[0, 0]
    with pytest.raises(RuntimeError, match="double-free"):
        pool.free_slot(1)


def test_pool_double_alloc_raises():
    pool = PagePool(num_pages=8, page_size=4, table_width=4, slots=2)
    assert pool.alloc(0, 2)
    # simulate free-list corruption: a live page re-enters the free list
    live = int(pool.tables[0, 0])
    pool._free.append(live)
    with pytest.raises(RuntimeError, match="double-alloc"):
        pool.alloc(1, 1)   # LIFO: pops the corrupt entry first


# ------------------------------------------------------------ fault storm
def test_fault_storm_survives(setup, clean_guard, tmp_path, monkeypatch):
    """Every fault class at once against the offloaded engine, warm-
    starting from a plan cache whose entries were bit-flipped on disk:
    every kernel launch fails (guarded fallback to ref, quarantine,
    all_far re-plan), one request's logits turn NaN, page allocations
    fail transiently, slow steps push a deadlined request past its
    budget, and every artifact read/write is truncated."""
    cfg, params, _ = setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 250, size=int(rng.integers(5, 17))).astype(
        np.int32) for _ in range(6)]

    def reqs(deadline_s):
        return [Request(p, max_new_tokens=8, rid=i,
                        deadline_s=deadline_s if i == 0 else 0.0)
                for i, p in enumerate(prompts)]

    kw = dict(slots=4, max_len=64, page_size=8, offload=True,
              offload_policy=OffloadPolicy(impl="interpret"))
    monkeypatch.setenv("MPU_PLAN_CACHE", str(tmp_path))
    base = Engine(cfg, params, **kw).generate(reqs(0.0))
    poisoned = list(tmp_path.glob("*.bin"))
    assert poisoned
    for b in poisoned:
        raw = bytearray(b.read_bytes())
        raw[len(raw) // 2] ^= 0x01
        b.write_bytes(bytes(raw))

    # quarantine after the first failure: a single-segment plan
    # dispatches once per trace, so the default threshold never trips
    threshold = clean_guard.threshold
    clean_guard.threshold = 1
    inj = FaultInjector(FaultConfig(
        kernel_fail_rate=1.0, nan_logit_rate=1.0, nan_logit_limit=1,
        page_fail_rate=0.3, slow_step_rate=1.0, slow_step_s=0.02,
        disk_fail_rate=1.0, disk_truncate_share=1.0, seed=7))
    try:
        eng = Engine(cfg, params, fault_injector=inj, **kw)
        done = eng.generate(reqs(0.08))
    finally:
        set_disk_injector(None)
        clean_guard.threshold = threshold

    sv, st = eng.serve_counters, eng.offload_stats
    ok = [r for r, c in done.items() if c.status == "ok"]
    assert ok
    for r in ok:
        assert done[r].tokens == base[r].tokens, r
    assert done[0].status == "cancelled"
    assert eng.pool.used_pages == 0
    assert st["quarantines"] >= 1
    assert sv["nan_aborts"] >= 1
    assert sv["page_faults"] >= 1
    assert st["plan_misses"] <= 1 + st["plan_invalidations"]
    assert st["disk_corrupt"] >= 1
    assert inj.counters["disk_faults_injected"] >= 1
