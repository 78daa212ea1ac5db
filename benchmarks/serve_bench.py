"""Serving benchmark: paged continuous batching vs the fixed-slot engine.

Open-loop synthetic workload (deterministic arrival schedule, prompts
drawn from a fixed rng) through both engines **at equal KV-cache
memory**:

* ``FixedSlotEngine`` pins ``slots_fixed * max_len`` KV positions per
  layer whether or not tokens exist;
* the paged ``Engine`` gets the same position budget as a page pool
  (``num_pages * page_size == slots_fixed * max_len``) but twice the
  concurrency — pages track live tokens, so more requests fit the same
  memory.  That is the continuous-batching claim, and the bench holds
  memory constant so the speedup is attributable to paging alone.

Reported per engine: tokens/s (wall clock over the full workload) and
p50/p99 per-token latency (the wall time of the decode step that
emitted each token).  Deterministic companions:

* **KV traffic model**: per decode step the dense engine streams
  ``slots * capacity`` cache positions per attention layer (its kernel
  grids over the padded cache; masked chunks still stream).  The paged
  engine streams only allocated pages — table tails point at the
  reserved scratch page, which stays in the activated row buffer (the
  near-bank re-reference the MPU row-locality argument is about) and
  costs no new DRAM traffic.  The positions-streamed ratio is exact,
  machine-independent, and ratcheted.
* **Exactness**: both engines must emit identical greedy tokens.
* **Zero-retrace**: the paged engine must finish the whole churning
  workload with one decode trace/plan and frozen admit buckets.

``MUST_SERVE`` carries the committed floors; violating any floor exits
non-zero (CI fails without needing the artifact), and the committed
``BENCH_serve.json`` ratchets the deterministic traffic ratio against
the last recorded run.  ``--smoke`` shrinks the workload for per-push
CI freshness; ``--csv`` emits machine-readable rows; under GitHub
Actions the one-liner (and any regression) lands in
``$GITHUB_STEP_SUMMARY``.

``--chaos`` additionally drives the paged engine through a seeded
fault storm — every interpret kernel launch fails (guarded dispatch
falls back to ref, quarantines, and the offload planner degrades to
all_far), one request's logits are NaN-poisoned, transient page-alloc
failures pause/resume slots, slow steps push a deadlined request past
its budget, and (since schema v3) the **disk_io fault class** fires on
every artifact read/write while the engine warm-starts from a
persistent plan cache whose entries were bit-flipped on disk.
``MUST_SURVIVE`` is the committed contract for that run: requests that
finish ``ok`` emit tokens identical to the fault-free run, the
deadlined request is cancelled (not wedged), no pool pages leak,
re-plans stay bounded by quarantine events, and every bad plan-cache
read is a COUNTED ``disk_corrupt`` (quarantined entry + fresh plan) —
never an exception and never a token divergence.  The fault-free
comparison (and its MUST_SERVE floors) still runs first, so
``--chaos`` is a strict superset of the plain bench.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import Engine, FixedSlotEngine, Request  # noqa: E402

ARTIFACT = ROOT / "BENCH_serve.json"

# v3: chaos covers the disk_io fault class + corrupted warm plan-cache
# entries (MUST_SURVIVE gains min_disk_corrupt / min_disk_faults)
SCHEMA_VERSION = 3

# Committed serving contract.  Deterministic floors are exact
# (positions-streamed model, token equality, trace counters); the
# wall-clock speedup floor is set well under the measured value so CI
# machine jitter cannot trip it, but a paged engine SLOWER than the
# fixed-slot baseline at equal memory still fails.
MUST_SERVE = {
    "speedup_floor": 1.0,          # paged tokens/s / fixed tokens/s
    "traffic_floor": 2.0,          # modeled KV positions streamed ratio
    "max_step_traces": 1,          # decode signature is stable
    "max_admit_traces": 8,         # <= one per pow2 prompt bucket
    "exact_tokens": True,          # paged greedy == fixed-slot greedy
}

# Committed chaos contract (``--chaos``): what the engine guarantees
# while faults are being injected.  All checks are deterministic (the
# fault schedule is seeded).
MUST_SURVIVE = {
    "ok_tokens_exact": True,   # status=="ok" => tokens == fault-free run
    "deadline_cancelled": True,  # the deadlined request ends "cancelled"
    "pages_reclaimed": True,   # pool.used_pages == 0 after the run
    "min_quarantines": 1,      # guarded dispatch tripped and degraded
    "min_nan_aborts": 1,       # poisoned logits abort only their request
    "min_page_faults": 1,      # transient alloc failures were exercised
    "bounded_replans": True,   # plan_misses <= 1 + plan_invalidations
    "min_disk_corrupt": 1,     # bad plan-cache entries detected + counted
    "min_disk_faults": 1,      # the disk_io fault class actually fired
}


def _workload(n_requests: int, seed: int = 0):
    """Deterministic open-loop workload: arrival steps + mixed-length
    prompts.  Arrivals are independent of completions (open loop) but
    scheduled in engine steps so the run is reproducible."""
    rng = np.random.default_rng(seed)
    reqs, arrivals = [], []
    t = 0
    for i in range(n_requests):
        n = int(rng.integers(6, 49))
        prompt = rng.integers(1, 250, size=n).astype(np.int32)
        reqs.append(Request(prompt, max_new_tokens=16, rid=i))
        t += int(rng.integers(0, 3))     # 0-2 steps between arrivals
        arrivals.append(t)
    return reqs, arrivals


def _run_engine(eng, reqs, arrivals, *, traffic_fn):
    """Drive one engine through the open-loop schedule.  Returns
    (tokens, per-token step latencies, modeled positions streamed)."""
    done = {r.rid: [] for r in reqs}
    latencies = []
    positions_streamed = 0
    queue = list(zip(arrivals, reqs))
    step_i = 0
    requeue = getattr(eng, "_requeue", None)
    t0 = time.perf_counter()
    while queue or (requeue and len(requeue)) or _busy(eng):
        while requeue and len(requeue) and eng.admit(requeue[0]):
            requeue.pop(0)
        while queue and queue[0][0] <= step_i and eng.admit(queue[0][1]):
            queue.pop(0)
        positions_streamed += traffic_fn(eng)
        s0 = time.perf_counter()
        made = eng.step()
        dt = time.perf_counter() - s0
        for rid, tok in made:
            done[rid].append(tok)
            latencies.append(dt)
        step_i += 1
    wall = time.perf_counter() - t0
    return done, latencies, positions_streamed, wall


def _busy(eng) -> bool:
    if isinstance(eng, Engine):
        return bool(eng._host_active.any())
    return bool(eng.active.any())


def _fixed_traffic(eng: FixedSlotEngine) -> int:
    """Dense decode streams the padded cache for every slot each step
    (its kernel masks dead positions but still grids over them)."""
    if not eng.active.any():
        return 0
    return eng.slots * eng.max_len


def _paged_traffic(eng: Engine) -> int:
    """Paged decode streams allocated pages only; unallocated table
    entries re-reference the scratch page (stays in the activated row
    buffer — no new DRAM traffic)."""
    if not eng._decode_active.any():
        return 0
    return sum(eng.pool.allocated(s) * eng.page_size
               for s in range(eng.slots) if eng._decode_active[s])


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def run(write_artifact: bool = True, n_requests: int = 24,
        seed: int = 0) -> dict:
    cfg = dataclasses.replace(reduced(get_config("qwen3-1.7b")),
                              num_layers=2, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    slots_fixed, max_len, page_size = 4, 128, 16
    kv_budget = slots_fixed * max_len           # positions per layer
    num_pages = 1 + kv_budget // page_size
    slots_paged = 2 * slots_fixed               # same memory, 2x batch

    reqs, arrivals = _workload(n_requests, seed)
    total_new = sum(r.max_new_tokens for r in reqs)

    fixed = FixedSlotEngine(cfg, params, slots=slots_fixed,
                            max_len=max_len)
    f_done, f_lat, f_pos, f_wall = _run_engine(
        fixed, [dataclasses.replace(r) for r in reqs], arrivals,
        traffic_fn=_fixed_traffic)

    paged = Engine(cfg, params, slots=slots_paged, max_len=max_len,
                   page_size=page_size, num_pages=num_pages,
                   offload=True)
    p_done, p_lat, p_pos, p_wall = _run_engine(
        paged, [dataclasses.replace(r) for r in reqs], arrivals,
        traffic_fn=_paged_traffic)

    exact = all(p_done[r.rid] == f_done[r.rid] for r in reqs)
    sv = paged.serve_stats
    st = paged.offload_stats

    result = {
        "schema_version": SCHEMA_VERSION,
        "config": {
            "arch": "qwen3-1.7b/reduced", "num_layers": 2,
            "slots_fixed": slots_fixed, "slots_paged": slots_paged,
            "max_len": max_len, "page_size": page_size,
            "num_pages": num_pages, "kv_budget_positions": kv_budget,
            "n_requests": n_requests, "total_new_tokens": total_new,
        },
        "fixed": {
            "tokens_per_s": total_new / f_wall,
            "p50_token_ms": _pct(f_lat, 50) * 1e3,
            "p99_token_ms": _pct(f_lat, 99) * 1e3,
            "wall_s": f_wall,
            "positions_streamed": f_pos,
        },
        "paged": {
            "tokens_per_s": total_new / p_wall,
            "p50_token_ms": _pct(p_lat, 50) * 1e3,
            "p99_token_ms": _pct(p_lat, 99) * 1e3,
            "wall_s": p_wall,
            "positions_streamed": p_pos,
            "preemptions": sv["preemptions"],
            "admit_traces": sv["admit_traces"],
            "step_traces": sv["step_traces"],
            "offload_traces": st["traces"],
            "offload_plan_misses": st["plan_misses"],
        },
        "speedup": f_wall / p_wall,
        "traffic_reduction": f_pos / max(p_pos, 1),
        "exact_tokens": exact,
    }
    if write_artifact:
        ARTIFACT.write_text(json.dumps(result, indent=2))
    return result


def run_chaos(n_requests: int = 8, seed: int = 7) -> tuple[dict, list[str]]:
    """Seeded fault storm against a fault-free reference run of the same
    engine config.  Returns (chaos result dict, MUST_SURVIVE failures).

    The disk leg: the fault-free reference engine persists its decode
    plan into a throwaway ``MPU_PLAN_CACHE`` directory; every persisted
    entry is then bit-flipped on disk, and the chaos engine warm-starts
    against that poisoned cache with the ``disk_io`` fault class
    truncating every artifact read/write.  The engine must detect the
    rot (counted ``disk_corrupt``, entry quarantined), re-plan fresh,
    and still emit token-exact output."""
    import os
    import shutil
    import tempfile

    from repro.core.artifacts import set_disk_injector  # noqa: E402
    from repro.core.policy import OffloadPolicy  # noqa: E402
    from repro.kernels.guard import kernel_guard, set_injector  # noqa: E402
    from repro.serve import FaultConfig, FaultInjector  # noqa: E402

    cfg = dataclasses.replace(reduced(get_config("qwen3-1.7b")),
                              num_layers=2, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 250, size=int(rng.integers(5, 17))).astype(
        np.int32) for _ in range(n_requests)]
    deadline_rid = 0

    def reqs(with_deadline: bool):
        return [Request(p, max_new_tokens=8, rid=i,
                        deadline_s=0.08 if with_deadline
                        and i == deadline_rid else 0.0)
                for i, p in enumerate(prompts)]

    kw = dict(slots=4, max_len=64, page_size=8, offload=True,
              offload_policy=OffloadPolicy(impl="interpret"))
    guard = kernel_guard()
    thr = guard.threshold
    guard.reset()
    cache_dir = tempfile.mkdtemp(prefix="mpu_chaos_plans_")
    prev_cache = os.environ.get("MPU_PLAN_CACHE")
    os.environ["MPU_PLAN_CACHE"] = cache_dir
    try:
        base = Engine(cfg, params, **kw).generate(reqs(False))

        # poison the warm start: bit-flip every plan the fault-free
        # engine persisted — the chaos engine must detect, count, and
        # quarantine each one instead of deserializing garbage
        n_poisoned = 0
        for b in pathlib.Path(cache_dir).glob("*.bin"):
            raw = bytearray(b.read_bytes())
            raw[len(raw) // 2] ^= 0x01
            b.write_bytes(bytes(raw))
            n_poisoned += 1

        # quarantine after the first failure: a single-segment plan
        # dispatches once per trace, so the default threshold would
        # never trip inside one trace.  disk faults: truncate every
        # artifact read/write (truncated reads also exercise the
        # unparsable-marker corruption path deterministically)
        guard.threshold = 1
        inj = FaultInjector(FaultConfig(
            kernel_fail_rate=1.0, nan_logit_rate=1.0, nan_logit_limit=1,
            page_fail_rate=0.3, slow_step_rate=1.0, slow_step_s=0.02,
            disk_fail_rate=1.0, disk_truncate_share=1.0,
            seed=seed))
        eng = Engine(cfg, params, fault_injector=inj, **kw)
        done = eng.generate(reqs(True))
    finally:
        set_injector(None)
        set_disk_injector(None)
        guard.threshold = thr
        guard.reset()
        if prev_cache is None:
            os.environ.pop("MPU_PLAN_CACHE", None)
        else:
            os.environ["MPU_PLAN_CACHE"] = prev_cache
        shutil.rmtree(cache_dir, ignore_errors=True)

    sv = eng.serve_counters
    st = eng.offload_stats
    ok_exact = all(c.tokens == base[r].tokens
                   for r, c in done.items() if c.status == "ok")
    statuses: dict = {}
    for c in done.values():
        statuses[c.status] = statuses.get(c.status, 0) + 1

    chaos = {
        "n_requests": n_requests,
        "seed": seed,
        "statuses": statuses,
        "ok_tokens_exact": ok_exact,
        "deadline_status": done[deadline_rid].status,
        "pages_leaked": eng.pool.used_pages,
        "deadline_cancels": sv["deadline_cancels"],
        "nan_aborts": sv["nan_aborts"],
        "page_faults": sv["page_faults"],
        "alloc_stalls": sv["alloc_stalls"],
        "kernel_replans": sv["kernel_replans"],
        "quarantines": st["quarantines"],
        "kernel_failures": st["kernel_failures"],
        "kernel_fallbacks": st["kernel_fallbacks"],
        "plan_misses": st["plan_misses"],
        "plan_invalidations": st["plan_invalidations"],
        "disk_corrupt": st["disk_corrupt"],
        "disk_hits": st["disk_hits"],
        "disk_misses": st["disk_misses"],
        "plan_cache_entries_poisoned": n_poisoned,
        "injected": dict(inj.counters),
    }

    bad = []
    if MUST_SURVIVE["ok_tokens_exact"] and not ok_exact:
        bad.append("chaos: an 'ok' request's tokens diverge from the "
                   "fault-free run")
    if MUST_SURVIVE["deadline_cancelled"] and \
            done[deadline_rid].status != "cancelled":
        bad.append(f"chaos: deadlined request ended "
                   f"'{done[deadline_rid].status}', expected 'cancelled'")
    if MUST_SURVIVE["pages_reclaimed"] and eng.pool.used_pages != 0:
        bad.append(f"chaos: {eng.pool.used_pages} pool pages leaked")
    if st["quarantines"] < MUST_SURVIVE["min_quarantines"]:
        bad.append(f"chaos: {st['quarantines']} quarantines < "
                   f"{MUST_SURVIVE['min_quarantines']} (guarded dispatch "
                   f"never degraded)")
    if sv["nan_aborts"] < MUST_SURVIVE["min_nan_aborts"]:
        bad.append(f"chaos: {sv['nan_aborts']} nan aborts < "
                   f"{MUST_SURVIVE['min_nan_aborts']}")
    if sv["page_faults"] < MUST_SURVIVE["min_page_faults"]:
        bad.append(f"chaos: {sv['page_faults']} page faults < "
                   f"{MUST_SURVIVE['min_page_faults']}")
    if MUST_SURVIVE["bounded_replans"] and \
            st["plan_misses"] > 1 + st["plan_invalidations"]:
        bad.append(f"chaos: plan_misses {st['plan_misses']} > 1 + "
                   f"plan_invalidations {st['plan_invalidations']} "
                   f"(re-planned without a quarantine event)")
    if st["disk_corrupt"] < MUST_SURVIVE["min_disk_corrupt"]:
        bad.append(f"chaos: {st['disk_corrupt']} disk_corrupt < "
                   f"{MUST_SURVIVE['min_disk_corrupt']} (poisoned plan "
                   f"cache was never detected)")
    if inj.counters["disk_faults_injected"] < MUST_SURVIVE["min_disk_faults"]:
        bad.append(f"chaos: {inj.counters['disk_faults_injected']} disk "
                   f"faults injected < {MUST_SURVIVE['min_disk_faults']}")
    return chaos, bad


def _chaos_one_liner(chaos: dict) -> str:
    return (f"chaos: {chaos['statuses']} "
            f"(quarantines {chaos['quarantines']}, "
            f"fallbacks {chaos['kernel_fallbacks']}, "
            f"nan_aborts {chaos['nan_aborts']}, "
            f"page_faults {chaos['page_faults']}, "
            f"replans {chaos['plan_misses']}<="
            f"1+{chaos['plan_invalidations']}, "
            f"disk_corrupt {chaos['disk_corrupt']}, "
            f"disk_faults {chaos['injected']['disk_faults_injected']}, "
            f"pages_leaked {chaos['pages_leaked']}, "
            f"ok tokens exact: {chaos['ok_tokens_exact']})")


def check_regressions(res: dict, baseline: dict | None = None) -> list[str]:
    bad = []
    if res["speedup"] < MUST_SERVE["speedup_floor"]:
        bad.append(f"paged speedup {res['speedup']:.2f}x < committed "
                   f"floor {MUST_SERVE['speedup_floor']:.2f}x")
    if res["traffic_reduction"] < MUST_SERVE["traffic_floor"]:
        bad.append(f"KV traffic reduction {res['traffic_reduction']:.2f}x "
                   f"< committed floor {MUST_SERVE['traffic_floor']:.2f}x")
    if res["paged"]["step_traces"] > MUST_SERVE["max_step_traces"] or \
            res["paged"]["offload_traces"] > MUST_SERVE["max_step_traces"]:
        bad.append(f"decode retraced: step_traces="
                   f"{res['paged']['step_traces']} offload_traces="
                   f"{res['paged']['offload_traces']} (committed: 1)")
    if res["paged"]["admit_traces"] > MUST_SERVE["max_admit_traces"]:
        bad.append(f"admit traced {res['paged']['admit_traces']} times "
                   f"(committed: <= {MUST_SERVE['max_admit_traces']} "
                   f"pow2 buckets)")
    if MUST_SERVE["exact_tokens"] and not res["exact_tokens"]:
        bad.append("paged greedy tokens differ from fixed-slot tokens")
    if baseline:
        prev = baseline.get("traffic_reduction", 0.0)
        if res["traffic_reduction"] < prev * 0.98:
            bad.append(f"traffic reduction {res['traffic_reduction']:.2f}x"
                       f" < baseline {prev:.2f}x (deterministic ratchet)")
    return bad


def _load_baseline() -> dict | None:
    if not ARTIFACT.exists():
        return None
    try:
        prev = json.loads(ARTIFACT.read_text())
    except (OSError, json.JSONDecodeError):
        return None
    return prev if prev.get("schema_version") == SCHEMA_VERSION else None


def _one_liner(res: dict) -> str:
    return (f"paged {res['paged']['tokens_per_s']:.1f} tok/s vs fixed "
            f"{res['fixed']['tokens_per_s']:.1f} tok/s "
            f"(speedup {res['speedup']:.2f}x at equal KV memory, "
            f"KV traffic {res['traffic_reduction']:.2f}x lower, "
            f"p99 {res['paged']['p99_token_ms']:.1f}ms vs "
            f"{res['fixed']['p99_token_ms']:.1f}ms, "
            f"retraces {res['paged']['offload_traces']}, "
            f"artifact: {ARTIFACT.name})")


def _print_csv(res: dict) -> None:
    cols = ["engine", "tokens_per_s", "p50_token_ms", "p99_token_ms",
            "wall_s", "positions_streamed"]
    print(",".join(cols))
    for name in ("fixed", "paged"):
        r = res[name]
        print(",".join([name] + [f"{r[c]:.4f}" for c in cols[1:]]))


def _write_step_summary(res: dict, regressed: list[str]) -> None:
    import os

    path = os.environ.get("GITHUB_STEP_SUMMARY")
    if not path:
        return
    lines = ["### serve bench", "", f"`{_one_liner(res)}`", ""]
    if regressed:
        lines += ["**SERVING REGRESSION**", ""]
        lines += [f"- {r}" for r in regressed]
        lines.append("")
    try:
        with open(path, "a") as fh:
            fh.write("\n".join(lines) + "\n")
    except OSError:
        pass


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    csv = "--csv" in argv
    chaos_mode = "--chaos" in argv
    baseline = _load_baseline()      # before run() overwrites the artifact
    # --smoke shrinks the workload, so its deterministic traffic ratio is
    # not comparable to the committed full-run baseline: floors still
    # apply, but the artifact/ratchet stay full-run only
    res = run(write_artifact=False, n_requests=12 if smoke else 24)
    if csv:
        _print_csv(res)
    print(_one_liner(res))
    regressed = check_regressions(res, None if smoke else baseline)
    if chaos_mode:
        chaos, survive_bad = run_chaos(n_requests=6 if smoke else 8)
        res["chaos"] = chaos
        print(_chaos_one_liner(chaos))
        regressed += survive_bad
    if not smoke:
        ARTIFACT.write_text(json.dumps(res, indent=2))
    _write_step_summary(res, regressed)
    if regressed:
        print("SERVING REGRESSION: " + "; ".join(regressed),
              file=sys.stderr)
        sys.exit(1)
