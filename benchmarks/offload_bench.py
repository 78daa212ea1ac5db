"""JAX-level offload benchmark (beyond-paper deployable analogue).

For representative memory-bound chains (the Table-I workloads' value
chains + real transformer-block epilogues, now including the
matmul-anchored GEMM epilogues and lane-reduction chains), report:

1. **Traffic** (the paper's TSV accounting): naive per-eqn HBM bytes vs
   Algorithm-1 fused-segment bytes, plus the bytes whose round-trip is
   eliminated by segment-boundary donation (Pallas
   ``input_output_aliases`` on dead boundary buffers — the §IV-B3
   multiple-activated-row-buffers analogue), and the projected v5e time
   per call at 819 GB/s (memory-bound ops: time == bytes / bandwidth).
   For anchored chains the fused bytes count the matmul operands but
   NOT the product tensor — it lives in accumulator scratch; the [K,N]
   rhs weight is counted once per row block, matching the kernel's
   actual re-streaming.

2. **Interpreted vs compiled wall time**: the legacy per-call Python
   jaxpr interpreter (``mpu_offload_interpreted``) against the
   compile-time rewriter (``mpu_offload``).  Retrace counts and
   plan-cache hit rates come from the wrapper's ``stats`` counters; the
   compiled path must show exactly one trace and one plan miss
   regardless of call count.

3. **Regression guard**: every chain in ``MUST_FUSE`` carries its
   committed (segment count, traffic floor, anchored-backward floor):
   reporting a different segment count (an anchored chain splitting
   back to >= 2 segments or losing fusion entirely), a
   traffic_reduction below the floor, or fewer anchored BACKWARD
   (dlhs/drhs) segments than committed makes the process exit non-zero
   — independent of the artifact, so CI fails on fresh checkouts too.
   The committed ``BENCH_offload.json`` adds a second, tighter ratchet
   against the last recorded numbers.

The ``*_BWD`` / ``MLP_GRAD`` / ``TRAIN_STEP`` chains exercise the
grad-time contraction kernels: the handwritten GEMM backward anchors
both dGRAD forms, MLP_GRAD plans a real ``jax.grad`` trace, and
TRAIN_STEP plans loss -> grads -> momentum update as one program.
``ATTN_PREFILL`` commits the flash-shaped attention segment (QK^T ->
scale -> softmax -> PV as ONE anchored launch, zero score-matrix
bytes) and ``BATCHED_GEMM_BWD`` the batched N-D-grid backward anchors.

4. **Decision accounting** (the §IV-B1 policy view): every run plans
   under an ``OffloadPolicy`` (``--policy {greedy,cost,all_near,
   all_far}``, default greedy) and reports per chain how many candidate
   segments the policy *declined* plus the modeled near/far time ratio
   across all candidates.  The greedy run additionally re-plans every
   chain under ``cost`` and asserts the cost backend's decision-modeled
   bytes (each candidate at its chosen side's price) never exceed
   greedy's — cost picks the cheaper side per candidate, so a violation
   means the decision backend and the pricing have drifted apart.

5. **Persistent plan cache**: with ``MPU_PLAN_CACHE`` set, every
   compiled wrapper persists its plan to the shared artifact store and
   the summary aggregates the disk counters (``disk_hits`` /
   ``disk_misses`` / ``disk_corrupt`` plus total ``plan_misses``).
   ``--assert-warm`` turns the warm-restart contract into an exit
   code: a second run against the same cache directory must plan
   NOTHING fresh (``plan_misses == 0``) and serve every plan from disk
   (``disk_hits > 0``) — the CI warm-start smoke runs the bench twice
   and passes ``--assert-warm`` on the second.

Writes a versioned ``BENCH_offload.json`` artifact at the repo root
(greedy runs only — non-default policies must not clobber the ratchet
baseline).  ``--smoke`` runs a reduced rep count for per-push CI
freshness; ``--csv`` emits the rows table as CSV for quick diffing;
under GitHub Actions the geomean one-liner (and any regression) is
appended to the job summary via ``$GITHUB_STEP_SUMMARY``.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

import jax
import jax.numpy as jnp

from repro.core import (
    OffloadPolicy,
    mpu_offload,
    mpu_offload_interpreted,
    offload_report,
)
from repro.core.machine import V5E

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARTIFACT = ROOT / "BENCH_offload.json"

# v7: rows/summary grow persistent-plan-cache counters (disk_hits /
# disk_misses / disk_corrupt, summary["plan_cache"])
# v8: rows grow a static-verifier verdict ("verified": no finding of
# severity >= error from repro.analysis.verify_plan); check_regressions
# fails any unverified chain
SCHEMA_VERSION = 8

# Committed fusion contract: chain -> (segments, traffic_reduction
# floor, anchored-backward-segment floor).  A later segmenter change
# that reports a different segment count (e.g. an anchored GEMM chain
# splitting back into >= 2 segments), a traffic_reduction below the
# floor, or fewer anchored BACKWARD segments (dlhs/drhs forms — the
# grad-time contractions) than committed is a coverage regression and
# fails CI even without a baseline artifact.
MUST_FUSE = {
    "AXPY": (1, 1.3, 0),
    "BIAS_GELU_RES": (1, 2.0, 0),
    "SWIGLU_EPI": (1, 2.5, 0),
    "RMS_SCALE_RES": (1, 2.9, 0),
    "ADAM_CHAIN": (1, 3.0, 0),
    "MLP_RESIDUAL": (1, 2.5, 0),
    "GEMM_BIAS_GELU": (1, 1.5, 0),
    "GEMM_SWIGLU": (1, 1.5, 0),
    "RMSNORM_CHAIN": (1, 1.5, 0),
    "SOFTMAX_CHAIN": (1, 1.5, 0),
    "GEMM_BWD": (2, 2.3, 2),
    "MLP_GRAD": (4, 3.0, 1),
    "TRAIN_STEP": (5, 3.0, 1),
    # the batched-anchor chains: ATTN_PREFILL must plan as ONE
    # flash-shaped segment whose [S, T] score matrix never touches HBM
    # (the >= 4x floor is the PR's acceptance criterion), and the
    # batched GEMM backward must anchor both grad contractions with
    # batch dims as outer grid axes
    "ATTN_PREFILL": (1, 4.0, 0),
    "BATCHED_GEMM_BWD": (2, 2.0, 2),
}


def _cases():
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


def _time_us(fn, args, reps: int) -> float:
    out = fn(*args)                      # warmup (compile / first plan)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _geomean(vals):
    g = 1.0
    for v in vals:
        g *= v
    return g ** (1.0 / len(vals))


def run(write_artifact: bool = True, reps: int = 30, interp_reps: int = 5,
        policy_mode: str = "greedy"):
    policy = OffloadPolicy(mode=policy_mode, bulk_threshold=4096)
    rows = []
    bw = V5E.hbm_gbps * 1e9
    for name, fn, args, donate in _cases():
        # the modeled-traffic plan includes invar donation; the timed
        # executable does NOT donate (the timing loop reuses its inputs)
        plan = offload_report(fn, *args, policy=policy,
                              donate_argnums=donate)
        # static-verifier verdict on the measured plan: alias safety,
        # index bounds, VMEM legality (warnings are advisory; errors
        # fail the contract check below)
        from repro.analysis import verify_plan
        verified = not any(f.severity == "error" for f in verify_plan(plan))

        compiled = mpu_offload(fn, policy=policy)
        interpreted = mpu_offload_interpreted(fn, policy=policy)

        compiled_us = _time_us(compiled, args, reps)
        interp_us = _time_us(interpreted, args, interp_reps)
        st = compiled.stats.as_dict()
        near_us = sum(d.near_us for d in plan.decisions)
        far_us = sum(d.far_us for d in plan.decisions)

        rows.append({
            "chain": name,
            "verified": verified,
            "segments": len(plan.segments),
            "declined": sum(1 for d in plan.decisions if not d.fused),
            "near_far_ratio": near_us / far_us if far_us else 0.0,
            "anchored": sum(1 for s in plan.segments
                            if s.matmul is not None),
            "anchored_bwd": sum(1 for s in plan.segments
                                if s.matmul is not None
                                and s.matmul.form in ("dlhs", "drhs")),
            "naive_mb": plan.naive_hbm_bytes / 1e6,
            "fused_mb": plan.fused_hbm_bytes / 1e6,
            "donated_mb": plan.donated_hbm_bytes / 1e6,
            "effective_mb": plan.effective_hbm_bytes / 1e6,
            "traffic_reduction": plan.traffic_reduction,
            "naive_us_v5e": plan.naive_hbm_bytes / bw * 1e6,
            "fused_us_v5e": plan.fused_hbm_bytes / bw * 1e6,
            "interpreted_us": interp_us,
            "compiled_us": compiled_us,
            "compiled_speedup": interp_us / max(compiled_us, 1e-9),
            "retraces": st["traces"],          # must stay 1: plan baked in
            "plan_hits": st["plan_hits"],
            "plan_misses": st["plan_misses"],
            "plan_evictions": st["evictions"],
            "plan_hit_rate": st["hit_rate"],
            "disk_hits": st["disk_hits"],
            "disk_misses": st["disk_misses"],
            "disk_corrupt": st["disk_corrupt"],
        })

    mean_traffic = sum(r["traffic_reduction"] for r in rows) / len(rows)
    summary = {
        "schema_version": SCHEMA_VERSION,
        "policy": policy_mode,
        "segments_declined_total": sum(r["declined"] for r in rows),
        "anchored_bwd_total": sum(r["anchored_bwd"] for r in rows),
        "mean_traffic_reduction": mean_traffic,
        "geomean_traffic_reduction": _geomean(
            [r["traffic_reduction"] for r in rows]),
        "geomean_compiled_speedup": _geomean(
            [r["compiled_speedup"] for r in rows]),
        "geomean_fused_mb": _geomean([r["fused_mb"] for r in rows]),
        "geomean_effective_mb": _geomean([r["effective_mb"] for r in rows]),
        "max_retraces": max(r["retraces"] for r in rows),
        "backend": jax.default_backend(),
        # warm-restart accounting across every compiled wrapper: with a
        # shared MPU_PLAN_CACHE a SECOND run must show plan_misses == 0
        # and disk_hits == number of chains (--assert-warm enforces it)
        "plan_cache": {
            "dir": os.environ.get("MPU_PLAN_CACHE") or None,
            "plan_misses": sum(r["plan_misses"] for r in rows),
            "disk_hits": sum(r["disk_hits"] for r in rows),
            "disk_misses": sum(r["disk_misses"] for r in rows),
            "disk_corrupt": sum(r["disk_corrupt"] for r in rows),
        },
    }

    # the committed artifact is the greedy ratchet baseline: a run under
    # a different policy reports but never overwrites it
    if write_artifact and policy_mode == "greedy":
        ARTIFACT.write_text(json.dumps(
            {"schema_version": SCHEMA_VERSION, "rows": rows,
             "summary": summary}, indent=2))
    return rows, summary


def _decision_bytes(plan) -> int:
    """The plan's traffic under the DECISION model: each candidate at
    its chosen side's price (fused -> near bytes, declined -> modeled
    far bytes).  This is the objective the cost backend minimizes
    per-candidate, so cost <= greedy holds exactly — unlike the plan's
    naive traffic accounting, which prices unfused eqns at per-eqn
    round-trips and can legitimately report a correct cost-mode decline
    as a traffic increase."""
    return sum(d.near_bytes if d.fused else d.far_bytes
               for d in plan.decisions)


def check_cost_vs_greedy() -> tuple[list[str], float]:
    """The cost-backend invariant: ``cost`` picks, per candidate, the
    side the model prices cheaper, so its decision-modeled bytes can
    never exceed greedy's on any chain.  Returns (violations, cost
    geomean traffic reduction) — planning only, no execution."""
    greedy_policy = OffloadPolicy(bulk_threshold=4096)
    cost_policy = OffloadPolicy(mode="cost", bulk_threshold=4096)
    bad, reductions = [], []
    for name, fn, args, donate in _cases():
        pg = offload_report(fn, *args, policy=greedy_policy,
                            donate_argnums=donate)
        pc = offload_report(fn, *args, policy=cost_policy,
                            donate_argnums=donate)
        reductions.append(pc.traffic_reduction)
        bg, bc = _decision_bytes(pg), _decision_bytes(pc)
        if bc > bg:
            bad.append(f"{name}: cost-mode decision bytes {bc} > greedy "
                       f"{bg}: the cost model fused something it prices "
                       f"as unprofitable")
    return bad, _geomean(reductions)


def check_regressions(rows, baseline: dict | None = None) -> list[str]:
    """Chains violating their committed (segments, traffic floor,
    anchored-backward floor) contract, plus chains whose
    (deterministic, plan-derived) traffic_reduction dropped vs the
    committed artifact."""
    bad = []
    missing = set(MUST_FUSE) - {r["chain"] for r in rows}
    if missing:        # a contracted chain vanished from the suite
        bad.append(f"chains missing from the run: {sorted(missing)}")
    for r in rows:
        # schema v8: every chain's plan must pass the static verifier
        # (rows from a pre-v8 baseline lack the key — default to True)
        if not r.get("verified", True):
            bad.append(f"{r['chain']} plan failed static verification "
                       f"(run python -m repro.analysis.lint --chains)")
        contract = MUST_FUSE.get(r["chain"])
        if contract is None:
            continue
        want_segments, floor, bwd_floor = contract
        if r["segments"] != want_segments:
            bad.append(f"{r['chain']} fuses {r['segments']} segments"
                       f" (committed: {want_segments})")
        if r["traffic_reduction"] < floor:
            bad.append(f"{r['chain']} traffic {r['traffic_reduction']:.2f}x"
                       f" < committed floor {floor:.2f}x")
        if r["anchored_bwd"] < bwd_floor:
            bad.append(f"{r['chain']} anchors {r['anchored_bwd']} backward"
                       f" segments (committed: >= {bwd_floor})")
    base = {r["chain"]: r for r in (baseline or {}).get("rows", [])}
    for r in rows:
        b = base.get(r["chain"])
        if b and r["traffic_reduction"] < b["traffic_reduction"] * 0.98:
            bad.append(f"{r['chain']} traffic {r['traffic_reduction']:.2f}x"
                       f" < baseline {b['traffic_reduction']:.2f}x")
    return bad


def _load_baseline() -> dict | None:
    if not ARTIFACT.exists():
        return None
    try:
        prev = json.loads(ARTIFACT.read_text())
    except (OSError, json.JSONDecodeError):
        return None
    return prev if prev.get("schema_version") == SCHEMA_VERSION else None


_CSV_COLS = ["chain", "verified", "segments", "declined", "near_far_ratio",
             "anchored", "anchored_bwd",
             "naive_mb", "fused_mb",
             "donated_mb", "effective_mb", "traffic_reduction",
             "naive_us_v5e", "fused_us_v5e", "interpreted_us",
             "compiled_us", "compiled_speedup", "retraces", "plan_hits",
             "plan_misses", "plan_evictions", "plan_hit_rate",
             "disk_hits", "disk_misses", "disk_corrupt"]


def _print_csv(rows):
    print(",".join(_CSV_COLS))
    for r in rows:
        print(",".join(
            f"{r[c]:.4f}" if isinstance(r[c], float) else str(r[c])
            for c in _CSV_COLS))


def _geomean_line(summary) -> str:
    return (f"geomean: traffic_reduction="
            f"{summary['geomean_traffic_reduction']:.2f}x "
            f"compiled_speedup={summary['geomean_compiled_speedup']:.1f}x "
            f"(modeled {summary['geomean_fused_mb']:.2f}MB fused / "
            f"{summary['geomean_effective_mb']:.2f}MB after donation, "
            f"{summary['anchored_bwd_total']} anchored bwd segments, "
            f"artifact: {ARTIFACT.name})")


def _plan_cache_line(summary) -> str | None:
    pc = summary.get("plan_cache", {})
    if not pc.get("dir"):
        return None
    return (f"plan cache ({pc['dir']}): disk_hits={pc['disk_hits']} "
            f"disk_misses={pc['disk_misses']} "
            f"disk_corrupt={pc['disk_corrupt']} "
            f"fresh_plans={pc['plan_misses']}")


def _write_step_summary(summary, regressed) -> None:
    """Append the geomean one-liner (and the disk-cache hit line when a
    plan cache is active) to the GitHub job summary (no-op outside
    Actions).  Failures land there too so a red PR check shows WHICH
    chain regressed without opening the log."""
    path = os.environ.get("GITHUB_STEP_SUMMARY")
    if not path:
        return
    lines = ["### offload bench", "", f"`{_geomean_line(summary)}`", ""]
    cache_line = _plan_cache_line(summary)
    if cache_line:
        lines += [f"`{cache_line}`", ""]
    if regressed:
        lines += ["**FUSION REGRESSION**", ""]
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
    assert_warm = "--assert-warm" in argv
    policy_mode = "greedy"
    if "--policy" in argv:
        policy_mode = argv[argv.index("--policy") + 1]
    baseline = _load_baseline()      # before run() overwrites the artifact
    rows, summary = run(reps=5 if smoke else 30,
                        interp_reps=2 if smoke else 5,
                        policy_mode=policy_mode)
    if csv:
        _print_csv(rows)
    else:
        for r in rows:
            mark = "*" if r["anchored"] else " "
            mark = "+" if r["anchored_bwd"] else mark
            mark = "!" if not r["verified"] else mark
            print(f"{r['chain']:14s} segs={r['segments']}{mark} "
                  f"declined={r['declined']} "
                  f"nf={r['near_far_ratio']:.2f} "
                  f"traffic={r['traffic_reduction']:.2f}x "
                  f"donated={r['donated_mb']:6.2f}MB "
                  f"interp={r['interpreted_us']:9.1f}us "
                  f"compiled={r['compiled_us']:8.1f}us "
                  f"speedup={r['compiled_speedup']:7.1f}x "
                  f"retraces={r['retraces']}")
        print("(* = matmul-anchored segment, + = anchored backward "
              "segment, ! = failed static verification; nf = modeled "
              "near/far time ratio over all candidate segments)")
    print(_geomean_line(summary))
    cache_line = _plan_cache_line(summary)
    if cache_line:
        print(cache_line)
    regressed = []
    if assert_warm:
        # the warm-restart acceptance bar: everything from disk,
        # nothing planned fresh
        pc = summary["plan_cache"]
        if not pc["dir"]:
            regressed.append("--assert-warm requires MPU_PLAN_CACHE")
        else:
            if pc["plan_misses"] != 0:
                regressed.append(f"warm run planned {pc['plan_misses']} "
                                 f"chains fresh (expected 0)")
            if pc["disk_hits"] <= 0:
                regressed.append("warm run had zero disk hits")
    if policy_mode == "greedy":
        # the MUST_FUSE contract and the artifact ratchet are committed
        # for the default greedy policy; other policies report only
        # (+=: an --assert-warm failure above must survive this block)
        regressed += check_regressions(rows, baseline)
        cost_bad, g_cost = check_cost_vs_greedy()
        regressed += cost_bad
        print(f"cost-mode geomean traffic_reduction={g_cost:.2f}x "
              f"(decision-modeled bytes <= greedy on every chain: "
              f"{'ok' if not cost_bad else 'VIOLATED'})")
    _write_step_summary(summary, regressed)
    if regressed:
        print("FUSION REGRESSION: " + "; ".join(regressed), file=sys.stderr)
        sys.exit(1)
