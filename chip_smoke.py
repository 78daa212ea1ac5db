#!/usr/bin/env python3
"""Bring-up smoke test: the system's main path on a TPU, at Qwen3-1.7B's
published widths with random weights made from a seed.

    python chip_smoke.py              # one chip: paged serving + offload
    python chip_smoke.py --chips 4    # four chips: sharded training only

Default phase (one chip): an offloaded paged ``Engine`` serves a few
requests whose prompts plus answers cross a KV page boundary, then the
offloaded decode step and the same step planned ``mode="all_far"``
(plain XLA) are held, on the same params, cache and inputs, to a
float32 all_far reference; the offloaded step with float32 activations
is held to it too.  It fails unless the kernel guard saw no failure and
no fallback, the decode plan kept at least one near segment and was not
degraded, and the compiled decode step holds a Mosaic kernel
(``tpu_custom_call``).

``--chips 4`` phase: the training launcher's loop on a (data 2, model 2)
mesh over the four devices takes a few optimizer steps; the step-0 loss
must match a loss of the same params and batch on device 0, the
gradient w.r.t. every norm scale on the mesh must match device 0's (and
must not match device 0's of a half batch or of the next batch), and
every loss must be finite.

Everything runs in this one process.  The script exits non-zero, and
prints no result, unless JAX runs on a TPU; its last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ARCH = "qwen3-1.7b"
SEED = 0
# serving: 4 slots, prompts of 40-60 tokens and 24 new tokens each, so
# every request ends past position 64 — across the first 64-token page
REQUESTS, SLOTS, PAGE, MAX_LEN = 4, 4, 64, 128
PROMPT_LEN, NEW_TOKENS = (40, 60), 24
# decode logits.  The reference is the same step with float32
# activations, planned all_far, at the highest matmul precision.  Errors
# are max |x - ref| / max |ref|.  The offloaded bf16 step may be at most
# LOGITS_ERR_RATIO times as far from the reference as the all_far bf16
# step (bf16 rounding, at other points, through 28 layers); the
# offloaded float32 step at most F32_RTOL from it (float32 rounding).
LOGITS_ERR_RATIO = 2.0
F32_RTOL = 1e-3
# training: batch x sequence per step (from the compiled step's memory
# analysis on a described v5e:2x2) and steps.  The step-0 loss of the
# sharded step against a single-device loss, and the gradient of the
# step-0 loss w.r.t. every norm scale (a backward pass through every
# layer and the whole batch) on the mesh against device 0, as
# |mesh - ref| / |ref| in the 2-norm.  GRAD_RTOL must also reject the
# same gradient on device 0 of a half batch and of the next batch.
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 3
LOSS_RTOL = 1e-4
GRAD_RTOL = 0.1


def _compile_clock():
    """Seconds JAX has spent in backend compiles (a persistent-cache hit
    counts its retrieval), and how many compiles it served from the
    persistent cache, as a live dict."""
    import jax

    clock = {"compile_seconds": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            clock["compile_seconds"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            clock["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return clock


def serve_phase(cfg) -> dict:
    """Serve the requests, then compare the offloaded decode step with
    its all_far plan; returns what the checks read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.offload import mpu_offload
    from repro.core.policy import OffloadPolicy
    from repro.kernels.guard import kernel_guard
    from repro.launch.serve import build_engine, make_requests
    from repro.models import build_model

    engine = build_engine(cfg, seed=SEED, slots=SLOTS, max_len=MAX_LEN,
                          offload_policy=OffloadPolicy(mode="greedy"))
    assert engine.page_size == PAGE
    reqs = make_requests(cfg, REQUESTS, seed=SEED, prompt_len=PROMPT_LEN,
                         max_new_tokens=NEW_TOKENS)
    t0 = time.perf_counter()
    done = engine.generate(reqs)
    wall = time.perf_counter() - t0
    out = {
        "wall_seconds": wall,
        "statuses": sorted({c.status for c in done.values()}),
        "tokens": [len(done[r.rid].tokens) for r in reqs],
        "last_positions": [len(r.prompt) + len(done[r.rid].tokens)
                           for r in reqs],
        "tokens_in_vocab": all(0 <= t < cfg.vocab_size
                               for c in done.values() for t in c.tokens),
        "guard": kernel_guard().stats(),
        "offload_stats": engine.offload_stats,
    }
    report = engine.explain_decode()
    out["plan_mode"] = report.policy.mode
    out["near_segments"] = report.n_fused
    out["declined"] = report.n_declined

    # the decode step on live slots: each reads two pages of the pool
    # the requests just filled, at positions on both sides of a page
    # boundary
    model, max_len = engine.model, engine.max_len

    def paged_decode(params, cache, tok, pos, tables, active):
        return model.decode_step_paged(params, cache, tok, pos, tables,
                                       active, max_len=max_len)

    args = (engine.params, engine.cache,
            jnp.asarray([r.prompt[0] for r in reqs], jnp.int32),
            jnp.asarray([PAGE - 1, PAGE, PAGE + 7, 2 * PAGE - 2], jnp.int32),
            jnp.arange(1, 1 + 2 * SLOTS, dtype=jnp.int32).reshape(SLOTS, 2),
            jnp.ones((SLOTS,), bool))
    near = jax.jit(engine._decode_offload).lower(*args).compile()
    far = jax.jit(mpu_offload(paged_decode, policy=OffloadPolicy(
        mode="all_far"))).lower(*args).compile()
    out["tpu_custom_call"] = "tpu_custom_call" in near.as_text()
    logits = {"near": near(*args)[0], "far": far(*args)[0]}

    # the same step with float32 activations on the same params and
    # cache (widened): all_far is the reference, offloaded the witness
    model32 = build_model(dataclasses.replace(cfg, dtype="float32"))

    def paged_decode32(params, cache, tok, pos, tables, active):
        return model32.decode_step_paged(params, cache, tok, pos, tables,
                                         active, max_len=max_len)

    args32 = (args[0], jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        args[1]), *args[2:])
    with jax.default_matmul_precision("highest"):
        for name, mode in (("ref", "all_far"), ("near32", "greedy")):
            logits[name] = jax.jit(mpu_offload(
                paged_decode32, policy=OffloadPolicy(mode=mode)))(*args32)[0]
    logits = {k: np.asarray(v, np.float32) for k, v in logits.items()}
    ref = logits["ref"]
    out["logits_finite"] = all(np.isfinite(v).all()
                               for v in logits.values())
    out["logits_max_abs"] = float(np.abs(ref).max())
    for k in ("near", "far", "near32"):
        out[f"err_{k}"] = float(np.abs(logits[k] - ref).max()
                                / out["logits_max_abs"])
    out["near_vs_far"] = float(np.abs(logits["near"] - logits["far"]).max()
                               / out["logits_max_abs"])
    out["greedy_agree"] = int((logits["near"].argmax(-1)
                               == ref.argmax(-1)).sum())
    out["guard_after"] = kernel_guard().stats()
    return out


def check_serve(out: dict) -> list[str]:
    """The serving phase's failures (empty when it passed)."""
    bad = []
    if out["statuses"] != ["ok"]:
        bad.append(f"request statuses {out['statuses']}")
    if out["tokens"] != [NEW_TOKENS] * REQUESTS:
        bad.append(f"tokens per request {out['tokens']}")
    if not any(p > PAGE for p in out["last_positions"]):
        bad.append("no request crossed a page boundary")
    if not out["tokens_in_vocab"]:
        bad.append("a served token is outside the vocabulary")
    for key in ("guard", "guard_after"):
        g = out[key]
        if g["kernel_failures"] or g["kernel_fallbacks"]:
            bad.append(f"kernel guard {key}: {g}")
    if out["plan_mode"] == "all_far":
        bad.append("decode plan degraded to all_far")
    if out["near_segments"] < 1:
        bad.append("decode plan has no near segment")
    if not out["tpu_custom_call"]:
        bad.append("compiled decode step holds no tpu_custom_call")
    if not out["logits_finite"]:
        bad.append("non-finite decode logits")
    if not out["err_near"] <= LOGITS_ERR_RATIO * out["err_far"]:
        bad.append(f"offloaded bf16 logits err {out['err_near']} > "
                   f"{LOGITS_ERR_RATIO} x all_far bf16 err "
                   f"{out['err_far']}")
    if not out["err_near32"] <= F32_RTOL:
        bad.append(f"offloaded float32 logits err {out['err_near32']} > "
                   f"{F32_RTOL}")
    return bad


def _norm_grads(model, remat: bool):
    """``fn(params, batch) -> (loss, grad)``: the loss and its gradient
    w.r.t. every norm scale, flattened into one float32 vector.  The
    leaves are small, but their gradient needs a backward pass through
    every layer over the whole batch."""
    import jax
    import jax.numpy as jnp

    def fn(params, batch):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        is_norm = [jax.tree_util.keystr(p).endswith("['scale']")
                   for p, _ in flat]

        def loss(scales):
            it = iter(scales)
            leaves = [next(it) if n else v
                      for n, (_, v) in zip(is_norm, flat)]
            return model.loss_fn(jax.tree_util.tree_unflatten(
                treedef, leaves), batch, remat=remat)[0]

        value, grads = jax.value_and_grad(loss)(
            [v for n, (_, v) in zip(is_norm, flat) if n])
        return value, jnp.concatenate([g.ravel() for g in grads])

    return fn


def train_phase(cfg, batch: int, seq: int, steps: int, devices) -> dict:
    """Train ``steps`` steps on a mesh over ``devices``; returns the
    per-step losses and the step-0 loss and norm-scale gradient on the
    mesh and on device 0 (also of a half batch and of the next batch)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.configs import TrainConfig
    from repro.configs.base import ShapeConfig
    from repro.launch.train import Trainer, run

    tcfg = TrainConfig(total_steps=steps, warmup_steps=1, seed=SEED)
    trainer = Trainer(cfg, ShapeConfig("smoke", seq, batch, "train"), tcfg,
                      devices)
    grads = _norm_grads(trainer.model, tcfg.remat)
    # references first, on device 0 alone, and freed before training.
    # A whole batch's logits and their gradient do not fit on one chip
    # beside the weights, so each batch goes in two halves, combined by
    # their token counts (the loss is a mean over unmasked tokens)
    dev0 = SingleDeviceSharding(devices[0])
    params = jax.jit(trainer.model.init, out_shardings=dev0)(trainer.rng)
    ref_fn = jax.jit(grads)
    ref = {}
    for name, step in (("ref", 0), ("next", 1)):
        b = trainer.data.batch(step)
        parts = []
        for rows in (slice(0, batch // 2), slice(batch // 2, batch)):
            part = {k: v[rows] for k, v in b.items()}
            loss, g = ref_fn(params, jax.device_put(part, dev0))
            parts.append((float(part["mask"].sum()), float(loss),
                          np.asarray(g)))
        n = sum(w for w, _, _ in parts)
        ref[name] = (sum(w * v for w, v, _ in parts) / n,
                     sum(w * g for w, _, g in parts) / n)
        if name == "ref":
            ref["half"] = parts[0][1:]
    del params

    state = trainer.init_state()
    with trainer.sharding_scope():
        loss_mesh, g_mesh = jax.jit(grads)(state.params, trainer.batch(0))
    g_ref = ref["ref"][1]
    scale = float(np.linalg.norm(g_ref))

    def dist(g):
        return float(np.linalg.norm(np.asarray(g) - g_ref)) / scale

    losses: list[float] = []
    t0 = time.perf_counter()
    run(trainer, state, 0, steps, log_every=1,
        on_metrics=lambda step, m: losses.append(float(m["loss"])))
    return {"mesh": list(trainer.mesh_shape), "batch": batch, "seq": seq,
            "losses": losses, "loss_ref": ref["ref"][0],
            "loss_half": ref["half"][0], "loss_next": ref["next"][0],
            "loss_mesh_grad_fn": float(loss_mesh),
            "grad_mesh": dist(g_mesh), "grad_half": dist(ref["half"][1]),
            "grad_next": dist(ref["next"][1]), "grad_norm": scale,
            "grad_size": int(g_ref.size),
            "loss_finite": bool(np.isfinite(losses).all()),
            "wall_seconds": time.perf_counter() - t0}


def check_train(out: dict) -> list[str]:
    """The training phase's failures (empty when it passed)."""
    bad = []
    if not out["loss_finite"]:
        bad.append(f"non-finite loss {out['losses']}")
    diff = abs(out["losses"][0] - out["loss_ref"])
    if not diff <= LOSS_RTOL * abs(out["loss_ref"]):
        bad.append(f"step-0 loss {out['losses'][0]} vs single-device "
                   f"{out['loss_ref']}: |diff| {diff} > {LOSS_RTOL} x ref")
    if not out["grad_mesh"] <= GRAD_RTOL:
        bad.append(f"norm-scale gradient on the mesh differs from device "
                   f"0 by {out['grad_mesh']} > {GRAD_RTOL}")
    for k in ("grad_half", "grad_next"):
        if not out[k] > GRAD_RTOL:
            bad.append(f"the gradient check cannot tell {k} from the "
                       f"batch: {out[k]} <= {GRAD_RTOL}")
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded training phase")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import setup_compile_cache

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"platform: {dev.platform}")
    print(f"device_kind: {dev.device_kind}")
    print(f"device_count: {len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    print(f"compile_cache: {setup_compile_cache()}")
    clock = _compile_clock()
    cfg = get_config(ARCH)

    if args.chips == 4:
        out = train_phase(cfg, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS,
                          devices[:4])
        print(f"train mesh (data, model): {out['mesh']}, batch "
              f"{out['batch']} x seq {out['seq']}")
        print(f"train losses: {out['losses']}")
        print(f"single-device step-0 loss: {out['loss_ref']} "
              f"(tolerance {LOSS_RTOL} relative); of a half batch "
              f"{out['loss_half']}, of the next batch {out['loss_next']}")
        print(f"norm-scale gradient ({out['grad_size']} values, |g| "
              f"{out['grad_norm']}) vs device 0: mesh {out['grad_mesh']}"
              f" (bound {GRAD_RTOL}), half batch {out['grad_half']}, next "
              f"batch {out['grad_next']}; mesh loss "
              f"{out['loss_mesh_grad_fn']}")
        print(f"train wall_seconds: {out['wall_seconds']}")
        bad = check_train(out)
        count = 4
    else:
        out = serve_phase(cfg)
        print(f"served_requests: {REQUESTS}")
        print(f"tokens_served: {sum(out['tokens'])}")
        print(f"serve wall_seconds (compiles included): "
              f"{out['wall_seconds']}")
        print(f"decode plan: mode {out['plan_mode']}, "
              f"{out['near_segments']} near / {out['declined']} declined")
        print(f"offload stats: {out['offload_stats']}")
        print(f"kernel guard: {out['guard_after']}")
        print(f"decode logits vs float32 all_far reference (max |ref| "
              f"{out['logits_max_abs']}): offloaded bf16 err "
              f"{out['err_near']}, all_far bf16 err {out['err_far']} "
              f"(ratio bound {LOGITS_ERR_RATIO}), offloaded float32 err "
              f"{out['err_near32']} (bound {F32_RTOL}); offloaded vs "
              f"all_far bf16 {out['near_vs_far']}; greedy tokens agree "
              f"on {out['greedy_agree']}/{SLOTS}")
        bad = check_serve(out)
        count = 1
    print(f"compile_seconds: {clock['compile_seconds']} "
          f"(persistent-cache hits: {clock['cache_hits']})")
    if bad:
        for b in bad:
            print(f"FAILED: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
