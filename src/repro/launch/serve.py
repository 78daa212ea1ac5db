"""Serving launcher: one paged continuous-batching ``Engine`` on one device.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b [--offload]

Without ``--local`` the model runs at its published widths (``get_config``)
with random weights from ``--seed``; ``--local`` cuts it to the reduced
CPU-sized config.  Weights stay float32 on the device and the compute
dtype is the config's.  The requests are random prompts of a few dozen
tokens, long enough with their answers to cross a KV page boundary.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, ModelConfig, get_config, reduced
from repro.core.policy import OffloadPolicy
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.serve import Engine, Request


def build_engine(cfg: ModelConfig, *, seed: int = 0, slots: int = 4,
                 max_len: int = 128,
                 offload_policy: OffloadPolicy | None = None) -> Engine:
    """An ``Engine`` over freshly initialised weights, made on the device
    (``offload_policy`` None serves without the offload rewriter)."""
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return Engine(cfg, params, slots=slots, max_len=max_len, seed=seed,
                  offload=offload_policy is not None,
                  offload_policy=offload_policy)


def make_requests(cfg: ModelConfig, n: int, *, seed: int = 0,
                  prompt_len: tuple[int, int] = (40, 60),
                  max_new_tokens: int = 24) -> list[Request]:
    """``n`` greedy requests with random prompts whose lengths are drawn
    from ``prompt_len`` (inclusive)."""
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, cfg.vocab_size,
                                 size=int(rng.integers(prompt_len[0],
                                                       prompt_len[1] + 1))),
                    max_new_tokens=max_new_tokens, rid=i)
            for i in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(ARCH_IDS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--local", action="store_true",
                    help="serve the reduced config (CPU-sized)")
    ap.add_argument("--offload", action="store_true",
                    help="compile-time near-bank offload of the decode step")
    ap.add_argument("--offload-mode", default=None,
                    choices=["greedy", "cost", "all_near", "all_far"],
                    help="offload decision backend (OffloadPolicy.mode); "
                         "implies --offload")
    ap.add_argument("--explain-offload", action="store_true",
                    help="print the per-segment offload decision table "
                         "for the decode step; implies --offload")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="persistent offload-plan cache directory (sets "
                         "MPU_PLAN_CACHE): a restarted server warm-"
                         "starts its decode plan from disk with zero "
                         "fresh planning; implies --offload")
    args = ap.parse_args()
    # asking for a mode or the decision table means offload is wanted
    args.offload = args.offload or args.explain_offload \
        or args.offload_mode is not None or args.plan_cache is not None
    if args.plan_cache:
        import os
        os.environ["MPU_PLAN_CACHE"] = args.plan_cache
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.local:
        cfg = reduced(cfg)
    policy = OffloadPolicy(mode=args.offload_mode or "greedy") \
        if args.offload else None
    engine = build_engine(cfg, seed=args.seed, offload_policy=policy)
    reqs = make_requests(cfg, args.requests, seed=args.seed,
                         prompt_len=(4, 12) if args.local else (40, 60),
                         max_new_tokens=8 if args.local else 24)
    done = engine.generate(reqs)
    total = sum(len(c.tokens) for c in done.values())
    print(f"served {len(reqs)} requests / {total} tokens")
    if args.offload:
        # misses == traces == 1 means: planned once, compiled once,
        # every decode step ran the staged executable
        print(f"offload compile stats: {engine.offload_stats}")
        if args.explain_offload:
            print(engine.explain_decode())


if __name__ == "__main__":
    main()
