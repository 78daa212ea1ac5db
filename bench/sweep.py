#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest offered rate
the engine sustains.

    python3 bench/sweep.py --workload <cell> --rates 0.4,0.6,0.8 --seconds <s>

One process offers the cell's traffic at each rate in turn for the
window, on an engine set up afresh for each (rates override the mix's
``rate_per_s``; a block keeps the mix's span, so each rate times that
span has to be whole), drains, and prints one JSON line per rate: offered and
completed tokens per second, the tails and the queue wait (a queue that
grows all through the window waits long).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    import jax

    from bench import harness
    from repro.launch.compile_cache import setup_compile_cache

    cell = harness.load_cell(args.workload)
    devices, peak = harness.check_devices(cell.chips)
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell.sizes = {**cell.sizes, "check": {**cell.sizes["check"],
                                          "sample_requests": 1}}
    base = copy.deepcopy(cell.traffic)
    span = base["block"] / base["arrivals"]["rate_per_s"]
    for rate in (float(x) for x in args.rates.split(",")):
        cell.traffic = copy.deepcopy(base)
        cell.traffic["arrivals"]["rate_per_s"] = rate
        cell.traffic["block"] = max(1, round(rate * span))
        out = harness.measure(harness.set_up(cell, args.seed), cell,
                              args.seed, args.seconds, False,
                              time.perf_counter(), devices, peak)
        rec = out.record
        waits = [r.admitted - r.due for r in rec.attempted
                 if r.admitted == r.admitted]
        row = {"rate_per_s": rate, "attempted": len(rec.attempted),
               "offered_tokens_per_s": sum(r.max_new for r in rec.attempted)
               / args.seconds,
               "queue_wait_p95_ms": 1e3 * harness.percentile(waits, 95),
               "metrics": harness.read_metrics(cell.end_to_end, rec),
               "correct": out.correct}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
