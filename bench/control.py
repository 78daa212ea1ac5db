#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --seconds <s>

One process sets the cell up for each seed in turn (the programs load
from the compile cache after the first), serves that seed's weights and
traffic for a short window, and reads the widest logit gap of the served tokens against the
plain reference (the lower reading: the largest over the seeds).  For
the control seeds it also reads the gap of the token that the reference
computed with fp8 operands puts first (the upper reading: the smallest
over those seeds), and judges those gaps by the cell's own checks and
limits, as a run judges the served ones: the control has to come out
not correct.  Prints one JSON line per seed and a summary line.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    seeds = [int(x) for x in args.seeds.split(",")]
    ctl = {int(x) for x in args.control_seeds.split(",") if x}
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
    served, control, verdicts = [], [], []
    for i, seed in enumerate(seeds):
        t = T_START if i == 0 else time.perf_counter()
        out = harness.measure(harness.set_up(cell, seed), cell, seed,
                              args.seconds, False, t, devices, peak,
                              control=seed in ctl)
        gap = out.checks["max_logit_gap"][0]
        served.append(gap)
        row = {"seed": seed, "correct": out.correct, "served_gap": gap,
               "served_gaps": out.gaps["served"],
               "tokens": out.gaps["tokens"],
               "requests_not_ok": out.checks["requests_not_ok"][0],
               "attempted": len(out.record.attempted),
               "metrics": harness.read_metrics(cell.end_to_end, out.record),
               "seconds": time.perf_counter() - t}
        if seed in ctl:
            row["control_gap"] = out.control_checks["max_logit_gap"][0]
            row["control_gaps"] = out.gaps["control"]
            row["control_correct"] = out.control_correct
            row["control_checks"] = {
                k: {"value": v, "limit": lim}
                for k, (v, lim) in out.control_checks.items()}
            control.append(row["control_gap"])
            verdicts.append(out.control_correct)
        print(json.dumps(row), flush=True)
        del out
        gc.collect()
    print(json.dumps({"lower_reading": max(served),
                      "upper_reading": min(control) if control else None,
                      "seeds": len(seeds), "control_seeds": len(control),
                      "control_correct": verdicts}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
