#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a workload of ``BENCHMARK.json``) names its configuration and
traffic mix; everything else is found by name under ``bench/``.  The run
builds the paged, offloaded ``Engine`` over weights made from the seed,
warms the cell's shapes (set-up), offers the traffic for ``--seconds``,
drains the requests that were due, checks the served tokens against the
plain float32 reference, and prints:

- earlier lines on standard output: what the run saw (decode plan,
  engine and kernel-guard counters, generator lag, compiles inside the
  window, memory);
- each number compared with its limit, as the last lines on standard
  error;
- one JSON object as the last line on standard output: ``correct``,
  ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
  or with ``--trace 1`` its per-layer metrics), ``device``, with
  ``--trace 1`` a ``breakdown``, and the compared numbers under
  ``checks``, last.

It exits non-zero, printing no result, unless JAX runs on enough TPU
chips of a kind listed in ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(outcome, cell, trace: bool, devices) -> dict:
    from bench.harness import read_metrics

    rec = outcome.record
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    out = {"correct": outcome.correct, "attempted": len(rec.attempted),
           "failed": outcome.failed,
           "metrics": read_metrics(cell.per_layer if trace
                                   else cell.end_to_end, rec),
           "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = rec.trace.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in outcome.checks.items()}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench.harness import DeviceError, check_devices, load_cell, run_cell

    cell = load_cell(args.workload)
    try:
        devices, peak = check_devices(cell.chips)
    except DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache

    import jax

    print(f"compile_cache: {setup_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    outcome = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, devices, peak)
    for note in outcome.notes:
        print(note)
    line = result_line(outcome, cell, bool(args.trace), devices)
    for k, (v, lim) in outcome.checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
