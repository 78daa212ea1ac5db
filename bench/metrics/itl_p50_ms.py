"""Median, over every gap between consecutive tokens of every request due
in the window, of the host time between the two tokens coming back.  A
request that failed has no gaps; the run's check counts it."""
from bench.harness import percentile


def read(run):
    gaps = [b - a for r in run.attempted for a, b in zip(r.times, r.times[1:])]
    return 1e3 * percentile(gaps, 50) if gaps else None
