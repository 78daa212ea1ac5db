"""Serving engine: 95th percentile of due -> admit() accepted, over the
requests admitted inside the window (host clock)."""
from bench.harness import percentile


def read(run):
    waits = [r.admitted - r.due for r in run.requests
             if 0 <= r.admitted < run.seconds]
    return 1e3 * percentile(waits, 95) if waits else None
