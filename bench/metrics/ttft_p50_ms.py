"""Median, over every request due in the window, of the time from the
request's due time to the host time its first token came back.  A
request that failed, or never answered, counts as waiting until the run
ended, beyond every answered one."""
from bench.harness import percentile


def read(run):
    waits = [(r.times[0] if r.times and r.status in ("", "ok")
              else run.end_s) - r.due for r in run.attempted]
    return 1e3 * percentile(waits, 50) if waits else None
