"""Serving engine: median host time of the Engine.step() calls started
inside the window that decoded with no admit since the previous step
(each ends in the step's own host sync)."""
import statistics


def read(run):
    t = [s.end - s.start for s in run.steps
         if 0 <= s.start < run.seconds and not s.after_admit]
    return 1e3 * statistics.median(t) if t else None
