"""Model step, prefill on admit: operations the real prompt tokens of
the window's admits need, over the device time of the admit programs
(``admit_impl``) in the trace, over the chip's peak.  Pow2 padding of
the prompt shows as a lower share."""

PROGRAM = "admit_impl"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = t.module_seconds(PROGRAM)
    admits = [n for at, n in run.admits if 0 <= at < run.seconds]
    if secs <= 0 or not admits or t.module_count(PROGRAM) != len(admits):
        return None
    flops = sum(run.shapes.prefill_flops(n) for n in admits)
    return 100.0 * flops / secs / run.peak["flops_per_s"]
