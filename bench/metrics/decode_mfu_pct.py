"""Model step, decode: operations the window's decode steps need (one
token per active row through every matmul and the LM head, attention
over each row's actual length) over the device time of the decode
programs (``step_impl``) in the trace, over the chip's peak."""

PROGRAM = "step_impl"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = t.module_seconds(PROGRAM)
    steps = [s for s in run.steps if 0 <= s.start < run.seconds]
    if secs <= 0 or not steps or t.module_count(PROGRAM) != len(steps):
        return None
    flops = sum(run.shapes.decode_flops(s.lengths) for s in steps)
    return 100.0 * flops / secs / run.peak["flops_per_s"]
