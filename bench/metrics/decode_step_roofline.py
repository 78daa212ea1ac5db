"""Model step, decode: the least time the window's decode steps could
take on the chip (per step the larger of operations / peak FLOP/s and
bytes / HBM bandwidth; weights at the configuration's dtype, K/V over
actual lengths) over the device time of the decode programs
(``step_impl``).  Decode is bound by bytes at these sizes."""

PROGRAM = "step_impl"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = t.module_seconds(PROGRAM)
    steps = [s for s in run.steps if 0 <= s.start < run.seconds]
    if secs <= 0 or not steps or t.module_count(PROGRAM) != len(steps):
        return None
    p = run.peak
    least = sum(max(run.shapes.decode_flops(s.lengths) / p["flops_per_s"],
                    run.shapes.decode_bytes(s.lengths) / p["hbm_bytes_per_s"])
                for s in steps)
    return 100.0 * least / secs
