"""Kernels: the least time the paged attention of the window's decode
steps could take on the chip (per step the larger of its operations /
peak FLOP/s and its bytes / HBM bandwidth: K/V read over each row's
attended length, q in and the output out) over the device time of the
``paged_decode_attention`` Pallas calls.  A program whose kernels carry
no name gives nothing to read."""
from bench.scopes import pallas_seconds

PROGRAM = "step_impl"
KERNEL = "paged_decode_attention"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = pallas_seconds(t.ops, {KERNEL})
    steps = [s for s in run.steps if 0 <= s.start < run.seconds]
    if secs <= 0 or not steps or t.module_count(PROGRAM) != len(steps):
        return None
    p = run.peak
    least = sum(max(run.shapes.paged_attention_flops(s.lengths)
                    / p["flops_per_s"],
                    run.shapes.paged_attention_bytes(s.lengths)
                    / p["hbm_bytes_per_s"])
                for s in steps)
    return 100.0 * least / secs
