"""Kernels: the least time the latent attention of the window's decode
steps could take on the chip (per step the larger of its operations /
peak FLOP/s and its bytes / HBM bandwidth, ``bench/mla_moe_shapes.py``)
over the device time of the ``paged_mla_decode`` Pallas calls.  Nothing
to read in a cell without them."""
from bench import harness
from bench.mla_moe_shapes import MlaMoeShapes
from bench.scopes import pallas_seconds

PROGRAM = "step_impl"
KERNEL = "paged_mla_decode"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = pallas_seconds(t.ops, {KERNEL})
    steps = [s for s in run.steps if 0 <= s.start < run.seconds]
    if secs <= 0 or not steps or t.module_count(PROGRAM) != len(steps):
        return None
    shapes = MlaMoeShapes.from_config(harness.load_cell(run.cell).config)
    p = run.peak
    least = sum(max(shapes.mla_flops(s.lengths) / p["flops_per_s"],
                    shapes.mla_bytes(s.lengths) / p["hbm_bytes_per_s"])
                for s in steps)
    return 100.0 * least / secs
