"""The readers of the named kernels' metrics (``paged_attention_roofline``,
``near_kernel_ms``) over a trace summary whose op labels lead with the
Pallas calls' names, as ``bench/trace.py`` gives them for this program,
and over one whose kernels carry no name, as for a program without
``name=`` on its ``pallas_call``s."""
import json
from types import SimpleNamespace

import pytest

from bench import harness
from bench.flops import Shapes
from bench.trace import TraceSummary

SHAPES = Shapes.from_config(json.loads(
    (harness.BENCH / "configs" / "qwen3-1.7b.json").read_text()))
PEAK = json.loads((harness.BENCH / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]
LENGTHS = [[100, 200, 300], [101, 201, 301]]
NAMED = {
    "paged_decode_attention.6 custom-call(tpu) bf16[24,8,2,128]": 0.004,
    "fused_matmul.47 custom-call(tpu) bf16[24,6144]": 0.003,
    "fused_matmul_dlhs.2 custom-call(tpu) bf16[24,2048]": 0.0005,
    "fused_segment_grid.44 custom-call(tpu) bf16[24,2048]": 0.0005,
    "flash_attention.3 custom-call(tpu) bf16[1,8,2,512,128]": 0.25,
    "copy.86 copy bf16[28,601,8,64,128]": 0.5,
}
UNNAMED = {
    "closed_call.97 custom-call(tpu) bf16[24,8,2,128]": 0.004,
    "closed_call.12 custom-call(tpu) bf16[24,6144]": 0.004,
    "copy.86 copy bf16[28,601,8,64,128]": 0.5,
}


def _run(ops, runs=2, steps=LENGTHS):
    trace = TraceSummary(window_s=1.0, busy_s=0.9,
                         modules={"jit_step_impl": 0.2},
                         module_runs={"jit_step_impl": runs}, ops=ops)
    return SimpleNamespace(
        trace=trace, shapes=SHAPES, peak=PEAK, seconds=1.0,
        steps=[SimpleNamespace(start=0.1 * i, lengths=n)
               for i, n in enumerate(steps)] + [
            SimpleNamespace(start=1.5, lengths=[5])])   # after the window


@pytest.mark.parametrize("name", ["paged_attention_roofline",
                                  "paged_attention_roofline.batch"])
def test_paged_attention_roofline(name):
    """K/V over the attended lengths at HBM bandwidth (bytes bound at
    these sizes) over the paged kernel's own seconds."""
    least = sum(SHAPES.paged_attention_bytes(n) for n in LENGTHS) \
        / PEAK["hbm_bytes_per_s"]
    assert least > sum(SHAPES.paged_attention_flops(n) for n in LENGTHS) \
        / PEAK["flops_per_s"]
    got = harness.metric_reader(name)(_run(NAMED))
    assert got == pytest.approx(100.0 * least / 0.004)
    assert 0.0 < got < 100.0


@pytest.mark.parametrize("name", ["near_kernel_ms", "near_kernel_ms.batch"])
def test_near_kernel_ms(name):
    """The planner's fused_matmul, _dlhs and fused_segment_grid calls,
    per window step; the prefill's flash kernel and XLA's ops aside."""
    got = harness.metric_reader(name)(_run(NAMED))
    assert got == pytest.approx(1e3 * (0.003 + 0.0005 + 0.0005) / 2)


@pytest.mark.parametrize("name", ["paged_attention_roofline",
                                  "near_kernel_ms"])
def test_nothing_to_read(name):
    """No trace, kernels without names, or a traced step program the
    window's steps do not account for: the metric is left out."""
    read = harness.metric_reader(name)
    assert read(SimpleNamespace(trace=None)) is None
    assert read(_run(UNNAMED)) is None
    assert read(_run(NAMED, runs=3)) is None
    assert read(_run(NAMED, runs=0, steps=[])) is None
