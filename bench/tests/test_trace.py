"""The trace reduction on a small trace recorded on a TPU v5e by
``record_trace.py``: two tiny programs run three times each inside the
benchmark's host spans, between a 50 ms and a 20 ms wait."""
import pathlib

import pytest

from bench.trace import op_label, reduce_trace

SMALL = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return reduce_trace(SMALL)


def test_window_and_busy(small):
    # from the first wait's start to the sync's end: 50 + 20 ms of
    # waiting, three admits and steps, and the sync
    assert 0.070 < small.window_s < 0.080
    # six programs of tens of microseconds each
    assert 100e-6 < small.busy_s < 200e-6
    assert small.busy_s < small.window_s


def test_programs(small):
    assert small.module_count("small_admit") == 3
    assert small.module_count("small_step") == 3
    # every op ran inside one of the programs
    total = small.module_seconds("small_admit") + \
        small.module_seconds("small_step")
    assert small.busy_s <= total + 1e-9


def test_idle_by_span(small):
    idle = small.idle_by_span
    assert sum(idle.values()) == pytest.approx(
        small.window_s - small.busy_s, rel=1e-6)
    # the two waits are most of the idle time
    assert idle["bench.wait"] > 0.065
    assert max(idle, key=idle.get) == "bench.wait"


def test_breakdown(small):
    b = small.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["idle_gaps"][0][0] == "bench.wait"


def test_op_label():
    kernel = ('%closed_call.97 = bf16[24,8,2,128]{3,2,1,0:T(2,128)(2,1)S(1)}'
              ' custom-call(s32[24]{0:T(128)S(1)} %a), custom_call_target='
              '"tpu_custom_call"')
    assert op_label(kernel) == (
        "closed_call.97 custom-call(tpu) bf16[24,8,2,128]", False)
    loop = '%while.6 = (s32[]{:T(128)}, bf16[24,1,2048]{2,0,1}) while((s32[]'
    assert op_label(loop) == ("while.6 while s32[]", True)
    assert op_label("%multiply_add_fusion") == ("multiply_add_fusion", False)
