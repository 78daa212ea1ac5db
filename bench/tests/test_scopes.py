"""The scope reading (``bench/scopes.py``) on two chip traces recorded
on a TPU v5e: ``named.xplane.pb`` (``record_named_trace.py``: a named
Pallas kernel and a scoped far op, run three times inside the engine's
span tree with a 10 ms host sleep in ``engine.prepare``) and
``small.xplane.pb`` (``record_trace.py``: no scopes, no engine spans)."""
import pathlib

import pytest

from bench.scopes import (
    NEAR_KERNELS,
    SCAN,
    UNSCOPED,
    engine_host_ms,
    idle_intervals,
    innermost,
    kernel_of,
    layer_of,
    pallas_seconds,
    scope_path,
    scoped,
    split_idle,
)
from bench.trace import reduce_trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
NAMED = DATA / "named.xplane.pb"
SMALL = DATA / "small.xplane.pb"


@pytest.fixture(scope="module")
def named():
    return scoped(NAMED)


@pytest.mark.parametrize("path", [NAMED, SMALL])
def test_idle_split_shares_out_the_trace_idle(path):
    """Over bench/trace.py's window, the split by innermost span shares
    out exactly the idle time that reduction finds."""
    new, old = scoped(path), reduce_trace(path)
    assert sum(new.idle.values()) == pytest.approx(
        old.window_s - old.busy_s, rel=1e-6)


def test_scope_attribution(named):
    """The kernel's device time goes to its name under decode/mlp/near;
    the far matmul to decode/mlp; only the compiler's copies go
    unscoped."""
    by_scope = named.scopes["jit_named_step"]
    kernel = by_scope["decode/mlp/near/fused_elementwise/fused_elementwise"]
    assert 5e-6 < kernel < 1e-4                   # three 1024 x 1024 passes
    assert named.by_kernel("named_step")["fused_elementwise"] == kernel
    assert named.scope_seconds("named_step", "near") == kernel
    assert named.scope_seconds("named_step", "near/fused_elementwise") \
        == kernel
    assert by_scope["decode/mlp"] > kernel        # the tanh(x @ x) fusion
    assert by_scope.get(UNSCOPED, 0.0) < 1e-6
    assert max(named.by_layer("named_step"),
               key=named.by_layer("named_step").get) == "mlp"
    top = named.breakdown()["device_ops"]
    assert top[0][0].startswith("decode/mlp ")
    assert any(n.startswith("fused_elementwise fused_elementwise")
               and "custom-call(tpu)" in n for n, _ in top)


def test_pallas_seconds_reads_the_trace_labels(named):
    """bench/trace.py's op labels lead with the Pallas call's name: the
    kernel's seconds there are the seconds under its scope; a trace
    whose kernels carry no name has none."""
    ops = reduce_trace(NAMED).ops
    kernel = named.by_kernel("named_step")["fused_elementwise"]
    assert pallas_seconds(ops, {"fused_elementwise"}) == pytest.approx(
        kernel, rel=1e-9)
    assert pallas_seconds(ops, NEAR_KERNELS) == 0.0
    assert pallas_seconds(reduce_trace(SMALL).ops, {"closed_call"}) == 0.0


def test_idle_goes_to_the_innermost_span(named):
    """The 10 ms sleeps land in engine.prepare, not in bench.step,
    engine.step or host."""
    idle = named.idle
    assert idle["engine.prepare"] > 0.95 * 3 * 0.010
    assert idle["engine.prepare"] < 3 * 0.012
    for outer in ("bench.step", "engine.step", "host"):
        assert idle.get(outer, 0.0) < 0.001, outer
    assert idle["bench.wait"] > 0.065             # the 50 and 20 ms waits


def test_engine_steps(named):
    """Three decode steps, no admit: the host time per step is the
    sleep and the launch, the sync's wait left out."""
    assert len(named.steps) == 3
    assert not any(s.after_admit for s in named.steps)
    assert all(0 < s.sync_s < s.dur_s for s in named.steps)
    assert 10.0 <= engine_host_ms(named.steps) < 14.0


def test_small_trace_without_names():
    s = scoped(SMALL)
    assert s.steps == [] and engine_host_ms(s.steps) is None
    assert set(s.scopes) == {"jit_small_admit", "jit_small_step"}
    assert all(set(v) == {UNSCOPED} for v in s.scopes.values())
    assert max(s.idle, key=s.idle.get) == "bench.wait"


@pytest.mark.parametrize("tf_op,path", [
    ("jit(step_impl)/jit(flat_runner)/decode/while/body/closed_call/attn/"
     "paged_attention/paged_decode_attention/paged_decode_attention/"
     "pallas_call:", ("decode", "attn", "paged_attention",
                      "paged_decode_attention", "paged_decode_attention")),
    ("jit(step_impl)/jit(flat_runner)/decode/while/body/dynamic_slice:",
     ("decode", SCAN)),
    ("jit(step_impl)/jit(flat_runner)/decode/while/body/closed_call/attn/"
     "kv_write/scatter:", ("decode", "attn", "kv_write")),
    ("jit(step_impl)/decode/attn/out_proj/near/fused_matmul/reshape;"
     "attn/out_proj/reshape:", ("decode", "attn", "out_proj", "near",
                                "fused_matmul")),
    ("jit(step_impl)/reduce:", ()),
    ("", ()),
])
def test_scope_path(tf_op, path):
    assert scope_path(tf_op) == path


def test_kernel_and_layer():
    path = ("decode", "mlp", "near", "fused_matmul", "fused_matmul")
    assert kernel_of(path) == "fused_matmul" and layer_of(path) == "mlp"
    assert kernel_of(("decode", "attn", "rope")) is None
    assert layer_of(("decode", SCAN)) == SCAN
    assert layer_of(()) == UNSCOPED


def test_innermost_split():
    """Nested spans: each idle piece goes to the innermost open span."""
    spans = [(0, 100, "a"), (10, 50, "b"), (20, 30, "c"), (60, 70, "d")]
    pieces = innermost(spans, -10, 110)
    assert [p[2] for p in pieces] == ["host", "a", "b", "c", "b", "a", "d",
                                      "a", "host"]
    idle = split_idle([(-5, 25), (45, 65), (95, 105)], pieces)
    ns = {k: round(v * 1e9) for k, v in idle.items()}
    assert ns == {"host": 10, "a": 25, "b": 15, "c": 5, "d": 5}


def test_idle_intervals():
    """Gaps between overlapping op intervals, clipped to the window."""
    assert idle_intervals([5, 10, 12, 30], [11, 20, 15, 40], 0, 50) == [
        (0, 5), (20, 30), (40, 50)]
    assert idle_intervals([-5], [60], 0, 50) == []
    assert idle_intervals([], [], 0, 50) == [(0, 50)]
