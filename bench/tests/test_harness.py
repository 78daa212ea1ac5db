"""The harness on the CPU: it refuses to measure anywhere but a known
TPU, and its check passes a sound run and fails the control and each
fault a serving cell can have, at the program's reduced size."""
import json
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import pytest

import bench.harness as harness
from bench.tests.small import SMALL_QWEN, small_cell

ROOT = harness.ROOT
# between the widest served gap of sound runs at this size (at most
# 0.0011 over seeds 1, 2, 3 and 2**31 + 11) and the widest gap of the fp8
# control (at least 0.039 over the same seeds); the chip cells' limits
# are in bench/cells
SMALL_LIMIT = 0.02


def run_small(seed, control=False):
    return harness.run_cell(small_cell(SMALL_QWEN, limit=SMALL_LIMIT), seed,
                            1.0, False, time.perf_counter(), jax.devices(),
                            {}, control=control)


def test_cpu_is_refused():
    with pytest.raises(harness.DeviceError, match="needs a TPU"):
        harness.check_devices(1)


def test_unknown_kind_is_refused(monkeypatch):
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(harness.DeviceError, match="not in bench/peaks.json"):
        harness.check_devices(1)
    monkeypatch.setattr(jax, "devices", lambda: [
        SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    with pytest.raises(harness.DeviceError, match="needs 4 chips"):
        harness.check_devices(4)


def test_run_on_cpu_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.chat",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_sound_run_is_correct():
    out = run_small(2**31 + 11)
    assert out.correct, out.checks
    assert out.failed == 0
    assert out.checks["max_logit_gap"][0] <= SMALL_LIMIT
    assert out.gaps["tokens"] > 0
    assert out.record.window_tokens > 0
    assert any("compiles in the window: 0;" in n for n in out.notes)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails(seed):
    """The reference in fp8 (e4m3 mantissas on every linear layer's
    operands), read at the served positions and judged by the same
    checks, comes out not correct where the program's bf16 outputs
    keep the limit."""
    out = run_small(seed, control=True)
    assert out.correct, out.checks
    assert out.checks["max_logit_gap"][0] <= SMALL_LIMIT
    assert out.control_correct is False
    assert out.control_checks["max_logit_gap"][0] > SMALL_LIMIT
    assert out.control_checks["max_logit_gap"][1] == SMALL_LIMIT


def test_altered_token_fails(monkeypatch):
    """A token altered where it is produced: the first row of every step
    comes back one id off."""
    from repro.serve import Engine

    step = Engine.step

    def altered(self):
        out = step(self)
        if out:
            rid, tok = out[0]
            out[0] = (rid, (tok + 1) % self.cfg.vocab_size)
        return out

    monkeypatch.setattr(Engine, "step", altered)
    out = run_small(2**31 + 11)
    assert not out.correct
    assert out.checks["max_logit_gap"][0] > SMALL_LIMIT


def test_unchanged_state_fails(monkeypatch):
    """A decode step that returns its K/V state unchanged: no new token's
    keys and values reach the pages."""
    import repro.models.attention as attention

    monkeypatch.setattr(attention, "write_kv_page_entries",
                        lambda pages, new, ids, offs: pages)
    out = run_small(2**31 + 11)
    assert not out.correct
    assert out.checks["max_logit_gap"][0] > SMALL_LIMIT


def test_itl_p50_reads_every_gap_of_the_window_requests():
    """Gaps of requests due in the window only, pooled across requests;
    a request with one token (or none) adds no gap."""
    reader = harness.metric_reader("itl_p50_ms")
    req = SimpleNamespace
    run = SimpleNamespace(attempted=[
        req(times=[1.0, 1.1, 1.2, 1.5]),        # 0.1, 0.1, 0.3
        req(times=[2.0, 2.2]),                  # 0.2
        req(times=[3.0]), req(times=[])])
    assert reader(run) == pytest.approx(100.0)  # nearest-rank median
    assert reader(SimpleNamespace(attempted=[req(times=[1.0])])) is None
