"""Distributed-path correctness: the sharded/shard_map code paths must
produce the same numbers as the single-device reference.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(a 2x4 (data, model) mesh) because jax locks the device count at first
init — the main test process must keep seeing 1 device.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.sharding import cache_spec_tree, param_spec_tree, to_shardings
from repro.sharding.constraints import activation_sharding

AXES, SHAPE = ("data", "model"), (2, 4)
mesh = jax.make_mesh(SHAPE, AXES,
                     axis_types=(jax.sharding.AxisType.Auto,) * len(AXES))

# a reduced config whose dims divide the mesh: heads 4 % 4 == 0 but
# kv heads 2 % 4 != 0 -> exercises the seq_mp + split-KV shard_map paths
cfg = dataclasses.replace(
    reduced(get_config("qwen3-1.7b")), dtype="float32",
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16, d_model=64)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
B, S = 4, 16
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": toks}

# ---- reference: single-logical-device ----
loss_ref, _ = model.loss_fn(params, batch, remat=False)
logits_ref, cache_ref = model.prefill(params, batch, max_len=S + 8)
nxt = jnp.argmax(logits_ref, -1).astype(jnp.int32)
dec_ref, _ = model.decode_step(params, cache_ref, nxt,
                               jnp.full((B,), S, jnp.int32))

# ---- sharded: pjit with specs + activation constraints ----
pspec = param_spec_tree(cfg, jax.eval_shape(lambda: params), AXES, SHAPE)
p_sh = jax.device_put(params, to_shardings(mesh, pspec))
bspec = {"tokens": P("data", None), "labels": P("data", None)}
b_sh = jax.device_put(batch, to_shardings(mesh, bspec))

with mesh, activation_sharding(mesh, AXES, SHAPE):
    loss_sh, _ = jax.jit(
        lambda p, b: model.loss_fn(p, b, remat=False))(p_sh, b_sh)
    prefill = jax.jit(lambda p, b: model.prefill(p, b, S + 8))
    logits_sh, cache_sh = prefill(p_sh, b_sh)
    cspec = cache_spec_tree(cfg, jax.eval_shape(lambda: cache_sh),
                            AXES, SHAPE)
    cache_sh = jax.device_put(cache_sh, to_shardings(mesh, cspec))
    dec_sh, _ = jax.jit(model.decode_step)(
        p_sh, cache_sh, nxt, jnp.full((B,), S, jnp.int32))

out = {
    "loss_err": float(abs(loss_ref - loss_sh)),
    "prefill_err": float(jnp.max(jnp.abs(logits_ref - logits_sh))),
    "decode_err": float(jnp.max(jnp.abs(dec_ref - dec_sh))),
    "n_devices": jax.device_count(),
}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("dummy", [0])
def test_sharded_paths_match_reference(dummy, tmp_path):
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout + proc.stderr[-2000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["n_devices"] == 8
    assert out["loss_err"] < 1e-4, out
    assert out["prefill_err"] < 1e-3, out
    assert out["decode_err"] < 1e-3, out
