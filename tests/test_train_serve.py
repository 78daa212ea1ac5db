"""Integration: training loop convergence, resume, serving engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config, reduced
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.serve import Engine, Request
from repro.train import train

from conftest import tiny


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ckpt")
    cfg = dataclasses.replace(reduced(get_config("qwen3-1.7b")), num_layers=2)
    shape = ShapeConfig("smoke", 64, 8, "train")
    tcfg = TrainConfig(total_steps=30, warmup_steps=5, checkpoint_every=10,
                       checkpoint_dir=str(tmp), learning_rate=1e-3)
    state, hist = train(cfg, shape, tcfg, log_every=0)
    return cfg, shape, tcfg, state, hist


def test_loss_decreases(trained):
    _, _, _, _, hist = trained
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first, f"loss did not decrease: {first:.3f} -> {last:.3f}"


def test_metrics_are_finite(trained):
    _, _, _, _, hist = trained
    for h in hist:
        assert np.isfinite(h["loss"]) and np.isfinite(h["grad_norm"])


def test_resume_continues_from_checkpoint(trained):
    cfg, shape, tcfg, _, _ = trained
    # rerun: should load step>=20 checkpoint and only run the tail
    _, hist2 = train(cfg, shape, tcfg, log_every=0)
    assert len(hist2) <= 10


def test_grad_accumulation_matches_full_batch():
    cfg = tiny("qwen3-1.7b", num_layers=2)
    shape = ShapeConfig("s", 32, 4, "train")
    from repro.data import SyntheticLM, make_data_config
    from repro.train.step import init_train_state, make_train_step

    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    data = SyntheticLM(make_data_config(cfg, shape))
    batch = data.batch(0)

    t_full = TrainConfig(microbatches=1, remat=False)
    t_micro = TrainConfig(microbatches=2, remat=False)
    s0 = init_train_state(model, rng)
    s1, m1 = jax.jit(make_train_step(model, t_full))(s0, batch)
    s0b = init_train_state(model, rng)
    s2, m2 = jax.jit(make_train_step(model, t_micro))(s0b, batch)
    # parameters after one step agree (accumulated grads == full grads)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-5)


def test_engine_matches_manual_greedy_decode():
    cfg = tiny("qwen3-1.7b", num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.arange(6, dtype=np.int32)

    eng = Engine(cfg, params, slots=2, max_len=32)
    out = eng.generate([Request(prompt, max_new_tokens=5, rid=0)])
    got = out[0].tokens

    # manual: prefill then greedy decode
    lp, cache = model.prefill(params, {"tokens": prompt[None]}, 32)
    tok = int(jnp.argmax(lp[0]))
    want = [tok]
    pos = len(prompt)
    for _ in range(4):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        pos += 1
        want.append(tok)
    assert got == want


def test_engine_continuous_batching_slots_recycle():
    cfg = tiny("qwen3-1.7b", num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, slots=2, max_len=32)
    reqs = [Request(np.arange(4) + i, max_new_tokens=3, rid=i)
            for i in range(5)]
    out = eng.generate(reqs)
    assert set(out) == set(range(5))
    for c in out.values():
        assert len(c.tokens) == 3


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "zamba2-1.2b",
                                  "rwkv6-1.6b", "deepseek-7b"])
def test_engine_across_families(arch):
    """Continuous-batching engine serves every block family (MoE+SWA,
    hybrid Mamba2, RWKV6, dense) with finite tokens and full budgets."""
    cfg = tiny(arch, num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, slots=2, max_len=48)
    reqs = [Request(np.arange(4, dtype=np.int32) + i, max_new_tokens=4,
                    rid=i) for i in range(3)]
    out = eng.generate(reqs)
    assert set(out) == {0, 1, 2}
    for c in out.values():
        assert len(c.tokens) == 4
        assert all(0 <= t < cfg.vocab_size for t in c.tokens)


def test_engine_swa_generation_crosses_window_boundary():
    """SWA rolling cache stays consistent when generation wraps past the
    window: engine tokens == manual prefill+decode reference."""
    cfg = tiny("mixtral-8x7b", num_layers=2, sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.arange(6, dtype=np.int32)
    n_new = 8  # 6 + 8 > window 8: wraps

    eng = Engine(cfg, params, slots=1, max_len=32)
    got = eng.generate([Request(prompt, max_new_tokens=n_new, rid=0)])[0].tokens

    lp, cache = model.prefill(params, {"tokens": prompt[None]}, 32)
    tok = int(jnp.argmax(lp[0]))
    want = [tok]
    pos = len(prompt)
    for _ in range(n_new - 1):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        pos += 1
        want.append(tok)
    assert got == want


@pytest.mark.parametrize("n,shape", [(1, (1, 1)), (2, (2, 1)), (4, (2, 2)),
                                     (8, (4, 2)), (256, (16, 16))])
def test_train_mesh_shape_from_device_count(n, shape):
    from repro.launch.train import mesh_shape
    assert mesh_shape(n) == shape


def test_train_launcher_refuses_offload_on_a_sharded_mesh():
    """Mosaic kernels cannot be partitioned automatically, so the
    launcher refuses --offload on a mesh of more than one device instead
    of running a different plan; one device is fine."""
    from types import SimpleNamespace

    from repro.launch.train import check_offload_mesh

    def trainer(offload, size):
        return SimpleNamespace(tcfg=SimpleNamespace(offload=offload),
                               mesh=SimpleNamespace(size=size),
                               mesh_shape=(size, 1))

    check_offload_mesh(trainer(True, 1))
    check_offload_mesh(trainer(False, 4))
    with pytest.raises(SystemExit, match="--offload"):
        check_offload_mesh(trainer(True, 4))
