"""The plain reference against the program's model at the program's
reduced size, in float32, for both families of weights layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import program_config
from bench.reference.dense import Reference, round_mantissa
from bench.tests.small import SMALL_DEEPSEEK, SMALL_QWEN, small_cell
from bench.weights import make_params


@pytest.mark.parametrize("config", [SMALL_QWEN, SMALL_DEEPSEEK],
                         ids=["qwen3", "deepseek"])
def test_reference_matches_program_float32(config):
    from repro.models import build_model
    from repro.models.layers import lm_head_apply

    cell = small_cell(config, dtype="float32")
    pcfg = program_config(cell.config)
    model = build_model(pcfg)
    params = make_params(model, 7)
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        h, _, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
        want = np.asarray(lm_head_apply(params["embed"], h, pcfg.vocab_size))[0]
    ref = Reference(params, cell.config, max_len=64, max_new=40)
    got = ref.logits(tokens, np.arange(40))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_reference_reads_every_leaf():
    """A norm scale or a weight the reference did not read would leave
    its logits unmoved when that leaf changes (by a random amount per
    entry: a uniform scale of wq or wk is undone by qk_norm)."""
    from repro.models import build_model

    cell = small_cell(SMALL_QWEN, dtype="float32")
    model = build_model(program_config(cell.config))
    params = make_params(model, 3)
    tokens = np.arange(1, 33, dtype=np.int32)
    base = Reference(params, cell.config, max_len=64, max_new=32).logits(
        tokens, np.arange(32))
    flat, treedef = jax.tree_util.tree_flatten(params)
    for i in range(len(flat)):
        bumped = list(flat)
        noise = jax.random.normal(jax.random.PRNGKey(i), flat[i].shape)
        bumped[i] = flat[i] * (1.0 + 0.5 * noise)
        p = jax.tree_util.tree_unflatten(treedef, bumped)
        got = Reference(p, cell.config, max_len=64, max_new=32).logits(
            tokens, np.arange(32))
        assert np.abs(got - base).max() > 1e-6, f"leaf {i} is not read"


def test_round_mantissa():
    x = jnp.asarray([1.0, 1.0625, 1.125, 1.1875, -3.3, 1e-3], jnp.float32)
    y = np.asarray(round_mantissa(x, 3))
    # 3 mantissa bits: steps of 1/8 between 1 and 2; ties to even
    assert y[0] == 1.0 and y[1] == 1.0 and y[2] == 1.125 and y[3] == 1.25
    assert abs(y[4] + 3.25) < 1e-6
    assert abs(y[5] - 1e-3) / 1e-3 < 1 / 16
