"""The latent-attention MoE family (Moonlight) at a small CPU size: the
plain reference against the program in float32, every leaf read, the
harness's check on a served run and its fp8 control, and the two
kernel roofline readers over trace summaries."""
import copy
import json
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.harness import program_config
from bench.mla_moe_shapes import MlaMoeShapes
from bench.reference.mla_moe import Reference
from bench.tests.small import TRAFFIC
from bench.trace import TraceSummary
from bench.weights import make_params

CELL = "moonlight-16b-a3b.decode-batch"
FULL = json.loads((harness.BENCH / "configs" /
                   "moonlight-16b-a3b.json").read_text())
# the published block at a width the CPU runs in seconds: hidden 64,
# 4 heads, one dense layer and two MoE layers, a 256-token vocabulary;
# the latent, rope, value and expert widths and the router stay whole
SMALL = {**FULL, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 4, "vocab_size": 256}
# between the widest served gap of sound runs at this size over the
# well-routed positions (at most 0.017 over seeds 1, 2, 3 and 2**31 +
# 11) and the narrowest of the fp8 control (at least 0.40 over the same
# seeds)
SMALL_LIMIT = 0.1
PEAK = json.loads((harness.BENCH / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def _model_and_params(seed, dtype="float32"):
    from repro.models import build_model

    config = {**SMALL, "torch_dtype": dtype}
    model = build_model(program_config(config))
    params = make_params(model, seed)

    def leaf(path, a):      # the benchmark draws the bias as zero
        if jax.tree_util.keystr(path).endswith("['router_bias']"):
            return 0.1 * jax.random.normal(jax.random.PRNGKey(seed),
                                           a.shape)
        return a
    return config, model, jax.tree_util.tree_map_with_path(leaf, params)


def test_reference_matches_program_float32():
    from repro.models.layers import lm_head_apply

    config, model, params = _model_and_params(7)
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        h, _, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
        want = np.asarray(lm_head_apply(params["embed"], h, 256))[0]
    got = Reference(params, config, max_len=64, max_new=40).logits(
        tokens, np.arange(40))
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_reference_reads_every_leaf():
    """Each leaf, changed by a random amount per entry, moves the
    reference's logits."""
    config, _, params = _model_and_params(3)
    tokens = np.arange(1, 33, dtype=np.int32)
    base = Reference(params, config, max_len=64, max_new=32).logits(
        tokens, np.arange(32))
    flat, treedef = jax.tree_util.tree_flatten(params)
    for i in range(len(flat)):
        bumped = list(flat)
        noise = jax.random.normal(jax.random.PRNGKey(i), flat[i].shape)
        bumped[i] = flat[i] * (1.0 + 0.5 * noise)
        p = jax.tree_util.tree_unflatten(treedef, bumped)
        got = Reference(p, config, max_len=64, max_new=32).logits(
            tokens, np.arange(32))
        assert np.abs(got - base).max() > 1e-6, f"leaf {i} is not read"


@pytest.mark.parametrize("tie,kept", [(False, 1.0), (True, 0.0)])
def test_gaps_read_only_well_routed_positions(tie, kept):
    """A correction bias far apart for the first six experts leaves
    every position's routing with room; two experts of one router column
    and one bias, below five others, tie for sixth place at every
    position, so none is read."""
    config, _, params = _model_and_params(5)
    bias = jnp.where(jnp.arange(64) < 6, 10.0, 0.0)
    ffn = params["decoder"]["stack"]["0"]["ffn"]
    router = ffn["router"]
    if tie:
        bias = jnp.where(jnp.arange(64) < 5, 20.0, bias).at[6].set(10.0)
        router = router.at[:, :, 6].set(router[:, :, 5])
    p = copy.deepcopy(params)
    p["decoder"]["stack"]["0"]["ffn"] = {
        **ffn, "router": router,
        "router_bias": jnp.broadcast_to(bias, ffn["router_bias"].shape)}
    prompt = np.arange(1, 25, dtype=np.int32)
    served = np.arange(30, 38, dtype=np.int32)
    g = Reference(p, config, max_len=64, max_new=8).gaps(prompt, served,
                                                         control=True)
    assert g["kept"] == kept
    if not tie:
        assert g["control"].max() > 0.0
    else:
        assert g["served"].max() == 0.0 and g["control"].max() == 0.0


def test_served_run_is_correct_and_control_is_not():
    cell = harness.Cell(
        name="small", chips=1, config=copy.deepcopy(SMALL),
        traffic=copy.deepcopy(TRAFFIC),
        sizes={"slots": 4, "max_len": 80,
               "check": {"sample_requests": 4, "max_logit_gap": SMALL_LIMIT}},
        end_to_end=[], per_layer=[])
    out = harness.run_cell(cell, 1, 1.0, False, time.perf_counter(),
                           jax.devices(), {}, control=True)
    assert out.correct, out.checks
    assert out.failed == 0 and out.gaps["tokens"] > 0
    assert out.control_correct is False
    assert out.control_checks["max_logit_gap"][0] > SMALL_LIMIT
    routed = [n for n in out.notes if "after the drain" in n]
    assert routed and "'moe_routed': [" in routed[0]


SHAPES = MlaMoeShapes.from_config(FULL)
LENGTHS = [[1100, 2300, 700] + [900] * 61, [1101, 2301, 701] + [901] * 61]
NAMED = {
    "paged_mla_decode.4 custom-call(tpu) bf16[64,16,512]": 0.02,
    "moe_experts.7 custom-call(tpu) bf16[512,2048]": 0.004,
    "fused_matmul.5 custom-call(tpu) bf16[64,2048]": 0.003,
    "paged_decode_attention.6 custom-call(tpu) bf16[24,8,2,128]": 0.5,
    "fusion.3 fusion bf16[64,2048]": 0.1,
}
UNNAMED = {"closed_call.9 custom-call(tpu) bf16[64,16,512]": 0.02,
           "closed_call.10 custom-call(tpu) bf16[64,2048]": 0.003}


def _run(ops, runs=2, steps=LENGTHS):
    trace = TraceSummary(window_s=1.0, busy_s=0.9,
                         modules={"jit_step_impl": 0.2},
                         module_runs={"jit_step_impl": runs}, ops=ops)
    return SimpleNamespace(
        cell=CELL, trace=trace, peak=PEAK, seconds=1.0,
        admits=[(0.05, 1000), (1.2, 700)],      # one inside the window
        steps=[SimpleNamespace(start=0.1 * i, lengths=n)
               for i, n in enumerate(steps)] + [
            SimpleNamespace(start=1.5, lengths=[5])])   # after the window


def test_mla_attention_roofline():
    """Latent rows over the attended lengths at HBM bandwidth (bytes
    bound) over the kernel's own seconds."""
    least = sum(SHAPES.mla_bytes(n) for n in LENGTHS) / PEAK["hbm_bytes_per_s"]
    assert least > sum(SHAPES.mla_flops(n) for n in LENGTHS) \
        / PEAK["flops_per_s"]
    assert SHAPES.mla_bytes([1]) == 9 * (1152 + 16 * 1088 * 2)
    got = harness.metric_reader("mla_attention_roofline")(_run(NAMED))
    assert got == pytest.approx(100.0 * least / 0.02)
    assert 0.0 < got < 100.0


def test_near_kernel_ms_moonlight():
    """The offload planner's kernels per window decode step (two here),
    read with ``near_kernel_ms``'s reader; the model's own kernels are
    left out."""
    got = harness.metric_reader("near_kernel_ms.moonlight")(_run(NAMED))
    assert got == pytest.approx(1e3 * 0.003 / 2)


@pytest.mark.parametrize("name", ["mla_attention_roofline",
                                  "near_kernel_ms.moonlight"])
def test_nothing_to_read(name):
    """No trace, kernels without names, or a traced step program the
    window's steps do not account for: the metric is left out."""
    read = harness.metric_reader(name)
    assert read(SimpleNamespace(trace=None)) is None
    assert read(_run(UNNAMED)) is None
    assert read(_run(NAMED, runs=3)) is None
    assert read(_run(NAMED, runs=0, steps=[])) is None
