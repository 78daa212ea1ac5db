"""The operation and byte counts against hand counts of both
configurations (from their published shapes)."""
import json
import pathlib

import pytest

from bench.flops import Shapes

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def shapes(name):
    return Shapes.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen3_params_by_hand():
    s = shapes("qwen3-1.7b")
    # q 2048x2048, k and v 2048x1024, o 2048x2048; gate, up, down 2048x6144
    attn = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
    mlp = 3 * 2048 * 6144
    assert s.layer_matmul_params == attn + mlp == 50_331_648
    # two norms of 2048 and the q/k norms of 128
    assert s.layer_params == 50_331_648 + 4096 + 256
    # 28 layers, the final norm, one tied 151936 x 2048 table
    assert s.total_params == 28 * 50_336_000 + 2048 + 151936 * 2048 \
        == 1_720_574_976
    # K and V, 8 heads x 128, bf16, 28 layers: 112 KiB a token
    assert s.kv_bytes_per_token == 112 * 1024


def test_deepseek_params_by_hand():
    s = shapes("deepseek-7b")
    attn = 4 * 4096 * 4096
    mlp = 3 * 4096 * 11008
    assert s.layer_matmul_params == attn + mlp == 202_375_168
    assert s.layer_params == 202_375_168 + 2 * 4096
    # 6 of 30 layers, the final norm, an untied embedding and head
    assert s.total_params == 6 * 202_383_360 + 4096 + 2 * 102400 * 4096
    # the whole published model is DeepSeek-LLM-7B's 6.9B
    assert 30 * 202_383_360 + 4096 + 2 * 102400 * 4096 == 6_910_365_696
    # K and V, 32 heads x 128, bf16, 6 layers: 96 KiB a token
    assert s.kv_bytes_per_token == 96 * 1024


@pytest.mark.parametrize("name", ["qwen3-1.7b", "deepseek-7b"])
def test_decode_counts(name):
    s = shapes(name)
    lengths = [100, 300]
    per_row = 2 * (s.layers * s.layer_matmul_params + s.vocab * s.d)
    attn = s.layers * 4 * s.nq * s.hd * (100 + 300)
    assert s.decode_flops(lengths) == 2 * per_row + attn
    weights = (s.layers * s.layer_params + s.d + s.vocab * s.d) * 2
    embed_rows = 0 if s.tied else 2 * s.d * 2
    kv = (400 + 2) * s.kv_bytes_per_token
    assert s.decode_bytes(lengths) == weights + embed_rows + kv
    assert s.paged_attention_flops(lengths) == attn


@pytest.mark.parametrize("name", ["qwen3-1.7b", "deepseek-7b"])
def test_prefill_counts(name):
    s = shapes(name)
    n = 1000
    expect = (2 * n * s.layers * s.layer_matmul_params
              + s.layers * 4 * s.nq * s.hd * (n * (n + 1) // 2)
              + 2 * s.vocab * s.d)
    assert s.prefill_flops(n) == expect
    # one token's prefill is a decode step at length 1 without K/V reads
    assert s.prefill_flops(1) == s.decode_flops([1])
