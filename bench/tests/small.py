"""A cell at the program's reduced CPU size, for tests: the same harness,
reference and checks as a chip cell, with a few short requests."""
from __future__ import annotations

import copy

from bench.harness import Cell

SMALL_QWEN = {
    "arch": "qwen3-1.7b", "family": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16", "qk_norm": True,
}
SMALL_DEEPSEEK = {**SMALL_QWEN, "arch": "deepseek-7b",
                  "num_key_value_heads": 4, "head_dim": None,
                  "rope_theta": 10000.0, "tie_word_embeddings": False,
                  "qk_norm": False}
TRAFFIC = {
    "arrivals": {"kind": "poisson", "rate_per_s": 40.0},
    "preroll_s": 0.4,
    "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                   "min": 8, "max": 48},
    "output_len": {"dist": "uniform", "min": 8, "max": 24},
    "block": 8,
}


def small_cell(config=SMALL_QWEN, *, limit: float = 0.05,
               dtype: str = "bfloat16") -> Cell:
    config = {**copy.deepcopy(config), "torch_dtype": dtype}
    if config["head_dim"] is None:
        del config["head_dim"]
    return Cell(name="small", chips=1, config=config,
                traffic=copy.deepcopy(TRAFFIC),
                sizes={"slots": 4, "max_len": 80,
                       "check": {"sample_requests": 4,
                                 "max_logit_gap": limit}},
                end_to_end=[], per_layer=[])
