"""moonshot-v1-16b-a3b (Moonlight-16B-A3B) — DeepSeek-V3 block: latent
attention and a fine-grained MoE with shared experts.

27L d_model=2048 16H vocab=163840, untied head.  MLA without a query
low-rank path: kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128.  The
first layer is dense (SwiGLU 11264); the other 26 route over 64 experts
of width 1408, 6 per token (sigmoid scores, noaux_tc correction bias,
normalised top-k weights scaled by 2.446), plus 2 shared experts.
RMSNorm eps 1e-5, rope_theta 50000, context 8192
[hf:moonshotai/Moonlight-16B-A3B config.json].
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=50000.0,
    norm_eps=1e-5,
    mla=MLAConfig(kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128),
    first_dense=1,
    moe=MoEConfig(num_experts=64, top_k=6, capacity_factor=0.0,
                  d_expert=1408, num_shared=2, scoring="sigmoid",
                  score_bias=True, routed_scale=2.446),
    source="hf:moonshotai/Moonlight-16B-A3B",
)
