"""Configuration dataclasses for the repro framework.

Every assigned architecture is described by a ``ModelConfig``; every
workload shape by a ``ShapeConfig``.  Configs are plain frozen dataclasses
so they hash, compare, and print deterministically — they are used as
static args to jitted builders and as keys in the dry-run result table.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass, field
from typing import Literal, Sequence

from repro.core.policy import OffloadPolicy

BlockKind = Literal["attention", "mamba2", "rwkv6", "shared_attention"]
ModelKind = Literal["decoder", "encoder_decoder"]
Frontend = Literal["none", "audio", "vision"]


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings for one FFN block family."""

    num_experts: int
    top_k: int
    # capacity factor of the fixed-capacity dispatch the training forward
    # uses; 0 routes training dropless too.  Serving (prefill and decode)
    # is always dropless.
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01
    # width of one routed expert's SwiGLU; 0 -> the model's d_ff
    d_expert: int = 0
    # shared experts, computed for every token as one SwiGLU of
    # num_shared * d_expert
    num_shared: int = 0
    # router scores: "softmax" over all experts, or "sigmoid" per expert
    # (DeepSeek-V3); with score_bias the top-k is chosen on score + a
    # per-expert correction bias (noaux_tc) and weighted by the score;
    # the top-k weights are normalised to sum 1, then scaled
    scoring: str = "softmax"
    score_bias: bool = False
    routed_scale: float = 1.0
    # the experts this chip holds, (first, count), of num_experts: the
    # router routes over all of them, the layer computes its own
    # experts' part of the result.  None holds every expert.
    held: tuple[int, int] | None = None

    def expert_width(self, d_ff: int) -> int:
        return self.d_expert or d_ff

    @property
    def held_range(self) -> tuple[int, int]:
        return self.held or (0, self.num_experts)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3) without a query
    low-rank path: keys and values are decompressed from one normed
    latent of ``kv_rank`` per token, and RoPE runs on a separate
    ``rope_dim`` slice of the query and on one key slice shared by all
    heads.  The decode cache holds ``kv_rank + rope_dim`` per token."""

    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def cache_dim(self) -> int:
        return self.kv_rank + self.rope_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block settings."""

    state_dim: int = 64
    head_dim: int = 64
    expand: int = 2
    chunk_size: int = 256
    conv_width: int = 4


@dataclass(frozen=True)
class RWKVConfig:
    """RWKV6 (Finch) block settings."""

    head_dim: int = 64
    # decay lora rank (data-dependent decay projection)
    decay_lora: int = 64
    gate_lora: int = 64


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  Field names follow the assignment table."""

    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    kind: ModelKind = "decoder"

    num_layers: int = 12
    d_model: int = 1024
    num_heads: int = 16
    num_kv_heads: int = 16
    d_ff: int = 4096
    vocab_size: int = 32000
    head_dim: int = 0  # 0 -> d_model // num_heads

    # attention details
    qkv_bias: bool = False
    qk_norm: bool = False
    sliding_window: int = 0  # 0 -> full attention
    rope_theta: float = 10000.0

    # norm / activation
    norm_eps: float = 1e-5
    act: str = "silu"  # silu | gelu | relu
    gated_mlp: bool = True  # SwiGLU-family vs classic 2-matrix FFN
    tie_embeddings: bool = False

    # encoder (enc-dec only)
    enc_num_layers: int = 0
    enc_seq_len: int = 0  # fixed encoder memory length for serving shapes

    # heterogeneous stacks: pattern of block kinds, cycled over num_layers.
    # e.g. zamba2: mostly mamba2 with a shared attention block every k.
    block_pattern: tuple[BlockKind, ...] = ("attention",)

    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    # leading layers with a dense SwiGLU of d_ff in place of the MoE
    # (DeepSeek-V3's first_k_dense_replace)
    first_dense: int = 0
    ssm: SSMConfig | None = None
    rwkv: RWKVConfig | None = None

    frontend: Frontend = "none"
    # frontend stub: number of precomputed embedding frames/patches fed to
    # the backbone for [audio]/[vlm] archs (input_specs provides these).
    frontend_len: int = 0

    dtype: str = "bfloat16"
    source: str = ""  # citation tag from the assignment table

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_subquadratic(self) -> bool:
        """True if decode state is O(1)/bounded (may run long_500k)."""
        if any(k in ("mamba2", "rwkv6") for k in self.block_pattern):
            return True
        return self.sliding_window > 0

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs have a decode path (enc-dec included)

    def layer_kinds(self) -> tuple[BlockKind, ...]:
        """The per-layer block kind for the decoder stack."""
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.num_layers))

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, h = self.d_model, self.resolved_head_dim
        nq, nkv = self.num_heads, self.num_kv_heads
        total = self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d  # lm head
        def attn_params() -> int:
            if self.mla is not None:
                m = self.mla
                return (d * nq * m.qk_dim + d * m.cache_dim + m.kv_rank
                        + m.kv_rank * nq * (m.nope_dim + m.v_dim)
                        + nq * m.v_dim * d)
            p = d * (nq * h) + 2 * d * (nkv * h) + (nq * h) * d
            if self.qkv_bias:
                p += nq * h + 2 * nkv * h
            return p
        def ffn_params(dense_layer: bool = False) -> int:
            n_mats = 3 if self.gated_mlp else 2
            dense = n_mats * d * self.d_ff
            if self.moe is not None and not dense_layer:
                m = self.moe
                f = m.expert_width(self.d_ff)
                return (m.held_range[1] * n_mats * d * f
                        + m.num_shared * n_mats * d * f
                        + d * m.num_experts
                        + (m.num_experts if m.score_bias else 0))
            return dense
        def mamba_params() -> int:
            s = self.ssm or SSMConfig()
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            p = d * (2 * d_in + 2 * s.state_dim + nheads)  # in_proj(zxbcdt)
            p += s.conv_width * (d_in + 2 * s.state_dim)
            p += d_in * d  # out_proj
            p += 2 * nheads  # A_log, D
            return p
        def rwkv_params() -> int:
            r = self.rwkv or RWKVConfig()
            p = 4 * d * d  # r,k,v,output
            p += d * r.decay_lora + r.decay_lora * d  # decay lora
            p += d * r.gate_lora + r.gate_lora * d  # gate lora
            p += 6 * d  # token-shift mixes
            p += d * self.d_ff + self.d_ff * d  # channel mix
            return p
        for i, kind in enumerate(self.layer_kinds()):
            total += 2 * d  # norms
            if kind in ("attention", "shared_attention"):
                total += attn_params() + ffn_params(i < self.first_dense)
            elif kind == "mamba2":
                total += mamba_params() + ffn_params()
            elif kind == "rwkv6":
                total += rwkv_params()
        for _ in range(self.enc_num_layers):
            total += 2 * d + attn_params() + ffn_params()
            if self.kind == "encoder_decoder":
                # decoder cross-attention (one per decoder layer accounted here
                # as enc side for simplicity of the analytic count)
                pass
        if self.kind == "encoder_decoder":
            total += self.num_layers * (d + attn_params())  # cross attn + norm
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE counts top_k experts only)."""
        if self.moe is None:
            return self.param_count()
        full = self.param_count()
        d = self.d_model
        dense = 3 * d * self.moe.expert_width(self.d_ff)
        n_moe_layers = sum(
            1 for i, k in enumerate(self.layer_kinds())
            if k in ("attention", "shared_attention") and i >= self.first_dense
        )
        held = self.moe.held_range[1]
        inactive = n_moe_layers * max(0, held - self.moe.top_k) * dense
        return full - inactive


@dataclass(frozen=True)
class ShapeConfig:
    """A workload shape cell: (kind, seq_len, global_batch)."""

    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"] = "train"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

LM_SHAPES: tuple[ShapeConfig, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


def shapes_for(config: ModelConfig) -> tuple[ShapeConfig, ...]:
    """The shape cells that are well-defined for this architecture.

    ``long_500k`` requires sub-quadratic attention (SSM / hybrid / SWA);
    pure full-attention archs skip it (recorded in DESIGN.md §4).
    """
    out = []
    for s in LM_SHAPES:
        if s.name == "long_500k" and not config.is_subquadratic:
            continue
        out.append(s)
    return tuple(out)


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh description."""

    shape: tuple[int, ...]
    axes: tuple[str, ...]

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))
MULTI_POD = MeshConfig((2, 16, 16), ("pod", "data", "model"))


@dataclass(frozen=True)
class TrainConfig:
    """Training hyper-parameters and runtime knobs."""

    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    microbatches: int = 1  # gradient accumulation factor
    remat: bool = True
    seed: int = 0
    # near-bank instruction offload (compile-time jaxpr rewrite, §IV-B1):
    # ``offload`` switches the rewriter on; ``offload_policy`` (a
    # repro.core.policy.OffloadPolicy) selects the decision backend and
    # planner knobs — None leaves the wrapper unpinned, resolving the
    # active ``with offload_policy(...):`` scope (else the default
    # greedy policy) at call time.
    offload: bool = False
    offload_policy: "OffloadPolicy | None" = None
    # DEPRECATED: pre-policy knobs, folded into offload_policy by
    # train/step.py with a DeprecationWarning — set
    # offload_policy=OffloadPolicy(bulk_threshold=..., max_plans=...)
    offload_bulk_threshold: int | None = None
    offload_max_plans: int | None = None
    # distributed-optimization knobs
    zero3: bool = True  # shard params/opt-state over the data axis
    grad_compression: Literal["none", "int8"] = "none"
    hierarchical_allreduce: bool = True  # 2-step pod-aware gradient reduction
    # fault tolerance
    checkpoint_every: int = 100
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    # hard per-step wall-time deadline (0 = disabled): a step exceeding
    # it is flagged by StragglerMonitor and the loop force-commits a
    # checkpoint (train.loop / launch.train)
    step_deadline_s: float = 0.0

    def __post_init__(self):
        if self.offload_bulk_threshold is not None or \
                self.offload_max_plans is not None:
            warnings.warn(
                "TrainConfig.offload_bulk_threshold/offload_max_plans are "
                "deprecated: set offload_policy=OffloadPolicy("
                "bulk_threshold=..., max_plans=...) instead",
                DeprecationWarning, stacklevel=3)

    def resolved_offload_policy(self) -> OffloadPolicy | None:
        """The policy the train step should pin: ``offload_policy`` with
        any deprecated knobs folded on top, or None to leave the wrapper
        unpinned (scoped ``offload_policy(...)`` overrides / default)."""
        legacy = {k: v for k, v in (
            ("bulk_threshold", self.offload_bulk_threshold),
            ("max_plans", self.offload_max_plans)) if v is not None}
        if not legacy:
            return self.offload_policy
        return (self.offload_policy or OffloadPolicy()).replace(**legacy)


def reduced(config: ModelConfig, **overrides) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    small: dict = dict(
        num_layers=max(2, min(4, len(config.block_pattern))),
        d_model=64,
        num_heads=4,
        num_kv_heads=min(4, max(1, config.num_kv_heads * 4 // config.num_heads)),
        d_ff=128,
        vocab_size=256,
        head_dim=16,
    )
    if config.enc_num_layers:
        small["enc_num_layers"] = 2
        small["enc_seq_len"] = 16
    if config.moe is not None:
        m = config.moe
        e = min(4, m.num_experts) if m.held is None else min(8, m.num_experts)
        small["moe"] = dataclasses.replace(
            m, num_experts=e, top_k=min(2, m.top_k),
            d_expert=32 if m.d_expert else 0,
            held=None if m.held is None else (0, e // 2))
    if config.mla is not None:
        small["mla"] = MLAConfig(kv_rank=32, nope_dim=16, rope_dim=8,
                                 v_dim=16)
        small["num_layers"] = config.first_dense + 2
    if config.ssm is not None:
        small["ssm"] = SSMConfig(state_dim=16, head_dim=16, expand=2, chunk_size=16)
    if config.rwkv is not None:
        small["rwkv"] = RWKVConfig(head_dim=16, decay_lora=8, gate_lora=8)
    if config.sliding_window:
        small["sliding_window"] = 8
    if config.frontend != "none":
        small["frontend_len"] = 8
    small.update(overrides)
    return dataclasses.replace(config, **small)
