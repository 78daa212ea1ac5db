"""Operations and bytes that a dense decoder's work needs, from its shapes.

Counts are the algorithm's need, independent of how the program does
the work: a matmul of m x k by k x n is 2mkn operations; weights are
read once per step at the configuration's dtype (``torch_dtype``), K/V
once per step over each sequence's actual length at the same width.
Padding, recomputation, casts and idle batch rows are not counted, so
they show as a lower share of the peak.  Elementwise work (norms, RoPE,
softmax) is left out of the operations and of the bytes; it is well
under 1% of either at these widths.
"""
from __future__ import annotations

from dataclasses import dataclass

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Shapes:
    d: int          # hidden size
    layers: int
    nq: int         # query heads
    nkv: int        # key/value heads
    hd: int         # head size
    ff: int         # MLP width
    vocab: int
    tied: bool
    qk_norm: bool
    dtype_bytes: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Shapes":
        nq = cfg["num_attention_heads"]
        return cls(d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
                   nq=nq, nkv=cfg["num_key_value_heads"],
                   hd=cfg.get("head_dim") or cfg["hidden_size"] // nq,
                   ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   tied=bool(cfg["tie_word_embeddings"]),
                   qk_norm=bool(cfg.get("qk_norm", False)),
                   dtype_bytes=_DTYPE_BYTES[cfg["torch_dtype"]])

    # -- parameters -------------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o projections and the three SwiGLU matrices."""
        attn = self.d * self.hd * (2 * self.nq + 2 * self.nkv)
        return attn + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        return (self.layer_matmul_params + 2 * self.d
                + (2 * self.hd if self.qk_norm else 0))

    @property
    def head_params(self) -> int:
        return self.vocab * self.d

    @property
    def total_params(self) -> int:
        """Every parameter once: a tied table counts once."""
        return (self.layers * self.layer_params + self.d
                + self.head_params * (1 if self.tied else 2))

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over all layers."""
        return self.layers * 2 * self.nkv * self.hd * self.dtype_bytes

    # -- operations -------------------------------------------------------
    def attention_flops(self, length: int) -> int:
        """QK^T and PV of one query against ``length`` keys, all layers."""
        return self.layers * 4 * self.nq * self.hd * length

    def decode_flops(self, lengths) -> int:
        """One decode step: one token per active row; ``lengths`` are the
        rows' attended lengths (position + 1)."""
        per_row = 2 * (self.layers * self.layer_matmul_params
                       + self.head_params)
        return sum(per_row + self.attention_flops(n) for n in lengths)

    def prefill_flops(self, n: int) -> int:
        """One prompt of ``n`` real tokens, causal, with the LM head on
        the last position only."""
        causal_pairs = n * (n + 1) // 2
        return (2 * n * self.layers * self.layer_matmul_params
                + self.layers * 4 * self.nq * self.hd * causal_pairs
                + 2 * self.head_params)

    # -- bytes ------------------------------------------------------------
    def decode_bytes(self, lengths) -> int:
        """One decode step: every weight once (the embedding rows of the
        active tokens aside from the head), K/V read over each row's
        attended length, the new K/V written, and the embedding rows."""
        rows = len(lengths)
        weights = (self.layers * self.layer_params + self.d
                   + self.head_params) * self.dtype_bytes
        embed_rows = 0 if self.tied else rows * self.d * self.dtype_bytes
        kv = (sum(lengths) + rows) * self.kv_bytes_per_token
        return weights + embed_rows + kv

    def paged_attention_bytes(self, lengths) -> int:
        """K/V over the attended lengths, plus q in and the output out."""
        qo = 2 * len(lengths) * self.layers * self.nq * self.hd \
            * self.dtype_bytes
        return sum(lengths) * self.kv_bytes_per_token + qo

    def paged_attention_flops(self, lengths) -> int:
        return sum(self.attention_flops(n) for n in lengths)
