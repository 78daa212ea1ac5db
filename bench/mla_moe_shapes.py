"""Operations and bytes of latent attention in a latent-attention MoE
decoder (Moonlight, ``bench/reference/mla_moe.py``), from the cell's
configuration file.

Counts are the algorithm's need, as in ``bench/flops.py``: in the
absorbed decode form every head scores each cached row ``[c ‖ k_pe]``
(kv_lora_rank + rope width) and sums the rows' ``c``, so a row of
``n`` attended positions costs ``n * heads * (row + rank) * 2``
operations and reads ``n`` rows at the configuration's dtype in each
layer (every layer has MLA), plus the absorbed query in and the latent
output out.  The program's stored row is padded to whole 128-lane
tiles; that is not counted, so it shows as a lower share.
"""
from __future__ import annotations

from dataclasses import dataclass

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class MlaMoeShapes:
    layers: int
    heads: int
    rank: int           # kv_lora_rank
    rope: int           # qk_rope_head_dim
    dtype_bytes: int

    @classmethod
    def from_config(cls, cfg: dict) -> "MlaMoeShapes":
        return cls(layers=cfg["num_hidden_layers"],
                   heads=cfg["num_attention_heads"],
                   rank=cfg["kv_lora_rank"], rope=cfg["qk_rope_head_dim"],
                   dtype_bytes=_DTYPE_BYTES[cfg["torch_dtype"]])

    @property
    def row(self) -> int:
        return self.rank + self.rope

    # -- latent attention, one decode step over every layer -------------
    def mla_flops(self, lengths) -> int:
        return (sum(lengths) * self.heads * (self.row + self.rank) * 2
                * self.layers)

    def mla_bytes(self, lengths) -> int:
        qo = len(lengths) * self.heads * (self.row + self.rank)
        return (sum(lengths) * self.row + qo) * self.dtype_bytes \
            * self.layers
