"""Operations and bytes of the paged latent-attention decode kernel
(``paged_mla_decode``) from a configuration file's published latent
widths and the requests' real lengths, as ``costs.py`` counts the GQA
kernels.  A multiply-add is two operations.
"""
from __future__ import annotations

from dataclasses import dataclass

from chipbench.costs import BF16


@dataclass(frozen=True)
class MlaDims:
    layers: int     # layers held on this chip
    heads: int
    kv_lora: int    # latent width: what the kernel reads back per line
    rope: int       # shared roped key width

    @classmethod
    def from_config(cls, c: dict) -> "MlaDims":
        return cls(layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_lora=c["kv_lora_rank"], rope=c["qk_rope_head_dim"])


def paged_mla_decode_cost(m: MlaDims, context: int):
    """(operations, bytes) of the kernel for one decode token whose query
    attends to ``context`` lines, all layers: every head scores each
    ``kv_lora + rope`` line and reads its ``kv_lora`` latent back; the
    lines are read once for all heads, plus q (absorbed latent and rope)
    read and the latent output written."""
    line = m.kv_lora + m.rope
    flops = m.layers * 2 * m.heads * context * (line + m.kv_lora)
    nbytes = m.layers * BF16 * (line * context + m.heads * line
                                + m.heads * m.kv_lora)
    return flops, nbytes
