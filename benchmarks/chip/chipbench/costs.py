"""Operations and bytes that the served work needs, from shapes and the
requests' real lengths (never the padded buckets the program runs).

``Dims`` is read from a configuration file's published keys.  Every
function counts one sequence; the readers sum them over the requests a
window served.  A multiply-add is two operations.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2    # bytes per element of weights, activations and KV lines


@dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    layers: int     # layers held on this chip
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int        # intermediate size
    ffn_mats: int   # 3 for a gated FFN (SwiGLU), 2 for a plain MLP
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        d, h = c["hidden_size"], c["num_attention_heads"]
        return cls(d=d, layers=c["num_hidden_layers"], heads=h,
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or d // h,
                   ffn=c["intermediate_size"],
                   ffn_mats=3 if c["hidden_act"] == "silu" else 2,
                   vocab=c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        return attn + self.ffn_mats * self.d * self.ffn


def _causal_pairs(n: int, history: int = 0) -> int:
    """Query-key pairs of ``n`` new tokens after ``history`` cached ones,
    each token attending to itself and everything before it."""
    return n * history + n * (n + 1) // 2


def prefill_flops(m: Dims, prompt: int) -> float:
    """The whole prefill of one prompt: every layer's matmuls for each
    token, causal attention, and the head at the last token."""
    per_layer = (2 * m.layer_matmul_params * prompt
                 + 4 * m.heads * m.head_dim * _causal_pairs(prompt))
    return m.layers * per_layer + 2 * m.d * m.vocab


def decode_flops(m: Dims, context: int) -> float:
    """One decode token whose query attends to ``context`` keys (its own
    included)."""
    per_layer = (2 * m.layer_matmul_params
                 + 4 * m.heads * m.head_dim * context)
    return m.layers * per_layer + 2 * m.d * m.vocab


def flash_prefill_cost(m: Dims, prompt: int):
    """(operations, bytes) of the flash attention kernel over one prompt,
    all layers: causal QK^T and PV; q, k, v read once and o written."""
    flops = m.layers * 4 * m.heads * m.head_dim * _causal_pairs(prompt)
    nbytes = (m.layers * prompt * m.head_dim * BF16
              * (2 * m.heads + 2 * m.kv_heads))
    return flops, nbytes


def paged_decode_cost(m: Dims, context: int):
    """(operations, bytes) of the paged decode kernel for one token, all
    layers: the K and V lines of ``context`` live positions read, q read
    and o written."""
    flops = m.layers * 4 * m.heads * m.head_dim * context
    nbytes = m.layers * BF16 * m.head_dim * (2 * m.kv_heads * context
                                             + 2 * m.heads)
    return flops, nbytes
