"""Plain reference for the dense decoder configurations (``"reference":
"dense_gqa"``), and their mapping onto the program's config type.

The reference is the published block in straightforward ``jax.numpy``
and float32 at ``highest`` matmul precision: token embedding; per layer
a pre-norm grouped-query attention with rotary positions (rotate-half
form, theta from the file) and causal softmax, then a pre-norm MLP
(tanh GeLU for ``gelu_pytorch_tanh``, SiLU-gated for ``silu``), each
added to the residual; a final norm and the head.  It imports nothing
of the program.  It reads the benchmark's own weight arrays by the keys
the benchmark made them under (``chipbench.weights``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def program_config(c: dict):
    """The configuration as the program takes it."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense", source=c["paper"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        sliding_window=c.get("sliding_window"),
        rms_norm_eps=float(c.get("rms_norm_eps", c.get("norm_epsilon"))),
        activation="swiglu" if c["hidden_act"] == "silu" else "gelu",
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"])


def _eps(c):
    return float(c.get("rms_norm_eps", c.get("norm_epsilon")))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, heads, hd); rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd // 2, dtype=jnp.float32)
                          / (hd // 2))
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _mm(x, w, quant):
    """x @ w in float32; ``quant`` (or None) rounds both operands."""
    w = w.astype(jnp.float32)
    if quant is None:
        return x @ w
    return quant.act(x) @ quant.weight(w)


def _layer(c, x, w, quant):
    S = x.shape[0]
    H, KVH, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    pos = jnp.arange(S)
    h = _rms(x, w["norm1"].astype(jnp.float32), _eps(c))
    att = w["mixer"]
    q = _rope(_mm(h, att["wq"], quant).reshape(S, H, hd), pos,
              c["rope_theta"])
    k = _rope(_mm(h, att["wk"], quant).reshape(S, KVH, hd), pos,
              c["rope_theta"])
    v = _mm(h, att["wv"], quant).reshape(S, KVH, hd)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    x = x + _mm(o.reshape(S, H * hd), att["wo"], quant)
    h = _rms(x, w["norm2"].astype(jnp.float32), _eps(c))
    ffn = w["ffn"]
    if c["hidden_act"] == "silu":
        m = (jax.nn.silu(_mm(h, ffn["w_gate"], quant))
             * _mm(h, ffn["w_up"], quant))
    else:
        m = jax.nn.gelu(_mm(h, ffn["w_up"], quant), approximate=True)
    return x + _mm(m, ffn["w_down"], quant)


def logits(c: dict, params, tokens, quant=None):
    """Logits (S, vocab) at every position of one sequence ``tokens``
    (S,).  ``quant``, when given, rounds the operands of every matrix
    product (``quant.weight``, ``quant.act``); None is the reference."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[tokens]
        stack = params["segments"][0]["p0"]

        def body(x, w):
            return _layer(c, x, w, quant), None

        x, _ = jax.lax.scan(body, x, stack)
        x = _rms(x, params["final_norm"].astype(jnp.float32), _eps(c))
        head = (params["embed"].T if c["tie_word_embeddings"]
                else params["lm_head"])
        return _mm(x, head, quant)
