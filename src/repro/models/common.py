"""Shared model-building primitives (pure functional, pytree params)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float | None = None):
    """Fan-in scaled normal init, shape (in_dim, out_dim)."""
    std = scale if scale is not None else in_dim ** -0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


def zeros_init(shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: Array, weight: Array, eps: float) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x: Array, weight: Array, bias: Array, eps: float) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Positional encodings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, yarn=None) -> Array:
    """Inverse frequencies of the ``head_dim/2`` rotary pairs; with a
    :class:`~repro.configs.base.YarnConfig`, YaRN's blend: pairs that
    turn fewer than ``beta_slow`` times over the original context are
    interpolated (divided by ``factor``), those that turn more than
    ``beta_fast`` times keep their frequency, with a linear ramp between
    (DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding``)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is None:
        return inv

    def dim_of(rotations):
        return (head_dim * math.log(yarn.original_max
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 * mscale * ln(factor) +
    1`` (1 for no scaling)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: Array, positions: Array, theta: float, yarn=None) -> Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    Rotate-half pairing (dims i and i + head_dim/2)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, yarn)                 # (hd/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, hd/2)
    angles = angles[..., None, :]                             # (..., seq, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: Array, dim: int) -> Array:
    """Classic transformer sinusoidal embedding; positions (...,) -> (..., dim)."""
    half = dim // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def swiglu(gate: Array, up: Array) -> Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def gelu(x: Array) -> Array:
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# Key splitting helper
# ---------------------------------------------------------------------------


class KeyGen:
    """Deterministic PRNG splitter: kg = KeyGen(key); kg() -> fresh subkey."""

    def __init__(self, key):
        self._key = key

    def __call__(self):
        self._key, sub = jax.random.split(self._key)
        return sub
