"""Pure-jnp oracles for the Pallas kernels (the ground truth in tests)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(
    q: jax.Array,      # (B, Sq, H, hd)
    k: jax.Array,      # (B, Skv, KVH, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32).reshape(B, Sq, KVH, G, hd)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qf, k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bkgqd", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def mamba_scan_ref(x, dt, b_ssm, c_ssm, a, d, h0):
    """Selective-scan oracle. x/dt (B,S,C); b/c (B,S,N); a (C,N); d (C,);
    h0 (B,C,N) -> (y (B,S,C), h_final (B,C,N))."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        da = jnp.exp(dt_t[..., None] * a)
        h = da * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bcn,bn->bc", h, c_t) + d * x_t
        return h, y

    xs = (x.swapaxes(0, 1), dt.swapaxes(0, 1),
          b_ssm.swapaxes(0, 1), c_ssm.swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h0.astype(jnp.float32),
                         tuple(t.astype(jnp.float32) for t in xs))
    return ys.swapaxes(0, 1), h


def decode_attention_ref(
    q: jax.Array,        # (B, 1, H, hd) or (B, H, hd)
    k_cache: jax.Array,  # (B, KVH, W, hd)
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,)
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    squeeze = False
    if q.ndim == 4:
        q = q[:, 0]
        squeeze = True
    B, H, hd = q.shape
    KVH, W = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32).reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,bkwd->bkgw", qf, k_cache.astype(jnp.float32)) * scale
    valid = jnp.arange(W)[None] < lengths[:, None]
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bkwd->bkgd", p, v_cache.astype(jnp.float32))
    o = o.reshape(B, H, hd).astype(q.dtype)
    return o[:, None] if squeeze else o
