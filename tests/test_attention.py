"""jnp attention internals: chunked flash vs naive, ring buffers, MLA."""
import jax
import jax.numpy as jnp
import pytest

from repro.models.attention import (decode_attention, flash_attention,
                                    ring_valid, ring_write)


def naive_attention(q, k, v, causal, window=None, scale=None):
    import math
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = scale or 1.0 / math.sqrt(hd)
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(k.shape[1])[None]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= qp >= kp
    if window is not None:
        mask &= qp - kp < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))


@pytest.mark.parametrize("chunks", [(512, 1024), (16, 32), (7, 13)])
@pytest.mark.parametrize("window", [None, 20])
def test_flash_vs_naive(chunks, window, rng_key):
    qc, kc = chunks
    B, S, H, KVH, hd = 2, 48, 4, 2, 32
    k1, k2, k3 = jax.random.split(rng_key, 3)
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, KVH, hd))
    v = jax.random.normal(k3, (B, S, KVH, hd))
    out = flash_attention(q, k, v, causal=True, scale=hd ** -0.5,
                          window=window, q_chunk=qc, kv_chunk=kc)
    exp = naive_attention(q, k, v, causal=True, window=window)
    assert float(jnp.abs(out - exp.astype(out.dtype)).max()) < 1e-5


@pytest.mark.parametrize("axis", [1, 2])
def test_ring_write_scalar_and_vector(axis):
    """Lines on axis 1 (MLA latents) or axis 2 (head-major K/V); the
    checks read through a view with the line axis moved to 1."""
    cap = 8

    def lay(x):
        return jnp.moveaxis(x, 1, axis)

    def view(x):
        return jnp.moveaxis(x, axis, 1)

    cache = lay(jnp.zeros((2, cap, 3)))
    vals = lay(jnp.ones((2, 2, 3)))
    # scalar clock, wraps
    c1 = view(ring_write(cache, vals, jnp.int32(7), cap, axis=axis))
    assert float(c1[0, 7, 0]) == 1.0 and float(c1[0, 0, 0]) == 1.0
    # per-batch clock
    one = lay(jnp.ones((2, 1, 3)))
    c2 = view(ring_write(cache, one, jnp.array([1, 5]), cap, axis=axis))
    assert float(c2[0, 1, 0]) == 1.0 and float(c2[1, 5, 0]) == 1.0
    assert float(c2[0, 5, 0]) == 0.0


def test_ring_write_overflow_keeps_tail():
    cap = 4
    cache = jnp.zeros((1, cap, 1))
    vals = jnp.arange(6, dtype=jnp.float32).reshape(1, 6, 1)
    c = ring_write(cache, vals, jnp.int32(0), cap)
    # last 4 values (2,3,4,5) at slots (2,3,0,1): slot i holds value with
    # logical position p where p % cap == i
    got = sorted(float(x) for x in c[0, :, 0])
    assert got == [2.0, 3.0, 4.0, 5.0]
    for p in range(2, 6):
        assert float(c[0, p % cap, 0]) == float(p)


def test_ring_valid():
    assert ring_valid(jnp.int32(3), 8).sum() == 3
    assert ring_valid(jnp.int32(12), 8).sum() == 8
    v = ring_valid(jnp.array([2, 9]), 8)
    assert v.shape == (2, 8) and int(v[0].sum()) == 2 and int(v[1].sum()) == 8


def test_decode_attention_batched_valid(rng_key):
    B, H, KVH, hd, W = 2, 4, 2, 16, 32
    k1, k2, k3 = jax.random.split(rng_key, 3)
    q = jax.random.normal(k1, (B, 1, H, hd))
    kc = jax.random.normal(k2, (B, KVH, W, hd))      # head-major caches
    vc = jax.random.normal(k3, (B, KVH, W, hd))
    valid = jnp.arange(W)[None] < jnp.array([[5], [W]])
    out = decode_attention(q, kc, vc, scale=hd ** -0.5, valid=valid)
    # manual check for request 0: only first 5 slots
    exp = naive_attention(q[:1], kc[:1, :, :5].swapaxes(1, 2),
                          vc[:1, :, :5].swapaxes(1, 2), causal=False,
                          scale=hd ** -0.5)
    assert float(jnp.abs(out[0] - exp[0, 0].astype(out.dtype)).max()) < 1e-5
