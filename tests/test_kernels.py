"""Pallas kernel validation: interpret-mode execution of the real kernel
bodies vs the pure-jnp oracles, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas

FLASH_SHAPES = [
    # (B, S, H, KVH, hd)
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 256, 8, 1, 128),     # MQA, MXU-aligned head
    (2, 128, 16, 4, 128),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return 2e-6 if dtype == jnp.float32 else 2e-2


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("window", [None, 64])
def test_flash_kernel_sweep(shape, dtype, window, rng_key):
    B, S, H, KVH, hd = shape
    k1, k2, k3 = jax.random.split(rng_key, 3)
    q = jax.random.normal(k1, (B, S, H, hd), dtype)
    k = jax.random.normal(k2, (B, S, KVH, hd), dtype)
    v = jax.random.normal(k3, (B, S, KVH, hd), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=64, block_k=64, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    err = jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)).max()
    assert float(err) < _tol(dtype), f"{shape} {dtype} w={window}: {err}"


def test_flash_non_causal(rng_key):
    B, S, H, hd = 1, 128, 4, 64
    k1, k2, k3 = jax.random.split(rng_key, 3)
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, H, hd))
    v = jax.random.normal(k3, (B, S, H, hd))
    out = flash_attention_pallas(q, k, v, causal=False, block_q=64,
                                 block_k=64, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    assert float(jnp.abs(out - exp).max()) < 2e-6


DECODE_SHAPES = [
    # (B, H, KVH, hd, W)
    (1, 4, 4, 64, 256),
    (2, 8, 2, 64, 512),
    (3, 8, 1, 128, 256),
    (2, 16, 4, 128, 512),
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_kernel_sweep(shape, dtype, rng_key):
    B, H, KVH, hd, W = shape
    k1, k2, k3, k4 = jax.random.split(rng_key, 4)
    q = jax.random.normal(k1, (B, 1, H, hd), dtype)
    kc = jax.random.normal(k2, (B, KVH, W, hd), dtype)
    vc = jax.random.normal(k3, (B, KVH, W, hd), dtype)
    lengths = jax.random.randint(k4, (B,), 1, W + 1)
    out = decode_attention_pallas(q, kc, vc, lengths, block_k=128,
                                  interpret=True)
    exp = ref.decode_attention_ref(q, kc, vc, lengths)
    err = jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)).max()
    assert float(err) < _tol(dtype)


def test_decode_partial_lengths_masking(rng_key):
    """Slots past `length` must not affect output even if filled with junk."""
    B, H, KVH, hd, W = 1, 4, 2, 64, 256
    k1, k2, k3 = jax.random.split(rng_key, 3)
    q = jax.random.normal(k1, (B, 1, H, hd))
    kc = jax.random.normal(k2, (B, KVH, W, hd))
    vc = jax.random.normal(k3, (B, KVH, W, hd))
    L = 100
    lengths = jnp.array([L], jnp.int32)
    out1 = decode_attention_pallas(q, kc, vc, lengths, block_k=64,
                                   interpret=True)
    kc2 = kc.at[:, :, L:].set(1e4)
    vc2 = vc.at[:, :, L:].set(-1e4)
    out2 = decode_attention_pallas(q, kc2, vc2, lengths, block_k=64,
                                   interpret=True)
    assert float(jnp.abs(out1 - out2).max()) < 1e-6


# ---------------------------------------------------------------------------
# Paged decode attention (KV gathered through block tables)
# ---------------------------------------------------------------------------

from repro.kernels.decode_attention import (paged_decode_attention_pallas,
                                            paged_decode_attention_ref)

PAGED_SHAPES = [
    # (B, H, KVH, hd, W, block_lines)
    (1, 4, 4, 64, 256, 64),
    (2, 8, 2, 64, 512, 128),
    (3, 8, 1, 128, 256, 64),
]


def _scatter_to_pool(cache, tables, block_lines, num_blocks, per_page):
    """Place each request's contiguous head-major cache rows into the
    pool blocks its table names (inverse of the kernel's gather); block
    ``i`` is line block ``i % per_page`` of page ``i // per_page``."""
    B, KVH, W, hd = cache.shape
    pool = jnp.zeros((num_blocks // per_page, KVH, per_page, block_lines,
                      hd), cache.dtype)
    for b in range(B):
        for i, blk in enumerate(tables[b]):
            rows = cache[b, :, i * block_lines:(i + 1) * block_lines]
            pool = pool.at[int(blk) // per_page, :,
                           int(blk) % per_page].set(rows)
    return pool


@pytest.mark.parametrize("shape", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_decode_kernel_matches_dense(shape, dtype, rng_key):
    """The paged kernel over a scattered pool == the dense kernel over
    the contiguous caches the block tables describe."""
    B, H, KVH, hd, W, bl = shape
    nb = W // bl
    k1, k2, k3, k4, k5 = jax.random.split(rng_key, 5)
    q = jax.random.normal(k1, (B, 1, H, hd), dtype)
    kc = jax.random.normal(k2, (B, KVH, W, hd), dtype)
    vc = jax.random.normal(k3, (B, KVH, W, hd), dtype)
    lengths = jax.random.randint(k4, (B,), 1, W + 1)
    # a non-trivial physical placement: shuffled pool twice as large
    num_blocks = 2 * B * nb
    tables = jax.random.permutation(k5, num_blocks)[: B * nb]
    tables = tables.reshape(B, nb).astype(jnp.int32)
    k_pool = _scatter_to_pool(kc, tables, bl, num_blocks, nb)
    v_pool = _scatter_to_pool(vc, tables, bl, num_blocks, nb)
    out = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=True)
    exp = decode_attention_pallas(q, kc, vc, lengths, block_k=bl,
                                  interpret=True)
    err = jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)).max()
    assert float(err) < _tol(dtype), f"{shape} {dtype}: {err}"
    # and the jnp oracle agrees
    oracle = paged_decode_attention_ref(q, k_pool, v_pool, tables, lengths)
    err = jnp.abs(out.astype(jnp.float32)
                  - oracle.astype(jnp.float32)).max()
    assert float(err) < max(_tol(dtype), 2e-5)


def test_paged_kernel_reads_store_block_tables(rng_key):
    """End-to-end with the live store: attention over a PagedStore leaf
    through its real (slot-affine) block tables matches the dense view."""
    import numpy as np
    from repro.configs import get_config
    from repro.kvstore import PagedStore
    cfg = get_config("starcoder2-3b").reduced()
    store = PagedStore(cfg, num_slots=4, kv_capacity=64, block_lines=16)
    rids, slots = [11, 22], [1, 3]
    lengths = [20, 37]
    for rid, slot, n in zip(rids, slots, lengths):
        store.alloc(rid, slot, lines=n)
    # one attention leaf, repeat index 0: (B, KVH, W, hd)
    i, pj, key, kind = next(p for p in store._paths if p[3] == "line")
    leaf = store.state["layers"][i][pj][key][0]
    B, KVH, W, hd = leaf.shape
    k1, k2, k3 = jax.random.split(rng_key, 3)
    kc = jax.random.normal(k1, leaf.shape)
    vc = jax.random.normal(k2, leaf.shape)
    H = cfg.num_heads
    q = jax.random.normal(k3, (B, 1, H, cfg.head_dim))
    pool_k, pool_v = store.pool_view(kc), store.pool_view(vc)
    nb = store.line_blocks_per_slot
    tables = np.zeros((B, nb), np.int32)
    lens = np.zeros((B,), np.int32)
    for rid, slot, n in zip(rids, slots, lengths):
        t = store.line_block_table(rid)
        tables[slot, :len(t)] = t
        lens[slot] = n
    out = paged_decode_attention_pallas(q, pool_k, pool_v,
                                        jnp.asarray(tables),
                                        jnp.asarray(lens), interpret=True)
    exp = decode_attention_pallas(q, kc, vc, jnp.asarray(lens), block_k=16,
                                  interpret=True)
    # only rows of slots that hold requests are meaningful
    for slot in slots:
        err = jnp.abs(out[slot] - exp[slot]).max()
        assert float(err) < 2e-6
