"""Pallas TPU decode-attention kernel (single query token vs KV cache).

The decode phase is HBM-bandwidth bound (AcceLLM §3.3): per step the whole
KV cache streams HBM->VMEM once while compute is two skinny matmuls. The
kernel therefore tiles the cache's sequence dim and processes all G grouped
query heads of one KV head per tile, so every K/V byte fetched feeds G
query heads (GQA bandwidth amplification).

K/V are head-major: a cache is ``(B, KVH, W, hd)`` and the paged pool
``(P, KVH, blocks_per_page, block_lines, hd)`` (the same array reshaped,
no copy), so each fetched tile is one head's ``(block_lines, hd)`` rows —
contiguous in HBM, and its last two dims are the array's own, which
Mosaic requires of a block.

Grid: (batch, kv_heads, num_kv_blocks), KV-block axis innermost and
sequential, online-softmax accumulation in VMEM scratch. Invalid (not yet
written) cache slots are masked via the per-request ``length`` scalar,
prefetched to SMEM with the block tables.  The dense kernel is the paged
kernel over the identity table.

MLA (``paged_mla_decode``): every head shares one line ``[latent, roped
key, zero pad]`` per token (``models.state.mla_line_width``), so the
grid is (batch, line blocks): each step fetches one ``(block_lines,
width)`` block through the same scalar-prefetched tables and scores it
against all heads' absorbed queries at once.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(lengths_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *,
                         scale: float, block_lines: int, max_blocks: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths_ref[b]
    k_start = ki * block_lines

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
        k = k_ref[0, 0, 0].astype(jnp.float32)       # (block_lines, hd)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, block_lines)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == max_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention_pallas(
    q: jax.Array,             # (B, 1, H, hd) or (B, H, hd)
    k_pool: jax.Array,        # (P, KVH, blocks_per_page, block_lines, hd)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32 — pool block ids
    lengths: jax.Array,       # (B,) int32 — valid KV lines per request
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a paged KV pool (`repro.kvstore.PagedStore`
    layout): the kernel never sees a contiguous per-request cache — each
    KV tile is DMA'd from the block the request's block table names, via
    scalar-prefetched table indices in the BlockSpec index map.  Block id
    ``i`` is line block ``i % blocks_per_page`` of page ``i //
    blocks_per_page``; entries of ``block_tables`` beyond a request's
    blocks may be any valid block id (their scores are masked by
    ``lengths``)."""
    squeeze = False
    if q.ndim == 4:
        assert q.shape[1] == 1
        q = q[:, 0]
        squeeze = True
    B, H, hd = q.shape
    _, KVH, per_page, block_lines, _ = k_pool.shape
    G = H // KVH
    max_blocks = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qg = q.reshape(B, KVH, G, hd)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_lines=block_lines,
        max_blocks=max_blocks)

    def kv_block(b, h, ki, lens, tabs):
        blk = tabs[b, ki]
        return (blk // per_page, h, blk % per_page, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, ki, lens, tabs: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_lines, hd), kv_block),
            pl.BlockSpec((1, 1, 1, block_lines, hd), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, ki, lens, tabs: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      qg, k_pool, v_pool)

    out = out.reshape(B, H, hd)
    return out[:, None] if squeeze else out


def decode_attention_pallas(
    q: jax.Array,         # (B, 1, H, hd) or (B, H, hd)
    k_cache: jax.Array,   # (B, KVH, W, hd)
    v_cache: jax.Array,
    lengths: jax.Array,   # (B,) int32 — valid KV entries per request
    *,
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Dense decode attention: each request's cache is one page of
    ``W / block_k`` blocks, read through the identity block table."""
    B, KVH, W, hd = k_cache.shape
    block_k = min(block_k, W)
    assert W % block_k == 0, f"cache window {W} must divide block_k {block_k}"
    nk = W // block_k
    pool_shape = (B, KVH, nk, block_k, hd)
    tables = jnp.arange(B * nk, dtype=jnp.int32).reshape(B, nk)
    return paged_decode_attention_pallas(
        q, k_cache.reshape(pool_shape), v_cache.reshape(pool_shape), tables,
        lengths, scale=scale, interpret=interpret)


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    backend: str = "jnp",
) -> jax.Array:
    """Backend dispatcher for the model's paged decode hot path:
    ``"pallas"`` runs the compiled Mosaic kernel, ``"interpret"`` the same
    kernel body in Pallas interpret mode (CPU tests), ``"jnp"`` the
    gather oracle.  All three read K/V exclusively through the block
    tables — the dense per-slot window is never touched.

    Under an active mesh (repro.meshserve) the pools arrive with their
    KV-head dim on the slice's model axis; the gather is per-head, so
    each shard touches only its own heads' blocks and the result needs
    no collective until the attention output hits the row-parallel
    output projection.  The tables and lengths are tiny and replicated."""
    from repro import sharding
    # pin the pools' KV-head dim where the store committed it, so GSPMD
    # never rematerializes the whole pool for the gather (no-op without
    # a mesh; skipped when the KV heads don't divide the slice — the
    # store then keeps the pool replicated and only q heads split)
    ctx = sharding.current()
    if (ctx.mesh is not None and ctx.model_axis is not None
            and k_pool.shape[1] % ctx.model_size == 0):
        k_pool = sharding.constrain(k_pool, None, "model", None, None, None)
        v_pool = sharding.constrain(v_pool, None, "model", None, None, None)
    if backend == "jnp":
        return paged_decode_attention_ref(q, k_pool, v_pool, block_tables,
                                          lengths, scale=scale)
    assert backend in ("pallas", "interpret"), backend
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, block_tables, lengths, scale=scale,
        interpret=backend == "interpret")


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                               *, scale: Optional[float] = None):
    """jnp oracle: gather each request's blocks into a contiguous
    head-major cache, then run the dense decode path."""
    from repro.models.attention import decode_attention, ring_valid
    squeeze = q.ndim == 4
    if not squeeze:
        q = q[:, None]
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    _, KVH, per_page, bl, _ = k_pool.shape
    B, M = block_tables.shape
    page, blk = block_tables // per_page, block_tables % per_page

    def gather(pool):
        # (B, M, KVH, bl, hd) -> (B, KVH, M * bl, hd)
        return pool[page, :, blk].swapaxes(1, 2).reshape(B, KVH, M * bl, hd)

    gathered_k, gathered_v = gather(k_pool), gather(v_pool)
    valid = ring_valid(lengths, M * bl)
    out = decode_attention(q, gathered_k, gathered_v, scale=scale,
                           valid=valid)
    return out if squeeze else out[:, 0]


# ---------------------------------------------------------------------------
# MLA: latent lines shared by every head
# ---------------------------------------------------------------------------


def _paged_mla_kernel(lengths_ref, tables_ref, q_ref, line_ref, o_ref,
                      acc_ref, m_ref, l_ref, *,
                      scale: float, lat: int, block_lines: int,
                      max_blocks: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths_ref[b]
    k_start = ki * block_lines

    @pl.when(k_start < length)
    def _compute():
        lines = line_ref[0, 0]                       # (block_lines, width)
        s = jax.lax.dot_general(
            q_ref[0], lines, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H, block_lines)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(lines.dtype), lines[:, :lat], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == max_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_mla_decode_pallas(
    q: jax.Array,             # (B, H, width): [q W_UK, q_rope, 0]
    pool: jax.Array,          # (P, blocks_per_page, block_lines, width)
    block_tables: jax.Array,  # (B, max_blocks) int32 — pool block ids
    lengths: jax.Array,       # (B,) int32 — valid lines per request
    *,
    lat: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """MLA decode attention over paged lines ``[latent, roped key, 0]``:
    softmax over the row's lines of ``scale * q . line``, read back as
    the weighted sum of the lines' first ``lat`` columns (B, H, lat).
    Block id ``i`` is line block ``i % blocks_per_page`` of page ``i //
    blocks_per_page``, as in :func:`paged_decode_attention_pallas`."""
    B, H, width = q.shape
    _, per_page, block_lines, _ = pool.shape
    max_blocks = block_tables.shape[1]

    kernel = functools.partial(
        _paged_mla_kernel, scale=scale, lat=lat, block_lines=block_lines,
        max_blocks=max_blocks)

    def line_block(b, ki, lens, tabs):
        blk = tabs[b, ki]
        return (blk // per_page, blk % per_page, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, H, width), lambda b, ki, lens, tabs: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_lines, width), line_block),
        ],
        out_specs=pl.BlockSpec((1, H, lat),
                               lambda b, ki, lens, tabs: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, lat), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, lat), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_mla_decode",
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), q, pool)


def paged_mla_decode_ref(q, pool, block_tables, lengths, *, lat: int,
                         scale: float):
    """jnp oracle: gather each row's line blocks, then dense float32
    attention over its first ``lengths`` lines."""
    _, per_page, bl, width = pool.shape
    B, M = block_tables.shape
    page, blk = block_tables // per_page, block_tables % per_page
    lines = pool[page, blk].reshape(B, M * bl, width).astype(jnp.float32)
    s = jnp.einsum("bhc,bwc->bhw", q.astype(jnp.float32), lines) * scale
    valid = jnp.arange(M * bl)[None] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhw,bwl->bhl", p, lines[..., :lat]).astype(q.dtype)


def paged_mla_decode(q, pool, block_tables, lengths, *, lat: int,
                     scale: float, backend: str = "jnp") -> jax.Array:
    """Backend dispatcher for the MLA paged decode hot path (``"pallas"``,
    ``"interpret"`` or the ``"jnp"`` gather oracle); all three read the
    lines only through the block tables."""
    if backend == "jnp":
        return paged_mla_decode_ref(q, pool, block_tables, lengths, lat=lat,
                                    scale=scale)
    assert backend in ("pallas", "interpret"), backend
    return paged_mla_decode_pallas(q, pool, block_tables, lengths, lat=lat,
                                   scale=scale,
                                   interpret=backend == "interpret")
