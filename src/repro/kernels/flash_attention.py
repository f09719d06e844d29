"""Pallas TPU flash-attention kernel (prefill / training path).

Causal (optionally sliding-window) multi-head attention with GQA, computed
block-by-block in VMEM with online softmax — the HBM->VMEM streaming
analogue of FlashAttention's SRAM tiling (see DESIGN.md §3: this is a
re-tiling for the TPU memory hierarchy, not a CUDA port).

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) with the KV-block axis
innermost and sequential ("arbitrary"), accumulating into VMEM scratch.
Causal block-skipping uses @pl.when so fully-masked KV blocks do no MXU
work. GQA is expressed in the K/V index_map (kv_head = q_head // G) so K/V
tiles are fetched once per KV head, not once per Q head.  The wrapper
lays K/V out head-major, ``(B, KVH, S, hd)`` (an O(S) transpose of the
chunk's own projections), so each K/V tile's last two dims are
``(block_k, hd)`` as Mosaic requires.

Block sizes default to (128, head_dim): MXU-aligned when head_dim is a
multiple of 128; head_dim=64 models still lower (Mosaic pads lanes).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, num_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level liveness: skip KV blocks that are entirely masked out
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(
            live, k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)         # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)         # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,      # (B, Sq, H, hd)
    k: jax.Array,      # (B, Skv, KVH, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (
        f"seq ({Sq},{Skv}) must divide blocks ({block_q},{block_k})")
    nq, nk = Sq // block_q, Skv // block_k

    qt = q.transpose(0, 2, 1, 3)                     # (B, H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)                     # (B, KVH, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_prefill",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
