"""Attention: GQA (RoPE, optional sliding window) and MLA (DeepSeek latent).

Four execution paths:
  * full   — train / prefill over S tokens: chunked online-softmax "flash"
             in pure jnp (lax.scan over KV blocks), so the S x S score matrix
             is never materialized. On TPU the Pallas kernel in
             ``repro.kernels.flash_attention`` replaces this (same math).
  * decode — single query token against the KV cache: two einsums + softmax.
  * paged  — the serving decode step: each row writes its new line in
             place in the carried stacked cache and a Pallas kernel reads
             the row's lines through its block table (GQA K/V:
             ``paged_decode``; MLA latent lines: ``paged_mla_decode``).
  * cross  — encoder-decoder cross attention (full or cached decode).

KV caches use ring-buffer indexing when capacity < logical position count.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (KeyGen, apply_rope, dense_init, rms_norm,
                                 yarn_mscale)
from repro.models.state import (KV_HEAD_AXIS, KV_LINE_AXIS, mla_line_width,
                                stacked_latent_pool_view, stacked_pool_view)

Array = jax.Array

NEG_INF = -1e30

# Kernel backend switch: "jnp" (portable), "pallas" (compiled Mosaic
# kernels, TPU only), "interpret" (the Pallas kernel bodies in interpret
# mode, for CPU tests) or "auto" (pallas on TPU, jnp elsewhere).
KERNEL_BACKEND = "auto"


def set_kernel_backend(name: str):
    global KERNEL_BACKEND
    assert name in ("jnp", "pallas", "interpret", "auto")
    KERNEL_BACKEND = name


def kernel_backend() -> str:
    """The resolved backend: "jnp", "pallas" or "interpret"."""
    if KERNEL_BACKEND == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return KERNEL_BACKEND


def prefill_kernel(q_len: int, history: int = 0) -> str:
    """Backend of the prefill attention over ``q_len`` new tokens after
    ``history`` cached ones: the Pallas flash kernel takes a fresh span
    of whole 128-token tiles, everything else the jnp scan."""
    backend = kernel_backend()
    if backend == "jnp" or history or q_len % 128:
        return "jnp"
    return backend


# ---------------------------------------------------------------------------
# Chunked flash attention (pure jnp, differentiable)
# ---------------------------------------------------------------------------


def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (keeps scan shapes exact)."""
    c = min(n, target)
    while n % c:
        c -= 1
    return c


def flash_attention(
    q: Array,            # (B, Sq, H, hd)
    k: Array,            # (B, Skv, KVH, hd)
    v: Array,            # (B, Skv, KVH, hd)
    *,
    causal: bool,
    scale: float,
    window: Optional[int] = None,
    q_offset: int = 0,   # absolute position of q[0] relative to k[0]
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> Array:
    """Online-softmax attention over KV chunks. Never materializes SxS.
    Supports distinct value head dim (MLA: qk=192, v=128).

    GSPMD-friendly by construction (§Perf iteration 1): the head dim H is
    never split — GQA is expressed as a broadcast of K/V from KVH to H
    heads, which XLA fuses into the dot. Splitting H into (KVH, G) defeated
    sharding propagation and silently replicated attention across the mesh
    (SPMD "involuntary full rematerialization"). Explicit constraints pin
    batch to the data axis and heads to the model axis (uneven head counts
    are padded by GSPMD).
    """
    from repro import sharding
    B, Sq, H, hd = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    dv = v.shape[3]
    G = H // KVH
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Skv, kv_chunk)
    nq, nk = Sq // qc, Skv // kc

    def expand(t, d_last):
        # (B, Skv, KVH, d) -> (B, nk, kc, H, d): GQA broadcast, fused by XLA
        t = t.astype(jnp.float32).reshape(B, nk, kc, KVH, 1, d_last)
        t = jnp.broadcast_to(t, (B, nk, kc, KVH, G, d_last))
        return t.reshape(B, nk, kc, H, d_last)

    qf = q.astype(jnp.float32).reshape(B, nq, qc, H, hd)
    qf = sharding.constrain(qf, "batch", None, None, "model", None)
    kf = sharding.constrain(expand(k, hd), "batch", None, None, "model", None)
    vf = sharding.constrain(expand(v, dv), "batch", None, None, "model", None)
    q_pos = q_offset + jnp.arange(Sq).reshape(nq, qc)
    k_pos = jnp.arange(Skv).reshape(nk, kc)

    def q_block(qb, qp, kv_lo, kv_hi):
        """qb (B,qc,H,hd); scans ONLY kv blocks [kv_lo, kv_hi)."""

        def kv_step(carry, xs):
            m, l, acc = carry
            kb, vb, kp = xs  # (B, kc, H, hd/dv), (kc,)
            s = jnp.einsum("bqhd,bchd->bhqc", qb, kb) * scale
            mask = jnp.ones((qp.shape[0], kp.shape[0]), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= qp[:, None] - kp[None, :] < window
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqc,bchd->bhqd", p, vb)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kf[:, kv_lo:kv_hi].swapaxes(0, 1),
             vf[:, kv_lo:kv_hi].swapaxes(0, 1), k_pos[kv_lo:kv_hi]))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,H,qc,dv)
        return out.transpose(0, 2, 1, 3)                # (B,qc,H,dv)

    if causal and nq > 1:
        # causal block-skip (§Perf iteration 6): q blocks are grouped into
        # <=8 statically-unrolled BANDS; each band lax.maps its q blocks
        # over only the kv range any of them can see. Removes most of the
        # ~2x masked-block matmul waste without exploding HLO size
        # (residual waste ~ 1/(2*bands) ~ 6%).
        n_bands = min(nq, 8)
        per = -(-nq // n_bands)
        outs = []
        for b0 in range(0, nq, per):
            b1 = min(nq, b0 + per)
            q_end = q_offset + b1 * qc                   # static
            kv_hi = min(nk, -(-q_end // kc))
            kv_lo = 0
            if window is not None:
                kv_lo = max(0, (q_offset + b0 * qc - window + 1) // kc)
            band = jax.lax.map(
                lambda args, lo=kv_lo, hi=kv_hi: q_block(args[0], args[1],
                                                         lo, hi),
                (qf[:, b0:b1].swapaxes(0, 1), q_pos[b0:b1]))
            outs.append(band.swapaxes(0, 1))             # (B,nb,qc,H,dv)
        out = jnp.concatenate(outs, axis=1).reshape(B, Sq, H, dv)
    else:
        out = jax.lax.map(
            lambda args: q_block(args[0], args[1], 0, nk),
            (qf.swapaxes(0, 1), q_pos))                 # (nq,B,qc,H,dv)
        out = out.swapaxes(0, 1).reshape(B, Sq, H, dv)
    return out.astype(q.dtype)


class PagedDecode:
    """Paged-decode context threaded through the layer stack.

    The decode batch is *compacted*: row ``i`` of the activations is
    request slot ``slots[i]`` of the (full, ``num_slots``-row) cache
    state, and its K/V is read back through ``tables[i]`` — physical
    line-block ids into the pool view of one layer's cache
    (``PagedStore.pool_view`` layout: the head-major ``(B, KVH, W, hd)``
    leaf reshaped to ``(B, KVH, W/block_lines, block_lines, hd)``, block
    id ``slot * W/block_lines + j``).  Replica and free slots are simply
    absent from ``slots``, so they cost nothing.

    The layer scan hands each layer the segment's whole stacked K/V and
    :meth:`at_layer` names the layer (``layer``, a traced index into the
    stack) that attention writes and reads.
    """

    __slots__ = ("slots", "tables", "block_lines", "layer")

    def __init__(self, slots: Array, tables: Array, block_lines: int,
                 layer: Optional[Array] = None):
        self.slots = slots            # (Bc,) int32 — state rows of the batch
        self.tables = tables          # (Bc, max_blocks) int32 pool block ids
        self.block_lines = block_lines
        self.layer = layer            # int32 index into the stacked state

    def at_layer(self, layer: Array) -> "PagedDecode":
        return PagedDecode(self.slots, self.tables, self.block_lines, layer)


def decode_attention(
    q: Array,            # (B, 1, H, hd)
    k_cache: Array,      # (B, KVH, W, hd)
    v_cache: Array,      # (B, KVH, W, hd)
    *,
    scale: float,
    valid: Array,        # (W,) bool or (B, W) bool — which slots are live
) -> Array:
    B, _, H, hd = q.shape
    KVH = k_cache.shape[1]
    G = H // KVH
    qf = q.astype(jnp.float32).reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,bkwd->bkgw", qf, k_cache.astype(jnp.float32)) * scale
    if valid.ndim == 1:
        valid = valid[None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bkwd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring-buffer cache helpers
# ---------------------------------------------------------------------------


def ring_write(cache: Array, values: Array, t: Array, capacity: int,
               axis: int = 1) -> Array:
    """Write values at logical positions [t, t+S) modulo capacity along
    the line ``axis`` of ``cache`` (``values`` has S lines on the same
    axis; batch is axis 0 of both).

    ``t`` may be a scalar clock (shared by the batch — prefill) or a (B,)
    per-request clock (continuous batching decode)."""
    S = values.shape[axis]
    lead = (slice(None),) * axis
    if S >= capacity:
        # keep only the last `capacity` entries, already aligned to slots
        vals = values[lead + (slice(S - capacity, None),)]
        pos = (t + S - capacity + jnp.arange(capacity)) % capacity
        return cache.at[lead + (pos,)].set(vals)
    if jnp.ndim(t) == 0:
        pos = (t + jnp.arange(S)) % capacity
        return cache.at[lead + (pos,)].set(values)

    def write_one(c, val, tt):
        pos = (tt + jnp.arange(S)) % capacity
        return c.at[lead[1:] + (pos,)].set(val)

    return jax.vmap(write_one)(cache, values, t)


def _write_kv(state: dict, k: Array, v: Array, t: Array) -> dict:
    """Ring-write the (B, S, KVH, hd) K/V projections at [t, t+S) into
    the head-major (B, KVH, W, hd) cache."""
    cap = state["k"].shape[KV_LINE_AXIS]
    k, v = (jnp.moveaxis(x, 2, KV_HEAD_AXIS) for x in (k, v))
    return dict(state,
                k=ring_write(state["k"], k, t, cap, axis=KV_LINE_AXIS),
                v=ring_write(state["v"], v, t, cap, axis=KV_LINE_AXIS))


def ring_valid(t_next: Array, capacity: int) -> Array:
    """Valid-slot mask after t_next tokens written into a ring of size cap.
    Scalar t -> (cap,); per-request (B,) t -> (B, cap)."""
    n_valid = jnp.minimum(t_next, capacity)
    if jnp.ndim(t_next) == 0:
        return jnp.arange(capacity) < n_valid
    return jnp.arange(capacity)[None] < n_valid[:, None]


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig, dtype, cross: bool = False):
    kg = KeyGen(key)
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(kg(), d, h * hd, dtype),
        "wk": dense_init(kg(), d, kvh * hd, dtype),
        "wv": dense_init(kg(), d, kvh * hd, dtype),
        "wo": dense_init(kg(), h * hd, d, dtype),
    }
    return p


def gqa_forward(
    cfg: ModelConfig,
    params,
    x: Array,                       # (B, S, D)
    *,
    mode: str,                      # "full" | "decode"
    positions: Array,               # (S,) absolute positions (or (B,S))
    state=None,                     # KV cache dict or None
    t: Optional[Array] = None,      # scalar clock (decode / cache writes)
    window: Optional[int] = None,
    update_cache: bool = False,
    causal: bool = True,
    history: int = 0,               # static: cached KV rows [0, history)
                                    # precede this chunk (chunked prefill)
    paged: Optional[PagedDecode] = None,  # compacted block-table decode
) -> Tuple[Array, Optional[dict]]:
    B, S, D = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(hd)
    q = (x @ params["wq"]).reshape(B, S, h, hd)
    k = (x @ params["wk"]).reshape(B, S, kvh, hd)
    v = (x @ params["wv"]).reshape(B, S, kvh, hd)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if mode == "full" and history:
        # chunked-prefill continuation: this chunk's queries attend to
        # the previously cached rows (already roped at their absolute
        # positions) plus the chunk itself; new rows land at [t, t+S).
        assert state is not None and causal
        def cached(c):
            # (B, KVH, W, hd) rows [0, history) -> (B, history, KVH, hd)
            rows = jax.lax.slice_in_dim(c, 0, history, axis=KV_LINE_AXIS)
            return jnp.moveaxis(rows, KV_HEAD_AXIS, 2).astype(k.dtype)

        k_all = jnp.concatenate([cached(state["k"]), k], axis=1)
        v_all = jnp.concatenate([cached(state["v"]), v], axis=1)
        out = flash_attention(q, k_all, v_all, causal=True, scale=scale,
                              window=window, q_offset=history)
        new_state = state
        if update_cache:
            new_state = _write_kv(state, k, v, t if t is not None
                                  else jnp.int32(history))
    elif mode == "full":
        backend = prefill_kernel(S)
        if backend != "jnp":
            from repro.kernels.flash_attention import flash_attention_pallas
            out = flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                         window=window,
                                         interpret=backend == "interpret")
        else:
            out = flash_attention(q, k, v, causal=causal, scale=scale,
                                  window=window)
        new_state = state
        if update_cache and state is not None:
            new_state = _write_kv(state, k, v,
                                  t if t is not None else jnp.int32(0))
    elif mode == "decode" and paged is not None:
        # paged hot path (ISSUE 5): the batch is compacted to the active
        # primary slots and ``state`` is the segment's whole stacked K/V
        # (R, B, KVH, W, hd), carried through the layer scan.  Each row's
        # new line is written in place at (layer, slot, :, t mod W) — one
        # dynamic-update-slice per row; a scatter would relayout the whole
        # stack — and the kernel reads the stack as R*B pages through the
        # row's block table shifted to this layer's pages.  It fetches
        # the table's blocks (the bucket covering the row's lines), never
        # the dense window of every slot.
        assert state is not None and t is not None and S == 1
        assert paged.layer is not None, "paged decode needs the layer scan"
        from repro import sharding
        from repro.kernels.decode_attention import paged_decode_attention
        # mesh serving (repro.meshserve): pin the compacted query batch's
        # head dim to the slice's model axis so the per-head block gather
        # below stays shard-local (no-op without an active mesh)
        q = sharding.constrain(q, "batch", None, "model", None)
        _, nslots, _, cap, _ = state["k"].shape
        pos = t % cap
        kc, vc = state["k"], state["v"]
        for i in range(B):
            at = (paged.layer, paged.slots[i], 0, pos[i], 0)
            kc = jax.lax.dynamic_update_slice(
                kc, k[i, 0, None, None, :, None].astype(kc.dtype), at)
            vc = jax.lax.dynamic_update_slice(
                vc, v[i, 0, None, None, :, None].astype(vc.dtype), at)
        bl = paged.block_lines
        tables = paged.tables + paged.layer * (nslots * (cap // bl))
        lengths = jnp.minimum(t + 1, cap)
        out = paged_decode_attention(
            q, stacked_pool_view(kc, bl), stacked_pool_view(vc, bl),
            tables, lengths, scale=scale, backend=kernel_backend())
        new_state = dict(state, k=kc, v=vc)
    elif mode == "decode":
        assert state is not None and t is not None
        new_state = _write_kv(state, k, v, t)
        valid = ring_valid(t + S, state["k"].shape[KV_LINE_AXIS])
        out = decode_attention(q, new_state["k"], new_state["v"],
                               scale=scale, valid=valid)
    else:
        raise ValueError(mode)

    out = out.reshape(B, S, h * hd) @ params["wo"]
    return out, new_state


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------


def init_cross_attn(key, cfg: ModelConfig, dtype):
    return init_gqa(key, cfg, dtype)


def cross_attn_forward(
    cfg: ModelConfig,
    params,
    x: Array,                 # (B, S, D) decoder states
    *,
    enc_out: Optional[Array],  # (B, S_src, D) or None when cached
    state=None,               # holds cached xk/xv after prefill
    precompute: bool = False,
):
    B, S, D = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(hd)
    q = (x @ params["wq"]).reshape(B, S, h, hd)
    if precompute or "xk" not in (state or {}):
        assert enc_out is not None
        k = (enc_out @ params["wk"]).reshape(B, -1, kvh, hd)
        v = (enc_out @ params["wv"]).reshape(B, -1, kvh, hd)
        if state is not None:
            state = dict(state, xk=k, xv=v)
    else:
        k, v = state["xk"], state["xv"]
    out = flash_attention(q, k, v, causal=False, scale=scale)
    out = out.reshape(B, S, h * hd) @ params["wo"]
    return out, state


# ---------------------------------------------------------------------------
# MLA (DeepSeek Multi-head Latent Attention)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig, dtype):
    kg = KeyGen(key)
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk, rope, nope, vd = m.qk_head_dim, m.qk_rope_head_dim, m.qk_nope_head_dim, m.v_head_dim
    return {
        "wq_a": dense_init(kg(), d, m.q_lora_rank, dtype),
        "norm_q": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": dense_init(kg(), m.q_lora_rank, h * qk, dtype),
        "wkv_a": dense_init(kg(), d, m.kv_lora_rank + rope, dtype),
        "norm_kv": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": dense_init(kg(), m.kv_lora_rank, h * (nope + vd), dtype),
        "wo": dense_init(kg(), h * vd, d, dtype),
    }


def _mla_qkv_latent(cfg, params, x, positions):
    """Shared projections: roped q (split nope/rope), normed latent, roped
    shared key-rope.  The rope dims rotate in rotate-half pairs (the
    published code interleaves them; random weights cannot tell the
    two apart), with YaRN's frequencies where the config has them."""
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.num_heads
    q = (x @ params["wq_a"])
    q = rms_norm(q, params["norm_q"], cfg.rms_norm_eps) @ params["wq_b"]
    q = q.reshape(B, S, h, m.qk_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.yarn)
    kv = x @ params["wkv_a"]
    c_kv, k_rope = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = rms_norm(c_kv, params["norm_kv"], cfg.rms_norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.yarn)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale of MLA scores: ``qk_head_dim ** -0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim) ** 2`` where the rope is scaled."""
    scale = cfg.mla.qk_head_dim ** -0.5
    if cfg.yarn is not None:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def _mla_decode_paged(paged: PagedDecode, state, line, q_line, t, scale,
                      lat: int):
    """Paged hot path, as GQA's: ``state["c_kv"]`` is the segment's
    stacked line leaf (R, B, W, width), carried through the layer
    scan.  Each row's new line is written in place at (layer, slot, t mod
    W) — one dynamic-update-slice per row — and the kernel reads the
    stack as R*B pages through the row's table shifted to this layer,
    each line block once for every head.  Returns the latent output
    (B, H, lat) and the state."""
    assert line.shape[1] == 1
    assert paged.layer is not None, "paged decode needs the layer scan"
    from repro.kernels.decode_attention import paged_mla_decode
    stack = state["c_kv"]
    _, nslots, cap, _ = stack.shape
    pos = t % cap
    for i in range(line.shape[0]):
        stack = jax.lax.dynamic_update_slice(
            stack, line[i, 0, None, None, None].astype(stack.dtype),
            (paged.layer, paged.slots[i], pos[i], 0))
    bl = paged.block_lines
    tables = paged.tables + paged.layer * (nslots * (cap // bl))
    ctx_lat = paged_mla_decode(
        q_line, stacked_latent_pool_view(stack, bl), tables,
        jnp.minimum(t + 1, cap), lat=lat, scale=scale,
        backend=kernel_backend())
    return ctx_lat, dict(state, c_kv=stack)


def _mla_decode_dense(state, line, q_line, t, scale, lat: int):
    """Dense oracle path over every slot's whole window.  Sharding
    (§Perf iteration 5): the line cache shards its SEQ dim over the model
    axis (all heads share the latent, so head-sharding it is
    impossible); q/scores replicate heads and the softmax/read
    contractions psum tiny partials instead of all-gathering the cache
    every layer."""
    from repro import sharding as _sh
    cap = state["c_kv"].shape[1]
    cache = _sh.constrain(ring_write(state["c_kv"], line, t, cap),
                          "batch", "model", None)
    q_line = _sh.constrain(q_line, "batch", None, None)
    lines = cache.astype(jnp.float32)
    s = jnp.einsum("bhc,bwc->bhw", q_line.astype(jnp.float32), lines)
    valid = ring_valid(t + line.shape[1], cap)
    if valid.ndim == 1:
        valid = valid[None]
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s * scale, NEG_INF),
                       axis=-1)
    ctx_lat = jnp.einsum("bhw,bwl->bhl", p, lines[..., :lat])
    return ctx_lat, dict(state, c_kv=cache)


def mla_forward(
    cfg: ModelConfig,
    params,
    x: Array,
    *,
    mode: str,
    positions: Array,
    state=None,
    t: Optional[Array] = None,
    window: Optional[int] = None,
    update_cache: bool = False,
    causal: bool = True,
    history: int = 0,
    paged: Optional[PagedDecode] = None,
):
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.num_heads
    nope, rope, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    scale = mla_scale(cfg)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(cfg, params, x, positions)

    lat = m.kv_lora_rank
    # one cache line per token (``state["c_kv"]``): the normed latent, the
    # roped key shared by every head, zeros to the line width
    pad = mla_line_width(cfg) - lat - rope
    line = jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((B, S, pad), c_kv.dtype)], -1)

    if mode == "full":
        # materialized path (prefill/train): expand latent to per-head K/V
        kvb = (c_kv @ params["wkv_b"]).reshape(B, S, h, nope + vd)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, h, rope))], -1)
        if history:
            # chunked-prefill continuation: expand the cached latent rows
            # [0, history) (already normed + roped) the same way
            assert state is not None and causal
            hist = state["c_kv"][:, :history].astype(c_kv.dtype)
            kvb_h = (hist[..., :lat] @ params["wkv_b"]).reshape(
                B, history, h, nope + vd)
            k_h = jnp.concatenate(
                [kvb_h[..., :nope],
                 jnp.broadcast_to(hist[:, :, None, lat:lat + rope],
                                  (B, history, h, rope))], -1)
            k = jnp.concatenate([k_h, k], axis=1)
            v = jnp.concatenate([kvb_h[..., nope:], v], axis=1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        out = flash_attention(q, k, v, causal=causal, scale=scale,
                              window=window, q_offset=history)
        new_state = state
        if update_cache and state is not None:
            cap = state["c_kv"].shape[1]
            t0 = t if t is not None else jnp.int32(0)
            new_state = dict(state, c_kv=ring_write(state["c_kv"], line, t0,
                                                    cap))
    elif mode == "decode":
        # absorbed path: W_UK folds into q, so the scores and the read-back
        # stay in latent space and every head reads the same lines; W_UV
        # then lifts the latent output
        assert state is not None and t is not None
        wkv_b = params["wkv_b"].reshape(lat, h, nope + vd)
        w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
        q_lat = jnp.einsum("bshn,lhn->bhl", q_nope, w_uk,
                           preferred_element_type=jnp.float32)
        q_line = jnp.concatenate(
            [q_lat, q_rope[:, 0].astype(jnp.float32),
             jnp.zeros((B, h, pad), jnp.float32)], -1)      # (B,H,width)
        if paged is not None:
            ctx_lat, new_state = _mla_decode_paged(
                paged, state, line, q_line.astype(x.dtype), t, scale, lat)
        else:
            ctx_lat, new_state = _mla_decode_dense(
                state, line, q_line, t, scale, lat)
        out = jnp.einsum("bhl,lhv->bhv", ctx_lat, w_uv,
                         preferred_element_type=jnp.float32)
        out = out[:, None].astype(x.dtype)  # (B,1,H,vd)
    else:
        raise ValueError(mode)

    out = out.reshape(B, S, h * vd) @ params["wo"]
    return out, new_state


# ---------------------------------------------------------------------------
# Unified entry
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype):
    if cfg.attention_kind == "mla":
        return init_mla(key, cfg, dtype)
    return init_gqa(key, cfg, dtype)


def attention_forward(cfg: ModelConfig, params, x, **kw):
    if cfg.attention_kind == "mla":
        return mla_forward(cfg, params, x, **kw)
    return gqa_forward(cfg, params, x, **kw)
