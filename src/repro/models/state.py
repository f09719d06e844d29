"""Serving-state pytrees: attention KV caches (dense / ring-buffer / MLA
latent), Mamba states, xLSTM states.

All states are plain dicts (pytrees) so they stack cleanly under the
layer-scan and shard with NamedSharding. Every state dict carries only
arrays; the scalar clock ``t`` lives in the engine, passed per call.

Layout conventions (R = segment repeat dim, added by the model's layer scan):
  attention KV : k,v          (B, KVH, W, HD)    W = cache window capacity
                 (head-major: one head's lines are contiguous, the tile
                 the paged decode kernel DMAs)
  MLA latent   : c_kv         (B, W, kv_lora_rank)
                 k_rope       (B, W, qk_rope_head_dim)
  mamba        : conv         (B, d_conv, d_in)
                 ssm          (B, d_in, d_state)
  mlstm        : C            (B, H, DK, DV)
                 n            (B, H, DK)
                 m            (B, H)
  slstm        : c,n,h        (B, d_in)
                 m            (B, d_in)
  encoder memory (enc-dec)   : enc_out (B, S_src, D) + per-layer cross K/V
                               xk,xv (B, S_src, KVH, HD)
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig

#: head and line axes of one layer's head-major K/V leaf (B, KVH, W, HD)
KV_HEAD_AXIS, KV_LINE_AXIS = 1, 2

# Axes of the *stacked* attention-state leaves (the layer scan prepends R),
# by state key.  Every consumer of the layout (the paged store's row
# copies, the mesh pool's head sharding, the launch-layer partition specs)
# reads these tables.
#: KV line (cache window) axis of the per-token caches
LINE_AXIS = {"k": KV_LINE_AXIS + 1, "v": KV_LINE_AXIS + 1,
             "c_kv": 2, "k_rope": 2}
#: sequence axis: the per-token caches' lines, the cross caches' source
SEQ_AXIS = dict(LINE_AXIS, xk=2, xv=2)
#: KV-head axis of the GQA caches
HEAD_AXIS = {"k": KV_HEAD_AXIS + 1, "v": KV_HEAD_AXIS + 1, "xk": 3, "xv": 3}


def pool_view(cache, block_lines: int):
    """One layer's head-major K/V leaf ``(B, KVH, W, HD)`` as the paged
    block pool ``(B, KVH, W/block_lines, block_lines, HD)`` the decode
    kernel gathers from (a reshape, no copy): block id ``b * W/block_lines
    + j`` is line block ``j`` of batch row ``b``."""
    B, KVH, W, HD = cache.shape
    return cache.reshape(B, KVH, W // block_lines, block_lines, HD)


def line_index(slot, pos, axis: int) -> tuple:
    """Index of lines ``pos`` (array or slice) of batch row ``slot`` in a
    stacked leaf whose line axis is ``axis``."""
    return (slice(None), slot) + (slice(None),) * (axis - 2) + (pos,)


def cache_capacity(cfg: ModelConfig, seq_len: int, long_context: bool) -> int:
    """KV capacity for attention layers: ring-buffer window when the
    long-context sliding-window policy is active, else full seq_len.

    jamba keeps FULL attention KV even at 500k (sharded over the data axis;
    see DESIGN.md §5) because its 9 attention layers make that affordable —
    this exercises the sharded-KV decode-combine path.
    """
    if not long_context:
        if cfg.sliding_window is not None and seq_len > cfg.sliding_window:
            return cfg.sliding_window
        return seq_len
    if cfg.family == "hybrid":
        return seq_len  # jamba: full KV, data-sharded
    win = cfg.sliding_window or cfg.long_context_window
    return min(win, seq_len)


def init_attn_kv(cfg: ModelConfig, batch: int, capacity: int, dtype):
    if cfg.attention_kind == "mla":
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, capacity, m.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, capacity, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, capacity, cfg.head_dim), dtype),
    }


def init_mamba_state(cfg: ModelConfig, batch: int, dtype):
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, mc.d_conv, d_in), dtype),
        "ssm": jnp.zeros((batch, d_in, mc.d_state), jnp.float32),
    }


def xlstm_dims(cfg: ModelConfig, kind: str):
    xc = cfg.xlstm
    if kind == "mlstm":
        d_in = int(xc.proj_factor_mlstm * cfg.d_model)
    else:
        d_in = int(xc.proj_factor_slstm * cfg.d_model)
    head_dim = d_in // cfg.num_heads
    return d_in, head_dim


def init_mlstm_state(cfg: ModelConfig, batch: int):
    d_in, hd = xlstm_dims(cfg, "mlstm")
    h = cfg.num_heads
    k = cfg.xlstm.conv1d_kernel_size
    return {
        "C": jnp.zeros((batch, h, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, h, hd), jnp.float32),
        "m": jnp.zeros((batch, h), jnp.float32),
        "conv": jnp.zeros((batch, k, d_in), jnp.float32),
    }


def init_slstm_state(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    return {
        "c": jnp.zeros((batch, d), jnp.float32),
        "n": jnp.zeros((batch, d), jnp.float32),
        "h": jnp.zeros((batch, d), jnp.float32),
        "m": jnp.zeros((batch, d), jnp.float32),
    }


def init_layer_state(cfg: ModelConfig, kind: str, batch: int, capacity: int,
                     dtype, cross_len: Optional[int] = None):
    """State for one layer of the given mixer kind (no repeat dim)."""
    if kind == "attn":
        st = init_attn_kv(cfg, batch, capacity, dtype)
        if cross_len is not None:  # enc-dec decoder layer: cached cross K/V
            st["xk"] = jnp.zeros(
                (batch, cross_len, cfg.num_kv_heads, cfg.head_dim), dtype)
            st["xv"] = jnp.zeros(
                (batch, cross_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        return st
    if kind == "mamba":
        return init_mamba_state(cfg, batch, dtype)
    if kind == "mlstm":
        return init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return init_slstm_state(cfg, batch)
    raise ValueError(kind)


def state_bytes(state) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state))
