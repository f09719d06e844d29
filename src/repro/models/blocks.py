"""Layer composition + segment planning.

A model is a sequence of layers; each layer = pre-norm mixer (attention /
mamba / mLSTM / sLSTM) + optional cross-attention + optional FFN (dense or
MoE), with (optionally depth-scaled) residuals.

Heterogeneous stacks (Jamba's 1:7 interleave, xLSTM's 7:1, DeepSeek's
dense-then-MoE) are compiled into *segments*: maximal periodic runs whose
parameters are stacked along a repeat dim and executed under ``lax.scan`` —
keeping the lowered HLO compact for 61–80-layer models.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mamba as mamba_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import (attention_forward, cross_attn_forward,
                                    init_attention, init_cross_attn)
from repro.models.common import KeyGen, rms_norm
from repro.models.ffn import dense_ffn, init_dense_ffn
from repro.models.moe import init_moe, moe_forward
from repro.models.state import cache_capacity, init_layer_state

Array = jax.Array


class LayerSpec(NamedTuple):
    block: str        # attn | mamba | mlstm | slstm
    is_moe: bool
    d_ff: int         # dense-path d_ff (0 = no FFN sublayer)
    cross: bool       # has cross-attention (enc-dec decoder layers)


class Segment(NamedTuple):
    specs: Tuple[LayerSpec, ...]
    repeats: int
    layer_start: int


def layer_specs(cfg: ModelConfig, decoder: bool = True) -> List[LayerSpec]:
    specs = []
    for i, blk in enumerate(cfg.block_pattern):
        is_moe = cfg.layer_is_moe(i) and blk != "mlstm" and blk != "slstm"
        d_ff = cfg.d_ff
        if cfg.moe is not None and i < cfg.moe.first_dense_layers:
            d_ff = cfg.moe.first_dense_d_ff or cfg.d_ff
        if blk in ("mlstm", "slstm"):
            d_ff = 0
        cross = decoder and cfg.is_encoder_decoder and blk == "attn"
        specs.append(LayerSpec(blk, is_moe, d_ff, cross))
    return specs


def plan_segments(specs: List[LayerSpec], max_period: int = 16) -> List[Segment]:
    """Greedy maximal periodic runs (prefers the longest total run)."""
    segs: List[Segment] = []
    i, L = 0, len(specs)
    while i < L:
        best_p, best_r = 1, 1
        for p in range(1, min(max_period, L - i) + 1):
            r = 1
            while (i + (r + 1) * p <= L
                   and specs[i + r * p: i + (r + 1) * p] == specs[i: i + p]):
                r += 1
            if r >= 2 and p * r > best_p * best_r:
                best_p, best_r = p, r
        segs.append(Segment(tuple(specs[i: i + best_p]), best_r, i))
        i += best_p * best_r
    return segs


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    kg = KeyGen(key)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": jnp.ones((d,), dtype)}
    if spec.block == "attn":
        p["mixer"] = init_attention(kg(), cfg, dtype)
    elif spec.block == "mamba":
        p["mixer"] = mamba_mod.init_mamba(kg(), cfg, dtype)
    elif spec.block == "mlstm":
        p["mixer"] = xlstm_mod.init_mlstm(kg(), cfg, dtype)
    elif spec.block == "slstm":
        p["mixer"] = xlstm_mod.init_slstm(kg(), cfg, dtype)
    if spec.cross:
        p["cross_norm"] = jnp.ones((d,), dtype)
        p["cross"] = init_cross_attn(kg(), cfg, dtype)
    if spec.d_ff or spec.is_moe:
        p["norm2"] = jnp.ones((d,), dtype)
        if spec.is_moe:
            p["ffn"] = init_moe(kg(), cfg, dtype)
        else:
            p["ffn"] = init_dense_ffn(kg(), cfg, spec.d_ff, dtype)
    return p


def _sp(x, ctx):
    """Sequence-parallel residual constraint (§Perf iteration 2): between TP
    blocks the residual stream shards its seq dim over the model axis,
    turning each TP boundary all-reduce into reduce-scatter + all-gather
    and sharding the norms. Enabled by the caller when S divides the mesh."""
    if not ctx.get("seq_shard"):
        return x
    from repro import sharding
    return sharding.constrain(x, "batch", "seq", None)


def apply_layer(cfg: ModelConfig, spec: LayerSpec, params, x: Array,
                state, ctx: Dict[str, Any]) -> Tuple[Array, Any, Array]:
    """Returns (x, new_state, aux_loss)."""
    rs = cfg.residual_scale
    aux = jnp.float32(0.0)
    x = _sp(x, ctx)
    h_in = rms_norm(x, params["norm1"], cfg.rms_norm_eps)
    kw = dict(mode=ctx["mode"], state=state, update_cache=ctx["update_cache"])
    if spec.block == "attn":
        h, new_state = attention_forward(
            cfg, params["mixer"], h_in, positions=ctx["positions"],
            t=ctx.get("t"), window=ctx.get("window"),
            causal=ctx.get("causal", True),
            history=ctx.get("history", 0),
            paged=ctx.get("paged"), **kw)
    elif spec.block == "mamba":
        h, new_state = mamba_mod.mamba_forward(cfg, params["mixer"], h_in, **kw)
    elif spec.block == "mlstm":
        h, new_state = xlstm_mod.mlstm_forward(cfg, params["mixer"], h_in, **kw)
    elif spec.block == "slstm":
        h, new_state = xlstm_mod.slstm_forward(cfg, params["mixer"], h_in, **kw)
    else:
        raise ValueError(spec.block)
    x = x + rs * h

    if spec.cross:
        x = _sp(x, ctx)
        cx = rms_norm(x, params["cross_norm"], cfg.rms_norm_eps)
        h, new_state = _apply_cross(cfg, params["cross"], cx, new_state, ctx)
        x = x + rs * h

    if spec.d_ff or spec.is_moe:
        x = _sp(x, ctx)
        f_in = rms_norm(x, params["norm2"], cfg.rms_norm_eps)
        if spec.is_moe:
            # serving (every call that fills the caches) drops no token
            h, aux = moe_forward(cfg, params["ffn"], f_in,
                                 dropless=ctx["update_cache"])
        else:
            h = dense_ffn(cfg, params["ffn"], f_in)
        x = x + rs * h
    return x, new_state, aux


def _apply_cross(cfg, params, cx, state, ctx):
    h, new_state = cross_attn_forward(
        cfg, params, cx, enc_out=ctx.get("enc_out"), state=state,
        precompute=ctx.get("precompute_cross", False))
    return h, new_state if new_state is not None else state


# ---------------------------------------------------------------------------
# Segment init / apply (stacked params, lax.scan over repeats)
# ---------------------------------------------------------------------------


def init_segment(key, cfg: ModelConfig, seg: Segment, dtype):
    """Params stacked along the repeat dim for each position-in-period."""
    out = {}
    keys = jax.random.split(key, len(seg.specs))
    for j, spec in enumerate(seg.specs):
        layer_keys = jax.random.split(keys[j], seg.repeats)
        out[f"p{j}"] = jax.vmap(
            lambda k: init_layer(k, cfg, spec, dtype))(layer_keys)
    return out


def init_segment_state(cfg: ModelConfig, seg: Segment, batch: int,
                       capacity: int, dtype, cross_len: Optional[int]):
    out = {}
    for j, spec in enumerate(seg.specs):
        one = init_layer_state(
            cfg, spec.block, batch, capacity, dtype,
            cross_len=cross_len if spec.cross else None)
        out[f"p{j}"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (seg.repeats,) + a.shape).copy()
            if seg.repeats > 1 else a[None], one)
    return out


def apply_segment(cfg: ModelConfig, seg: Segment, params, x: Array,
                  seg_state, ctx: Dict[str, Any], remat: bool
                  ) -> Tuple[Array, Any, Array]:
    """Scan the periodic body over the repeat dim."""
    has_state = seg_state is not None
    if has_state and ctx.get("paged") is not None:
        return _apply_segment_paged(cfg, seg, params, x, seg_state, ctx)

    def body(carry, xs):
        xc, aux = carry
        lp, ls = xs if has_state else (xs, None)
        new_states = {}
        for j, spec in enumerate(seg.specs):
            st_j = ls[f"p{j}"] if has_state else None
            xc, st_new, aux_j = apply_layer(cfg, spec, lp[f"p{j}"], xc, st_j, ctx)
            if has_state:
                new_states[f"p{j}"] = st_new
            aux = aux + aux_j
        return (xc, aux), (new_states if has_state else None)

    if remat:
        body = jax.checkpoint(body)

    xs = (params, seg_state) if has_state else params
    (x, aux), states = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
    return x, states, aux


def _apply_segment_paged(cfg: ModelConfig, seg: Segment, params, x: Array,
                         seg_state, ctx: Dict[str, Any]
                         ) -> Tuple[Array, Any, Array]:
    """Paged decode: the stacked K/V rides in the scan's carry and each
    layer updates its lines in place at its layer index.  A step writes
    one line per row and layer; threaded through ``xs``/``ys``, each
    layer's whole cache would be sliced out, relaid out and stacked back
    instead."""
    paged = ctx["paged"]

    def body(carry, xs):
        xc, aux, st = carry
        lp, layer = xs
        lctx = dict(ctx, paged=paged.at_layer(layer))
        st = dict(st)
        for j, spec in enumerate(seg.specs):
            xc, st[f"p{j}"], aux_j = apply_layer(cfg, spec, lp[f"p{j}"], xc,
                                                 st[f"p{j}"], lctx)
            aux = aux + aux_j
        return (xc, aux, st), None

    (x, aux, states), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0), seg_state),
        (params, jnp.arange(seg.repeats, dtype=jnp.int32)))
    return x, states, aux


# ---------------------------------------------------------------------------
# Whole-stack helpers
# ---------------------------------------------------------------------------


def init_stack(key, cfg: ModelConfig, dtype, decoder: bool = True):
    specs = layer_specs(cfg, decoder=decoder)
    segs = plan_segments(specs)
    keys = jax.random.split(key, len(segs))
    return segs, [init_segment(k, cfg, s, dtype) for k, s in zip(keys, segs)]


def init_stack_state(cfg: ModelConfig, segs: List[Segment], batch: int,
                     seq_len: int, long_context: bool, dtype,
                     cross_len: Optional[int] = None):
    cap = cache_capacity(cfg, seq_len, long_context)
    return [init_segment_state(cfg, s, batch, cap, dtype, cross_len)
            for s in segs]


def apply_stack(cfg: ModelConfig, segs: List[Segment], seg_params, x: Array,
                states, ctx: Dict[str, Any], remat: bool = False):
    aux_total = jnp.float32(0.0)
    new_states = []
    for i, seg in enumerate(segs):
        st = states[i] if states is not None else None
        x, st_new, aux = apply_segment(cfg, seg, seg_params[i], x, st, ctx, remat)
        new_states.append(st_new)
        aux_total = aux_total + aux
    return x, (new_states if states is not None else None), aux_total
