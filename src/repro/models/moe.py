"""Mixture-of-Experts FFN with TPU-native expert parallelism.

Token dispatch is sort-free scatter/gather (argsort ranking + capacity drop),
not the dense one-hot-einsum "dropping" formulation — the einsum form counts
T*E*C*d MAC FLOPs in HLO and would poison the roofline analysis.

Distribution strategies (chosen automatically from the active ShardCtx):
  local — no mesh (CPU smoke tests): all experts on one device.
  a2a   — tokens re-shard over the `model` axis; dispatch buffers exchanged
          with two all_to_alls (classic expert parallelism). Used when the
          sequence dim divides the model axis (train / prefill).
  psum  — every model shard computes its local expert slice over all tokens
          of its data shard and partial outputs are all-reduced. Used for
          decode steps (few tokens, weight-bound) where an a2a schedule
          would be latency-dominated anyway.

Serving (``dropless``) drops no token, so a request's output does not
depend on what shares its batch; capacity dropping is for training only.
Without a mesh, a layer holding no more experts than a token chooses
(``held <= top_k``) computes every held expert over every token, each
token's part weighted by its gate (``_routed_held``); one holding more
sorts the tokens' choices by expert and runs them as one ragged product
(``_routed_sorted``), the work of ``top_k`` experts per token.  On a
mesh the two strategies above serve with a capacity no expert can
overflow.

A layer holds the weights of experts ``[0, experts_held)`` of the
router's ``num_experts`` (one chip's share under expert parallelism):
routing runs over all of them and experts held elsewhere add nothing.

Shared experts (DeepSeek) and the dense residual MLP (Arctic) run outside
the routed path as plain TP-sharded dense FFNs.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.common import KeyGen, dense_init, swiglu
from repro.models.ffn import dense_ffn, init_dense_ffn

Array = jax.Array


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    kg = KeyGen(key)
    d, f, e = cfg.d_model, m.expert_d_ff, m.held
    assert cfg.activation == "swiglu", "routed experts implemented for swiglu"
    scale = d ** -0.5
    def ew(k_, a, b):
        return (jax.random.normal(k_, (e, a, b), jnp.float32) * scale).astype(dtype)
    p = {
        "router": dense_init(kg(), d, m.num_experts, jnp.float32),
        "w_gate": ew(kg(), d, f),
        "w_up": ew(kg(), d, f),
        "w_down": (jax.random.normal(kg(), (e, f, d), jnp.float32)
                   * f ** -0.5).astype(dtype),
    }
    if m.selection_bias:
        p["router_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.num_shared_experts:
        sf = (m.shared_d_ff or f) * m.num_shared_experts
        p["shared"] = init_dense_ffn(kg(), cfg, sf, dtype)
    if m.dense_residual_d_ff:
        p["dense_residual"] = init_dense_ffn(kg(), cfg, m.dense_residual_d_ff, dtype)
    return p


# ---------------------------------------------------------------------------
# Dispatch primitives (pure local math)
# ---------------------------------------------------------------------------


def _route(m: MoEConfig, x2: Array, router: Array,
           bias: Optional[Array] = None):
    """x2 (T, d) -> gates (T,k), expert ids (T,k), router probs (T,E).

    Scores are the ``softmax`` or ``sigmoid`` of the router's logits.
    Experts are chosen by score (+ ``bias``: DeepSeek-V3's noaux_tc)
    within the ``topk_group`` groups whose two best choice scores sum
    highest, and weighted by their scores without the bias, normalised
    to sum 1, then scaled by ``routed_scale``."""
    # float32 throughout (a TPU's default f32 product rounds to bf16)
    logits = jnp.matmul(x2.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if m.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        probs = jax.nn.sigmoid(logits)
    choice = probs if bias is None else probs + bias.astype(jnp.float32)
    if m.n_group > 1:
        T, E = choice.shape
        grouped = choice.reshape(T, m.n_group, E // m.n_group)
        best = jax.lax.top_k(grouped, min(2, E // m.n_group))[0].sum(-1)
        _, gidx = jax.lax.top_k(best, m.topk_group)
        kept = jax.nn.one_hot(gidx, m.n_group, dtype=jnp.bool_).any(1)
        choice = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(T, E)
    _, eidx = jax.lax.top_k(choice, m.top_k)
    gates = jnp.take_along_axis(probs, eidx, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates * m.routed_scale, eidx, probs


def _ranks_of(e_flat: Array, num_experts: int) -> Array:
    """Within-expert arrival rank of each flat assignment (stable)."""
    n = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    rank_sorted = jnp.arange(n) - start[sorted_e]
    return jnp.zeros_like(e_flat).at[order].set(rank_sorted)


def _fill_buffer(x2: Array, tok: Array, slot: Array, num_slots: int) -> Array:
    """Scatter token vectors into dispatch buffer; slot == num_slots drops."""
    buf = jnp.zeros((num_slots + 1, x2.shape[1]), x2.dtype)
    return buf.at[slot].set(x2[tok], mode="drop")[:num_slots]


def _expert_ffn(params, xs: Array) -> Array:
    """xs (E_loc, C, d) -> (E_loc, C, d); local expert slice of the weights."""
    h = swiglu(jnp.einsum("ecd,edf->ecf", xs, params["w_gate"]),
               jnp.einsum("ecd,edf->ecf", xs, params["w_up"]))
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def _combine(y_flat: Array, slot: Array, gates: Array, T: int, k: int) -> Array:
    """Gather per-assignment outputs back and mix with gate weights."""
    d = y_flat.shape[-1]
    y_pad = jnp.concatenate([y_flat, jnp.zeros((1, d), y_flat.dtype)], 0)
    contrib = y_pad[slot]                                   # (T*k, d)
    g = gates.reshape(-1, 1).astype(jnp.float32)
    return (contrib.astype(jnp.float32) * g).reshape(T, k, d).sum(1)


def _aux_loss(eidx: Array, probs: Array, num_experts: int, coef: float) -> Array:
    tk = eidx.size
    counts = jnp.zeros((num_experts,), jnp.float32).at[eidx.reshape(-1)].add(1.0)
    f = counts / tk
    p_mean = probs.mean(0)
    return num_experts * jnp.sum(f * p_mean) * coef


def _capacity(tokens: int, k: int, num_experts: int, cf: float) -> int:
    return max(1, math.ceil(tokens * k * cf / num_experts))


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------


def _routed_local(cfg: ModelConfig, params, x2: Array) -> Tuple[Array, Array]:
    m = cfg.moe
    T = x2.shape[0]
    E = m.held
    gates, eidx, probs = _route(m, x2, params["router"],
                                params.get("router_bias"))
    C = _capacity(T, m.top_k, m.num_experts, m.capacity_factor)
    e_flat = eidx.reshape(-1)
    ranks = _ranks_of(e_flat, m.num_experts)
    keep = (ranks < C) & (e_flat < E)
    slot = jnp.where(keep, e_flat * C + ranks, E * C)
    tok = jnp.arange(T * m.top_k) // m.top_k
    xs = _fill_buffer(x2, tok, slot, E * C).reshape(E, C, -1)
    ys = _expert_ffn(params, xs)
    y = _combine(ys.reshape(E * C, -1), slot, gates, T, m.top_k)
    return y, _aux_loss(eidx, probs, m.num_experts, m.router_aux_loss_coef)


def _routed_held(cfg: ModelConfig, params, x2: Array) -> Array:
    """Dropless: every held expert over every token, each token's part
    weighted by its gate for that expert (0 where it did not choose
    it).  x2 (T, d) -> (T, d) in float32."""
    m = cfg.moe
    gates, eidx, _ = _route(m, x2, params["router"],
                            params.get("router_bias"))
    # (T, held): ids of experts held elsewhere one-hot to a zero row
    held_gates = jnp.einsum("tk,tke->te", gates,
                            jax.nn.one_hot(eidx, m.held, dtype=jnp.float32))
    h = swiglu(jnp.einsum("td,edf->tef", x2, params["w_gate"]),
               jnp.einsum("td,edf->tef", x2, params["w_up"]))
    h = h * held_gates[:, :, None].astype(h.dtype)
    return jnp.einsum("tef,efd->td", h, params["w_down"],
                      preferred_element_type=jnp.float32)


def _routed_sorted(cfg: ModelConfig, params, x2: Array) -> Array:
    """Dropless: each token's choices of held experts, sorted by expert,
    through one ragged product per weight; choices of experts held
    elsewhere sort last, outside every group, and add nothing.
    x2 (T, d) -> (T, d) in float32."""
    m = cfg.moe
    T, k = x2.shape[0], m.top_k
    E = m.held
    gates, eidx, _ = _route(m, x2, params["router"],
                            params.get("router_bias"))
    e_flat = eidx.reshape(-1)
    here = e_flat < E
    group = jnp.where(here, e_flat, E)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=E + 1)[:E].astype(jnp.int32)
    xs = x2[order // k]
    h = swiglu(jax.lax.ragged_dot(xs, params["w_gate"], sizes),
               jax.lax.ragged_dot(xs, params["w_up"], sizes))
    ys = jax.lax.ragged_dot(h, params["w_down"], sizes,
                            preferred_element_type=jnp.float32)
    # back to (token, choice) order; summing over a token's k choices
    # in a fixed order keeps the result independent of the batch
    ys = ys[jnp.argsort(order)].reshape(T, k, -1)
    ys = jnp.where(here.reshape(T, k, 1), ys, 0.0)
    return (ys * gates[..., None]).sum(1)


def _routed_psum(cfg: ModelConfig, params, x_loc: Array, model_axis: str,
                 mean_axes: Tuple[str, ...], dropless: bool = False
                 ) -> Tuple[Array, Array]:
    """Per-shard local experts over all local tokens; all-reduce partials.
    ``dropless``: room for every token in every expert."""
    m = cfg.moe
    B, S, d = x_loc.shape
    T = B * S
    x2 = x_loc.reshape(T, d)
    E_loc = params["w_gate"].shape[0]
    midx = jax.lax.axis_index(model_axis)
    gates, eidx, probs = _route(m, x2, params["router"],
                                params.get("router_bias"))
    C = T if dropless else _capacity(T, m.top_k, m.num_experts,
                                     m.capacity_factor)
    e_flat = eidx.reshape(-1)
    ranks = _ranks_of(e_flat, m.num_experts)
    e_local = e_flat - midx * E_loc
    keep = (e_local >= 0) & (e_local < E_loc) & (ranks < C)
    slot = jnp.where(keep, e_local * C + ranks, E_loc * C)
    tok = jnp.arange(T * m.top_k) // m.top_k
    xs = _fill_buffer(x2, tok, slot, E_loc * C).reshape(E_loc, C, -1)
    ys = _expert_ffn(params, xs)
    y = _combine(ys.reshape(E_loc * C, -1), slot, gates, T, m.top_k)
    y = jax.lax.psum(y, model_axis)
    aux = _aux_loss(eidx, probs, m.num_experts, m.router_aux_loss_coef)
    if mean_axes:
        aux = jax.lax.pmean(aux, mean_axes)
    return y.reshape(B, S, d), aux


def _routed_a2a(cfg: ModelConfig, params, x_loc: Array, model_axis: str,
                mean_axes: Tuple[str, ...], model_size: int,
                dropless: bool = False) -> Tuple[Array, Array]:
    """Tokens sharded over the model axis; two all_to_alls (classic EP).
    ``dropless``: room for every local token in every expert."""
    m = cfg.moe
    B, S_loc, d = x_loc.shape
    T = B * S_loc
    x2 = x_loc.reshape(T, d)
    E, M = m.held, model_size
    E_loc = E // M
    gates, eidx, probs = _route(m, x2, params["router"],
                                params.get("router_bias"))
    C = T if dropless else _capacity(T, m.top_k, m.num_experts,
                                     m.capacity_factor)
    e_flat = eidx.reshape(-1)
    ranks = _ranks_of(e_flat, m.num_experts)
    keep = (ranks < C) & (e_flat < E)
    slot = jnp.where(keep, e_flat * C + ranks, E * C)
    tok = jnp.arange(T * m.top_k) // m.top_k
    send = _fill_buffer(x2, tok, slot, E * C)               # (E*C, d)
    send = send.reshape(M, E_loc * C, d)
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0,
                              tiled=False)                  # (M, E_loc*C, d)
    xs = recv.reshape(M, E_loc, C, d).transpose(1, 0, 2, 3).reshape(E_loc, M * C, d)
    ys = _expert_ffn(params, xs)
    back = ys.reshape(E_loc, M, C, d).transpose(1, 0, 2, 3).reshape(M, E_loc * C, d)
    got = jax.lax.all_to_all(back, model_axis, split_axis=0, concat_axis=0,
                             tiled=False)                   # (M, E_loc*C, d)
    y = _combine(got.reshape(E * C, d), slot, gates, T, m.top_k)
    aux = _aux_loss(eidx, probs, m.num_experts, m.router_aux_loss_coef)
    if mean_axes:
        aux = jax.lax.pmean(aux, mean_axes + (model_axis,))
    return y.reshape(B, S_loc, d), aux


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def moe_forward(cfg: ModelConfig, params, x: Array,
                dropless: bool = False) -> Tuple[Array, Array]:
    """x (B, S, d) -> (y (B, S, d), router aux loss scalar).  Serving
    passes ``dropless``; training routes with capacity."""
    ctx = sharding.current()
    B, S, d = x.shape
    m = cfg.moe

    routed_params = {k: params[k] for k in ("router", "router_bias",
                                            "w_gate", "w_up", "w_down")
                     if k in params}

    if ctx.mesh is None or ctx.model_size == 1:
        x2 = x.reshape(B * S, d)
        if not dropless:
            y, aux = _routed_local(cfg, routed_params, x2)
        else:
            routed = _routed_held if m.held <= m.top_k else _routed_sorted
            y, aux = routed(cfg, routed_params, x2), jnp.float32(0.0)
        y = y.reshape(B, S, d)
    else:
        M = ctx.model_size
        batch_shardable = B % ctx.batch_size_divisor == 0
        b_spec = ctx.batch_axes if batch_shardable else None
        seq_shardable = S % M == 0 and S >= M
        strategy = ctx.moe_strategy
        if strategy == "auto":
            strategy = "a2a" if seq_shardable else "psum"
        mean_axes = tuple(ctx.batch_axes) if batch_shardable else ()
        espec = P(ctx.model_axis, None, None)
        in_specs = (
            P(b_spec, ctx.model_axis if strategy == "a2a" else None, None),
            {k: espec if k.startswith("w_") else P()
             for k in routed_params},
        )
        out_specs = (in_specs[0], P())
        if strategy == "a2a":
            fn = lambda xl, pl: _routed_a2a(cfg, pl, xl, ctx.model_axis,
                                            mean_axes, M, dropless)
        else:
            fn = lambda xl, pl: _routed_psum(cfg, pl, xl, ctx.model_axis,
                                             mean_axes, dropless)
        y, aux = jax.shard_map(
            fn, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(x, routed_params)

    y = y.astype(x.dtype)
    if "shared" in params:
        y = y + dense_ffn(cfg, params["shared"], x)
    if "dense_residual" in params:
        y = y + dense_ffn(cfg, params["dense_residual"], x)
    return y, aux
