"""Plain reference for the latent-attention mixture-of-experts
configurations (``"reference": "mla_moe"``, DeepSeek-V3), and their
mapping onto the program's config type.

The reference is the published block in straightforward ``jax.numpy``
and float32 at ``highest`` matmul precision, with nothing absorbed or
cached: token embedding; per layer a pre-norm multi-head latent
attention (q = W_qb RMSNorm(W_qa x); the latent c = RMSNorm of the first
``kv_lora_rank`` outputs of W_kva x, expanded to per-head keys and
values by W_kvb; one roped key-rope shared by every head; causal
softmax at scale ``qk_head_dim ** -0.5`` times YaRN's mscale squared),
then a pre-norm SwiGLU FFN: dense for the leading
``first_k_dense_replace`` layers, else the routed experts this chip
holds plus the shared expert; a final norm and the untied head.

Rotary positions: YaRN's blended inverse frequencies (``rope_scaling``)
over the ``qk_rope_head_dim`` dims, in rotate-half pairs.  The published
code interleaves its pairs; random weights cannot tell the two apart.

Routing (``scoring_func`` sigmoid, ``topk_method`` noaux_tc): scores are
sigmoid(x W_r) over all ``assumed.router_experts`` outputs; experts are
chosen by score + selection bias among the ``topk_group`` of ``n_group``
groups whose two best such scores sum highest (the other groups' experts
masked to -inf, as the published inference code does); weights are the
chosen scores without the bias, normalised to sum 1, times
``routed_scaling_factor``.  Only experts ``[0, n_routed_experts)`` are
held: what the others add is left out.  The router's product is not
rounded by ``quant``: a W8A8 path keeps the gate in higher precision.

It imports nothing of the program and reads the benchmark's weight
arrays by the keys the benchmark made them under (``chipbench.weights``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: query rows per attention block (bounds the (heads, rows, keys) scores)
Q_BLOCK = 256


def program_config(c: dict):
    """The configuration as the program takes it."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    YarnConfig)
    if c["topk_method"] != "noaux_tc" or not c["norm_topk_prob"]:
        raise ValueError("the reference routes by noaux_tc with its "
                         "weights normalised only")
    rs = c.get("rope_scaling")
    yarn = None
    if rs is not None and rs.get("type") == "yarn":
        yarn = YarnConfig(
            factor=float(rs["factor"]),
            original_max=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]))
    return ModelConfig(
        name=c["name"], family="moe", source=c["paper"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["v_head_dim"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        attention_kind="mla", rope_theta=float(c["rope_theta"]), yarn=yarn,
        rms_norm_eps=float(c["rms_norm_eps"]), activation="swiglu",
        tie_embeddings=bool(c["tie_word_embeddings"]),
        mla=MLAConfig(q_lora_rank=c["q_lora_rank"],
                      kv_lora_rank=c["kv_lora_rank"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(
            num_experts=c["assumed"]["router_experts"],
            top_k=c["num_experts_per_tok"],
            expert_d_ff=c["moe_intermediate_size"],
            num_shared_experts=c["n_shared_experts"],
            shared_d_ff=c["moe_intermediate_size"],
            first_dense_layers=c["first_k_dense_replace"],
            first_dense_d_ff=c["intermediate_size"],
            scoring=c["scoring_func"], selection_bias=True,
            n_group=c["n_group"], topk_group=c["topk_group"],
            routed_scale=float(c["routed_scaling_factor"]),
            experts_held=c["n_routed_experts"]),
        mtp_depth=0, dtype=c["torch_dtype"])


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(c: dict):
    """YaRN's inverse frequencies of the rope dims (plain rope without
    ``rope_scaling``)."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    half = dim // 2
    inv = 1.0 / base ** (jnp.arange(half, dtype=jnp.float32) / half)
    rs = c.get("rope_scaling")
    if rs is None:
        return inv

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    return inv / rs["factor"] * ramp + inv * (1.0 - ramp)


def _rope(c: dict, x, pos):
    """x (S, heads, rope); rotate-half rotary embedding with YaRN."""
    ang = pos[:, None, None].astype(jnp.float32) * _inv_freq(c)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    rs = c.get("rope_scaling")
    if rs is not None:
        m = (_mscale(rs["factor"], rs["mscale"])
             / _mscale(rs["factor"], rs["mscale_all_dim"]))
        cos, sin = cos * m, sin * m
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _scale(c: dict) -> float:
    s = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    rs = c.get("rope_scaling")
    if rs is not None:
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _f32(w):
    return w.astype(jnp.float32)


def _mm(x, w, quant):
    """x @ w in float32; ``quant`` (or None) rounds both operands."""
    if quant is None:
        return x @ _f32(w)
    return quant.act(x) @ quant.weight(_f32(w))


def _attention(c, x, w, quant):
    S = x.shape[0]
    H, lat = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    eps = float(c["rms_norm_eps"])
    pos = jnp.arange(S)
    q = _mm(_rms(_mm(x, w["wq_a"], quant), _f32(w["norm_q"]), eps),
            w["wq_b"], quant).reshape(S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(c, q[..., nope:], pos)
    kv = _mm(x, w["wkv_a"], quant)
    latent = _rms(kv[:, :lat], _f32(w["norm_kv"]), eps)
    k_rope = _rope(c, kv[:, None, lat:], pos)[:, 0]           # (S, rope)
    kvb = _mm(latent, w["wkv_b"], quant).reshape(S, H, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    qb = Q_BLOCK if S % Q_BLOCK == 0 else S

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb)
        s = (jnp.einsum("qhn,khn->hqk", qn, k_nope)
             + jnp.einsum("qhr,kr->hqk", qr, k_rope)) * _scale(c)
        s = jnp.where((rows[:, None] >= pos[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, H * vd)
    return _mm(o, w["wo"], quant)


def _swiglu(x, w, quant):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"], quant))
               * _mm(x, w["w_up"], quant), w["w_down"], quant)


def route(c: dict, scores_logits, bias):
    """(weights (T, k), expert ids (T, k)) of noaux_tc routing from the
    router's logits (T, E) and the selection bias (E,)."""
    scores = jax.nn.sigmoid(scores_logits)
    choice = scores + bias
    T, E = choice.shape
    G = c["n_group"]
    grouped = choice.reshape(T, G, E // G)
    best = jax.lax.top_k(grouped, min(2, E // G))[0].sum(-1)
    _, gidx = jax.lax.top_k(best, c["topk_group"])
    kept = (jnp.arange(G)[None, None] == gidx[..., None]).any(1)
    choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(choice, c["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, idx, -1)
    weights = weights / weights.sum(-1, keepdims=True)
    return weights * c["routed_scaling_factor"], idx


def _moe(c, x, w, quant):
    held = c["n_routed_experts"]
    weights, idx = route(c, x @ _f32(w["router"]), _f32(w["router_bias"]))
    # each token's weight for each held expert (0 where not chosen)
    gate = (weights[..., None]
            * (idx[..., None] == jnp.arange(held))).sum(1)     # (T, held)

    def mm3(a, m):
        if quant is None:
            return jnp.einsum("td,edf->tef", a, _f32(m))
        return jnp.einsum("td,edf->tef", quant.act(a), quant.weight(_f32(m)))

    h = jax.nn.silu(mm3(x, w["w_gate"])) * mm3(x, w["w_up"])  # (T, held, f)
    h = h * gate[..., None]        # the down projection is linear
    if quant is None:
        y = jnp.einsum("tef,efd->td", h, _f32(w["w_down"]))
    else:
        y = jnp.einsum("tef,efd->td", quant.act(h),
                       quant.weight(_f32(w["w_down"])))
    return y + _swiglu(x, w["shared"], quant)


def _layer(c, x, w, quant):
    eps = float(c["rms_norm_eps"])
    x = x + _attention(c, _rms(x, _f32(w["norm1"]), eps), w["mixer"], quant)
    h = _rms(x, _f32(w["norm2"]), eps)
    ffn = w["ffn"]
    return x + (_moe(c, h, ffn, quant) if "router" in ffn
                else _swiglu(h, ffn, quant))


def logits(c: dict, params, tokens, quant=None):
    """Logits (S, vocab) at every position of one sequence ``tokens``
    (S,).  ``quant``, when given, rounds the operands of every matrix
    product but the router's (``quant.weight``, ``quant.act``); None is
    the reference."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[tokens]
        for seg in params["segments"]:
            for j in range(len(seg)):

                def body(x, w):
                    return _layer(c, x, w, quant), None

                x, _ = jax.lax.scan(body, x, seg[f"p{j}"])
        x = _rms(x, _f32(params["final_norm"]), float(c["rms_norm_eps"]))
        head = (params["embed"].T if c["tie_word_embeddings"]
                else params["lm_head"])
        return _mm(x, head, quant)
