"""Top-level model: embedding → (encoder) → layer stack → head.

Pure-functional API used by training, serving, and the dry-run:

  init_params(key, cfg)                          -> params pytree
  forward_train(cfg, params, batch)              -> (logits, aux_loss)
  init_state(cfg, batch, seq_len, long_context)  -> serving state pytree
  prefill(cfg, params, batch, state)             -> (last_logits, state)
  decode_step(cfg, params, tokens, state, t)     -> (logits, state)

``batch`` is a dict: {"tokens": (B,S) int32} plus, per the modality
carve-out, {"patch_embeds": (B,P,E)} for VLMs or {"frames": (B,F,E)} for
audio enc-dec (precomputed frontend embeddings — see DESIGN.md).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import (apply_stack, init_stack, init_stack_state,
                                 layer_specs, plan_segments)
from repro.models.common import (KeyGen, dense_init, dtype_of, embed_init,
                                 rms_norm, sinusoidal_positions)
from repro.models.state import cache_capacity

Array = jax.Array


def _segs(cfg: ModelConfig):
    return plan_segments(layer_specs(cfg))


def _seq_shard_ok(seq_len: int) -> bool:
    """Sequence parallelism gate (§Perf iteration 2 — REFUTED on this
    GSPMD version: the constraints added resharding all-gathers instead of
    converting TP all-reduces to RS+AG; collective term regressed 19.8s ->
    25.9s on phi3 train_4k). Kept behind an env flag for future compilers.
    """
    import os
    if os.environ.get("REPRO_SEQ_PARALLEL", "0") != "1":
        return False
    from repro import sharding as _sh
    c = _sh.current()
    return (c.mesh is not None and c.model_size > 1
            and seq_len % c.model_size == 0 and seq_len >= c.model_size)


def _enc_segs(cfg: ModelConfig):
    return plan_segments(layer_specs(cfg, decoder=False))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig):
    dtype = dtype_of(cfg.dtype)
    kg = KeyGen(key)
    params: Dict[str, Any] = {}
    params["embed"] = embed_init(kg(), cfg.vocab_size, cfg.d_model, dtype)
    _, params["segments"] = init_stack(kg(), cfg, dtype)
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kg(), cfg.d_model, cfg.vocab_size, dtype)
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        e = cfg.frontend.embed_dim
        params["proj"] = {
            "w1": dense_init(kg(), e, cfg.d_model, dtype),
            "w2": dense_init(kg(), cfg.d_model, cfg.d_model, dtype),
        }
    if cfg.is_encoder_decoder:
        enc_cfg = cfg  # same dims per config (asserted)
        assert cfg.encoder.d_model == cfg.d_model
        _, enc_segments = init_stack(kg(), enc_cfg, dtype, decoder=False)
        params["encoder"] = {
            "segments": enc_segments,
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
    if cfg.mtp_depth:
        # DeepSeek-V3 multi-token-prediction head: combine proj + one layer
        from repro.models.blocks import LayerSpec, init_layer
        spec = LayerSpec("attn", False, cfg.d_ff, False)
        params["mtp"] = {
            "norm": jnp.ones((cfg.d_model,), dtype),
            "combine": dense_init(kg(), 2 * cfg.d_model, cfg.d_model, dtype),
            "layer": init_layer(kg(), cfg, spec, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed_tokens(cfg, params, tokens: Array, positions: Array) -> Array:
    x = params["embed"][tokens]
    if cfg.abs_pos == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    return x


def _embed_inputs(cfg, params, batch: Dict[str, Array]) -> Array:
    """Token (+ visual prefix) embedding for decoder-only models."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        pe = batch["patch_embeds"]
        prefix = jax.nn.gelu(pe.astype(jnp.float32)
                             @ params["proj"]["w1"].astype(jnp.float32))
        prefix = (prefix @ params["proj"]["w2"].astype(jnp.float32)
                  ).astype(params["embed"].dtype)
        P = pe.shape[1]
        positions = jnp.arange(P + S)
        tok_x = _embed_tokens(cfg, params, tokens, positions[P:])
        return jnp.concatenate([prefix, tok_x], axis=1), positions
    positions = jnp.arange(S)
    return _embed_tokens(cfg, params, tokens, positions), positions


def _head(cfg, params, x: Array) -> Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ w).astype(jnp.float32)
    return logits * cfg.logit_scale


# ---------------------------------------------------------------------------
# Encoder (enc-dec models)
# ---------------------------------------------------------------------------


def _encode(cfg, params, frames: Array) -> Array:
    """frames: (B, F, E) precomputed frontend embeddings (stub carve-out)."""
    x = frames.astype(params["final_norm"].dtype)
    pos = jnp.arange(x.shape[1])
    if cfg.abs_pos == "sinusoidal":
        x = x + sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
    ctx = {"mode": "full", "positions": pos, "update_cache": False,
           "causal": False}
    segs = _enc_segs(cfg)
    x, _, _ = apply_stack(cfg, segs, params["encoder"]["segments"], x,
                          None, ctx)
    return rms_norm(x, params["encoder"]["final_norm"], cfg.rms_norm_eps)


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------


def forward_train(cfg: ModelConfig, params, batch: Dict[str, Array],
                  remat: bool = True) -> Tuple[Array, Array]:
    """Full causal forward; returns (logits (B,S,V), router aux loss)."""
    segs = _segs(cfg)
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"])
        tokens = batch["tokens"]
        pos = jnp.arange(tokens.shape[1])
        x = _embed_tokens(cfg, params, tokens, pos)
        ctx = {"mode": "full", "positions": pos, "update_cache": False,
               "enc_out": enc_out, "precompute_cross": True,
               "seq_shard": _seq_shard_ok(tokens.shape[1])}
        # training has no cache: cross-attn recomputes K/V from enc_out
        x, _, aux = apply_stack(cfg, segs, params["segments"], x, None, ctx,
                                remat=remat)
    else:
        x, pos = _embed_inputs(cfg, params, batch)
        ctx = {"mode": "full", "positions": pos, "update_cache": False,
               "seq_shard": _seq_shard_ok(x.shape[1])}
        x, _, aux = apply_stack(cfg, segs, params["segments"], x, None, ctx,
                                remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _head(cfg, params, x)
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        logits = logits[:, batch["patch_embeds"].shape[1]:]
    return logits, aux


def forward_mtp(cfg: ModelConfig, params, batch, hidden_no_head=None):
    """DeepSeek-V3 MTP auxiliary logits (depth 1): predict token t+2 from
    [h_t ; emb(token_{t+1})]. Used as an extra training loss term."""
    if not cfg.mtp_depth or "mtp" not in params:
        return None
    from repro.models.blocks import LayerSpec, apply_layer
    tokens = batch["tokens"]
    B, S = tokens.shape
    pos = jnp.arange(S)
    x, _ = _embed_inputs(cfg, params, batch)
    segs = _segs(cfg)
    ctx = {"mode": "full", "positions": pos, "update_cache": False}
    h, _, _ = apply_stack(cfg, segs, params["segments"], x, None, ctx,
                          remat=True)
    # shift: combine h_t with embedding of token_{t+1}
    h_t = h[:, :-1]
    e_next = params["embed"][tokens[:, 1:]]
    comb = jnp.concatenate([h_t, e_next], axis=-1) @ params["mtp"]["combine"]
    comb = rms_norm(comb, params["mtp"]["norm"], cfg.rms_norm_eps)
    spec = LayerSpec("attn", False, cfg.d_ff, False)
    out, _, _ = apply_layer(cfg, spec, params["mtp"]["layer"], comb, None,
                            {"mode": "full", "positions": pos[:-1],
                             "update_cache": False})
    return _head(cfg, params, out)


# ---------------------------------------------------------------------------
# Serving: state init / prefill / decode
# ---------------------------------------------------------------------------


def init_state(cfg: ModelConfig, batch: int, seq_len: int,
               long_context: bool = False, dtype_name: Optional[str] = None):
    dtype = dtype_of(dtype_name or cfg.dtype)
    segs = _segs(cfg)
    cross_len = None
    if cfg.is_encoder_decoder:
        cross_len = cfg.encoder.max_source_positions
    state: Dict[str, Any] = {
        "layers": init_stack_state(cfg, segs, batch, seq_len, long_context,
                                   dtype, cross_len=cross_len),
    }
    if cfg.is_encoder_decoder:
        state["enc_out"] = jnp.zeros(
            (batch, cross_len, cfg.d_model), dtype)
    return state


def prefill(cfg: ModelConfig, params, batch: Dict[str, Array], state,
            long_context: bool = False) -> Tuple[Array, Any]:
    """Process the prompt, fill the caches, return last-token logits."""
    segs = _segs(cfg)
    window = _window(cfg, long_context)
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"])
        state = dict(state, enc_out=enc_out)
        tokens = batch["tokens"]
        pos = jnp.arange(tokens.shape[1])
        x = _embed_tokens(cfg, params, tokens, pos)
        ctx = {"mode": "full", "positions": pos, "update_cache": True,
               "t": jnp.int32(0), "window": window, "enc_out": enc_out,
               "precompute_cross": True,
               "seq_shard": _seq_shard_ok(tokens.shape[1])}
    else:
        x, pos = _embed_inputs(cfg, params, batch)
        ctx = {"mode": "full", "positions": pos, "update_cache": True,
               "t": jnp.int32(0), "window": window,
               "seq_shard": _seq_shard_ok(x.shape[1])}
    layers, = (state["layers"],)
    x, layers, _ = apply_stack(cfg, segs, params["segments"], x, layers, ctx)
    state = dict(state, layers=layers)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.rms_norm_eps)
    return _head(cfg, params, x)[:, 0], state


def prefill_batched(cfg: ModelConfig, params, tokens: Array, state,
                    lengths: Array, long_context: bool = False
                    ) -> Tuple[Array, Any]:
    """Right-padded multi-prompt prefill: ``tokens`` (B, L) with each
    row's true length in ``lengths`` (B,); returns per-row logits at the
    last *real* token and the filled caches.

    Only valid for decoder-only attention stacks (the step-plan layer's
    batched-bucketed path): padded positions write garbage K/V rows
    beyond each row's length, which the per-request decode clocks mask —
    a recurrent block would fold the padding into its state, so hybrid /
    xLSTM / enc-dec models use the unpadded single-prompt path instead.
    """
    segs = _segs(cfg)
    window = _window(cfg, long_context)
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = _embed_tokens(cfg, params, tokens, positions)
    ctx = {"mode": "full", "positions": positions, "update_cache": True,
           "t": jnp.int32(0), "window": window,
           "seq_shard": _seq_shard_ok(S)}
    layers, = (state["layers"],)
    x, layers, _ = apply_stack(cfg, segs, params["segments"], x, layers, ctx)
    state = dict(state, layers=layers)
    last = x[jnp.arange(B), lengths - 1]
    last = rms_norm(last, params["final_norm"], cfg.rms_norm_eps)
    return _head(cfg, params, last), state


def prefill_chunk(cfg: ModelConfig, params, tokens: Array, state,
                  history: int, long_context: bool = False
                  ) -> Tuple[Array, Any]:
    """Resumable chunked prefill (Sarathi-style, executed for real):
    process ``tokens`` (B, C) at absolute positions [history, history+C)
    against a cache whose first ``history`` rows are already filled by
    earlier chunks.  Returns logits at the chunk's last token (only
    meaningful on the final chunk) and the extended caches.

    ``history`` is static (one compile per (chunk shape, cursor));
    attention-only stacks only — recurrent state continuation across
    chunks is not implemented."""
    segs = _segs(cfg)
    window = _window(cfg, long_context)
    B, C = tokens.shape
    positions = history + jnp.arange(C)
    x = _embed_tokens(cfg, params, tokens, positions)
    ctx = {"mode": "full", "positions": positions, "update_cache": True,
           "t": jnp.int32(history), "window": window, "history": history}
    layers, = (state["layers"],)
    x, layers, _ = apply_stack(cfg, segs, params["segments"], x, layers, ctx)
    state = dict(state, layers=layers)
    x = rms_norm(x[:, -1], params["final_norm"], cfg.rms_norm_eps)
    return _head(cfg, params, x), state


def decode_step(cfg: ModelConfig, params, tokens: Array, state, t: Array,
                long_context: bool = False, paged=None
                ) -> Tuple[Array, Any]:
    """One decode step: tokens (B,1) at clock t -> (logits (B,V), state).

    ``t`` is a scalar (homogeneous batch) or (B,) per-request clock
    (continuous batching).  With ``paged`` (a
    :class:`repro.models.attention.PagedDecode` context) the batch is
    compacted and attention reads K/V through block tables — see
    :func:`decode_step_paged`."""
    segs = _segs(cfg)
    window = _window(cfg, long_context)
    if jnp.ndim(t) == 0:
        pos = t + jnp.arange(1)
    else:
        pos = t[:, None] + jnp.arange(1)[None]       # (B, 1)
    x = _embed_tokens(cfg, params, tokens, pos)
    ctx = {"mode": "decode", "positions": pos, "update_cache": True,
           "t": t, "window": window}
    if paged is not None:
        ctx["paged"] = paged
    if cfg.is_encoder_decoder:
        ctx["enc_out"] = state["enc_out"]
    x, layers, _ = apply_stack(cfg, segs, params["segments"], x,
                               state["layers"], ctx)
    state = dict(state, layers=layers)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return _head(cfg, params, x)[:, 0], state


def decode_step_paged(cfg: ModelConfig, params, tokens: Array, state,
                      t: Array, slots: Array, tables: Array,
                      block_lines: int, long_context: bool = False
                      ) -> Tuple[Array, Any]:
    """One *compacted, paged* decode step: ``decode_step``
    with a :class:`~repro.models.attention.PagedDecode` context.

    ``tokens`` (Bc, 1) / ``t`` (Bc,) cover only the active primary
    slots; ``slots`` (Bc,) names each row's slot in the full cache
    state, and ``tables`` (Bc, max_blocks) its physical line blocks in
    the pool view (``PagedStore.decode_block_tables``).  Attention
    writes the new KV line at (slot, t mod W) and reads back through the
    block tables, so replica/free slots and dead cache rows cost
    nothing.  Attention-only decoder stacks (the engine gates on
    ``supports_paged_decode``); MLA writes and reads its latent lines."""
    from repro.models.attention import PagedDecode
    return decode_step(cfg, params, tokens, state, t,
                       long_context=long_context,
                       paged=PagedDecode(slots, tables, block_lines))


def decode_multi(cfg: ModelConfig, params, tokens: Array, state, t: Array,
                 slots: Array, tables: Array, budget: Array, keys: Array,
                 *, block_lines: int, temperature: float = 0.0,
                 eos_token: int = -1, long_context: bool = False
                 ) -> Tuple[Array, Any, Array]:
    """Fused multi-step paged decode: ``steps = keys.shape[0]``
    iterations of :func:`decode_step_paged` as ONE ``lax.scan``, with
    on-device sampling and EOS / budget short-circuiting — a single
    dispatch and a single host transfer for the whole span.

    Per row: ``budget`` is the remaining ``max_new_tokens``; a row goes
    dead once it has emitted its budget or sampled ``eos_token`` (-1 =
    no EOS).  Dead rows freeze: their clock stops, their (frozen) token
    re-writes the same reserved cache line, and their trace repeats the
    last token — the host reads only the first ``emitted[i]`` entries.
    Sampling draws one pre-split key per step (``sampling.decode_keys``)
    folded by slot, so the token stream is bit-identical to ``steps``
    sequential single-step calls, even as rows die mid-scan.

    Returns ``(tokens_all (steps, Bc), state, emitted (Bc,))``."""
    from repro.serving.sampling import sample_slots

    def body(carry, key):
        toks, st, tt, alive, emitted = carry
        logits, st = decode_step_paged(cfg, params, toks, st, tt, slots,
                                       tables, block_lines,
                                       long_context=long_context)
        nxt = sample_slots(logits, key, slots, temperature)
        nxt = jnp.where(alive, nxt, toks[:, 0])
        emitted = emitted + alive.astype(jnp.int32)
        tt = tt + alive.astype(tt.dtype)
        alive = alive & (nxt != eos_token) & (emitted < budget)
        return (nxt[:, None], st, tt, alive, emitted), nxt

    Bc = tokens.shape[0]
    init = (tokens, state, t, jnp.ones((Bc,), bool),
            jnp.zeros((Bc,), jnp.int32))
    (_, state, _, _, emitted), toks_all = jax.lax.scan(body, init, keys)
    return toks_all, state, emitted


def _window(cfg: ModelConfig, long_context: bool) -> Optional[int]:
    if long_context:
        if cfg.family == "hybrid":
            return None  # jamba: full attention, data-sharded KV
        return cfg.sliding_window or cfg.long_context_window
    return cfg.sliding_window
