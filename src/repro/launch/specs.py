"""ShapeDtypeStruct input specs + PartitionSpec shardings for the dry-run.

``input_specs(cfg, shape)`` builds weak-type-correct ShapeDtypeStruct
stand-ins for every model input (no device allocation — the full configs
are only ever lowered, never materialized).

``param_pspecs`` / ``state_pspecs`` derive PartitionSpec pytrees from leaf
paths + shapes with divisibility-checked rules:
  * TP ("model") on head/ffn/expert dims,
  * FSDP ("data", + "pod" when multi-pod) on a second dim in train mode,
  * batch on ("data") (+"pod"), KV-sequence on "data" for the long-context
    decode of the hybrid arch (sharded-KV decode combine — DESIGN.md §5).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.models.state import HEAD_AXIS, SEQ_AXIS

# ---------------------------------------------------------------------------
# Input ShapeDtypeStructs
# ---------------------------------------------------------------------------


def token_layout(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """Resolve per-arch token/frontend layout for an input shape."""
    B, S = shape.global_batch, shape.seq_len
    out: Dict[str, Any] = {}
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        p = cfg.frontend.num_prefix_tokens
        out["patch_embeds"] = (B, p, cfg.frontend.embed_dim)
        out["text_len"] = max(S - p, 1)
    elif cfg.is_encoder_decoder:
        frames = min(cfg.encoder.max_source_positions, S)
        out["frames"] = (B, frames, cfg.frontend.embed_dim)
        out["text_len"] = S
    else:
        out["text_len"] = S
    return out


def input_specs(cfg: ModelConfig, shape: InputShape, multi_pod: bool = False,
                layout: str = "tp"
                ) -> Tuple[Dict[str, jax.ShapeDtypeStruct], Dict[str, P]]:
    """(ShapeDtypeStructs, PartitionSpecs) for the batch of this shape."""
    if layout == "fsdp":
        b_ax = (("pod", "data", "model") if multi_pod
                else ("data", "model"))
        n_b = _axes_size(multi_pod) * 16
    else:
        b_ax = ("pod", "data") if multi_pod else ("data",)
        n_b = _axes_size(multi_pod)
    B = shape.global_batch
    bspec = b_ax if _div(B, n_b) else None
    layout = token_layout(cfg, shape)
    sds: Dict[str, jax.ShapeDtypeStruct] = {}
    specs: Dict[str, P] = {}

    if shape.kind in ("train", "prefill"):
        sds["tokens"] = jax.ShapeDtypeStruct((B, layout["text_len"]), jnp.int32)
        specs["tokens"] = P(bspec, None)
        if shape.kind == "train":
            sds["labels"] = jax.ShapeDtypeStruct((B, layout["text_len"]),
                                                 jnp.int32)
            specs["labels"] = P(bspec, None)
    else:  # decode: ONE new token + per-request clock
        sds["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        specs["tokens"] = P(bspec, None)
        sds["t"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        specs["t"] = P(bspec)

    if "patch_embeds" in layout and shape.kind in ("train", "prefill"):
        sds["patch_embeds"] = jax.ShapeDtypeStruct(layout["patch_embeds"],
                                                   jnp.bfloat16)
        specs["patch_embeds"] = P(bspec, None, None)
    if "frames" in layout and shape.kind in ("train", "prefill"):
        sds["frames"] = jax.ShapeDtypeStruct(layout["frames"], jnp.bfloat16)
        specs["frames"] = P(bspec, None, None)
    return sds, specs


def _axes_size(multi_pod: bool) -> int:
    return 32 if multi_pod else 16


def _div(n: int, k: int) -> bool:
    return n % k == 0 and n >= k


# ---------------------------------------------------------------------------
# Param PartitionSpecs (path+shape rules)
# ---------------------------------------------------------------------------

# column-parallel (shard OUTPUT dim on model)
_COL = re.compile(
    r"(wq|wk|wv|wq_b|wkv_a|wq_a|wkv_b|w_gate|w_up|w_z|w_in|in_proj|x_proj|"
    r"combine|w1)$")
# row-parallel (shard INPUT dim on model)
_ROW = re.compile(r"(wo|w_down|out_proj|dt_w|w2)$")
_EXPERT = re.compile(r"ffn/(w_gate|w_up|w_down)$")
_REPLICATED = re.compile(
    r"(norm|bias|b_i|b_f|b|dt_b|router|logit|w_i|w_f|w_o|A_log|D|r)$")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def pick_layout(cfg: ModelConfig, shape: InputShape) -> str:
    """Per-(arch x shape) parallel layout on the FIXED production mesh.

    §Perf iteration 3 tried pure FSDP/ZeRO-256 for dense training
    (napkin: ~8x less wire traffic) — REFUTED by measurement: at 1
    batch-row per chip GSPMD picks partial-sum TP-like schedules with
    (B,S,D) all-reduces over all 256 chips and re-gathers the stacked
    scan weights per layer step (collective 19.8s -> 73.3s on phi3
    train_4k). The baseline TP16(+FSDP16-on-data) layout stays the best
    known on this mesh; "fsdp" remains selectable for experimentation via
    REPRO_LAYOUT=fsdp."""
    import os
    if (os.environ.get("REPRO_LAYOUT") == "fsdp"
            and shape.kind == "train" and cfg.moe is None):
        return "fsdp"
    return "tp"


def param_pspecs(cfg: ModelConfig, params_shape, *, mode: str,
                 multi_pod: bool = False, layout: str = "tp",
                 model_n: int = 16):
    """mode: "serve" (TP only, replicated over data) or "train" (TP+FSDP).
    layout "fsdp": no tensor parallelism — every matrix shards one dim over
    ALL mesh axes combined (pure FSDP/ZeRO-3 data parallel).
    ``model_n`` is the model-axis width the divisibility rules check
    against — 16 on the fixed production mesh; a mesh-serving slice
    (repro.meshserve) passes its own TP width."""
    fsdp_ax = ("pod", "data") if multi_pod else ("data",)
    fsdp_n = _axes_size(multi_pod)

    if layout == "fsdp":
        all_ax = (("pod", "data", "model") if multi_pod
                  else ("data", "model"))
        all_n = _axes_size(multi_pod) * model_n

        def rule_fsdp(path, leaf) -> P:
            shape_ = leaf.shape
            spec = [None] * len(shape_)
            # shard the largest divisible dim over the full mesh
            order = sorted(range(len(shape_)), key=lambda i: -shape_[i])
            for i in order:
                if _div(shape_[i], all_n):
                    spec[i] = all_ax
                    break
            return P(*spec)

        return jax.tree_util.tree_map_with_path(rule_fsdp, params_shape)

    def rule(path, leaf) -> P:
        ps = _path_str(path)
        shape = leaf.shape
        nd = len(shape)
        spec = [None] * nd

        def try_shard(dim: int, axis, n: int) -> bool:
            if spec[dim] is None and _div(shape[dim], n):
                spec[dim] = axis
                return True
            return False

        is_expert = bool(_EXPERT.search(ps)) and cfg.moe is not None and \
            shape[-3:-2] and nd >= 3 and shape[-3] == cfg.moe.num_experts
        if ps.endswith("embed"):
            try_shard(0, "model", model_n)          # vocab on model
            if mode == "train":
                try_shard(1, fsdp_ax, fsdp_n)
        elif ps.endswith("lm_head"):
            try_shard(nd - 1, "model", model_n)
            if mode == "train":
                try_shard(nd - 2, fsdp_ax, fsdp_n)
        elif is_expert:
            try_shard(nd - 3, "model", model_n)     # expert dim
            if mode == "train":
                try_shard(nd - 2, fsdp_ax, fsdp_n)
        elif ps.endswith("ffn/router"):
            pass                                    # replicated
        elif _COL.search(ps):
            try_shard(nd - 1, "model", model_n)
            if mode == "train" and nd >= 2:
                try_shard(nd - 2, fsdp_ax, fsdp_n)
        elif _ROW.search(ps):
            if nd >= 2:
                try_shard(nd - 2, "model", model_n)
                if mode == "train":
                    try_shard(nd - 1, fsdp_ax, fsdp_n)
        elif ps.endswith("conv_w") and nd >= 2:
            try_shard(nd - 1, "model", model_n)     # (k, d_in)
        elif ps.endswith("conv_b") or ps.endswith("A_log") \
                or ps.endswith("/D") or ps.endswith("dt_b") \
                or ps.endswith("w_o"):
            try_shard(nd - 1 if ps.endswith(("conv_b", "dt_b", "w_o"))
                      else nd - 2, "model", model_n)
        elif ps.endswith("wv") and nd >= 3:
            # xLSTM headwise value proj: shard hd_out — the mLSTM matrix
            # memory C then shards its value dim and the whole time scan
            # runs collective-free (§Perf iteration 4; q/k/n replicated)
            try_shard(nd - 1, "model", model_n)
        elif re.search(r"(wq|wk|r)$", ps) and nd >= 3:
            pass  # replicated: q/k must be whole per chip (C's key dim)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


# ---------------------------------------------------------------------------
# Serving-state PartitionSpecs
# ---------------------------------------------------------------------------


def state_pspecs(cfg: ModelConfig, state_shape, shape: InputShape,
                 *, long_context: bool, multi_pod: bool = False,
                 model_n: int = 16):
    """KV caches: batch on data when divisible; otherwise (long_500k, B=1)
    shard the KV sequence dim on data (sharded-KV decode combine)."""
    b_ax = ("pod", "data") if multi_pod else ("data",)
    b_n = _axes_size(multi_pod)
    B = shape.global_batch
    batch_ok = _div(B, b_n)

    def rule(path, leaf) -> P:
        ps = _path_str(path)
        shape_ = leaf.shape
        nd = len(shape_)
        spec = [None] * nd
        is_enc = ps.endswith("enc_out")
        # batch dim: 1 for layer states (dim0 = repeat), 0 for enc_out
        bdim = 0 if is_enc else 1
        if batch_ok and nd > bdim and _div(shape_[bdim], b_n):
            spec[bdim] = b_ax
        tail = ps.rsplit("/", 1)[-1]
        sd = SEQ_AXIS.get(tail)
        if not batch_ok and sd is not None and nd > sd \
                and _div(shape_[sd], b_n):
            spec[sd] = b_ax                     # shard KV seq over data
        if tail == "c_kv" and nd > sd and spec[sd] is None \
                and _div(shape_[sd], model_n):
            # MLA latent cache: shard the SEQUENCE dim over the model axis
            # (flash-decode-style sharded-KV; §Perf iteration 5). All heads
            # share the latent, so head-sharding the cache is impossible —
            # sequence sharding keeps HBM reads 1/16 per chip and replaces
            # two per-layer latent all-gathers with tiny softmax-combine
            # all-reduces.
            spec[sd] = "model"
        # model-parallel inner dims where divisible
        hd_ = HEAD_AXIS.get(tail)
        if hd_ is not None and nd >= 4 and _div(shape_[hd_], model_n):
            spec[hd_] = "model"                 # kv heads
        if tail == "ssm" and nd >= 3 and _div(shape_[2], model_n):
            spec[2] = "model"                   # mamba d_in
        if tail == "conv" and nd >= 4 and _div(shape_[3], model_n):
            spec[3] = "model"
        if tail == "C" and nd >= 4 and _div(shape_[3], model_n):
            spec[3] = "model"                   # mLSTM key dim
        if tail == "n" and nd >= 4 and _div(shape_[3], model_n):
            spec[3] = "model"
        if is_enc and _div(shape_[-1], model_n):
            spec[-1] = None                     # keep enc_out replicated on d
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, state_shape)
