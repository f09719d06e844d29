"""DeepSeek-V3's block (latent attention, noaux_tc routing over held
experts, a shared expert) on the serving path, against the benchmark's
plain float32 reference (``benchmarks/chip/configs/mla_moe.py``) at a
tiny size with seeded random weights."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import ServeSpec, build_cluster
from repro.configs import get_config
from repro.kvstore import PagedStore
from repro.kvstore.base import LineCosts
from repro.models import decode_step_paged, init_params, init_state
from repro.models import prefill_batched
from repro.models.attention import set_kernel_backend
from repro.models.ffn import dense_ffn
from repro.models.moe import (_route, _routed_held, _routed_sorted, init_moe,
                              moe_forward)
from repro.serving.request import Request

REF_PATH = (Path(__file__).resolve().parent.parent / "benchmarks" / "chip"
            / "configs" / "mla_moe.py")

TINY = {
    "name": "tiny-mla-moe", "paper": "test", "reference": "mla_moe",
    "hidden_size": 256, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 96, "kv_lora_rank": 64,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 32, "v_head_dim": 32,
    "intermediate_size": 512, "moe_intermediate_size": 64,
    "hidden_act": "silu", "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 8, "topk_group": 4,
    "first_k_dense_replace": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "vocab_size": 512, "tie_word_embeddings": False,
    "torch_dtype": "float32", "assumed": {"router_experts": 32},
}

#: float32 program against the float32 reference: the same arithmetic in
#: another order (absorbed vs expanded attention, fused vs per-expert
#: products) differs by about 1e-5 on logits of magnitude about 4
LOGIT_TOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("mla_moe_ref", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    """(config, weights): the program's init with a random selection
    bias and norm weights away from 1, so neither is a no-op."""
    cfg = ref.program_config(TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def perturb(path, a):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(jax.random.PRNGKey(1),
                               len(jax.tree_util.keystr(path)))
        if name == "router_bias":
            return jax.random.normal(k, a.shape) * 0.3
        if name.startswith("norm") or name == "final_norm":
            return a + 0.1 * jax.random.normal(k, a.shape)
        return a

    return cfg, jax.tree_util.tree_map_with_path(perturb, params)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_paged_decode_logits_match_reference(ref, model, backend):
    """Prefill into a bucket, merge into a slot of the paged store, then
    decode through the block tables: logits at every position equal the
    reference's full forward pass."""
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (40,), 0,
                                         cfg.vocab_size))
    full = ref.logits(TINY, params, jnp.asarray(toks))
    store = PagedStore(cfg, 4, 64, block_lines=16)
    P, slot = 20, 2
    fresh = init_state(cfg, 1, 32)
    set_kernel_backend(backend)
    try:
        lg, fresh = prefill_batched(
            cfg, params, jnp.asarray(toks[None, :32]).at[:, P:].set(0),
            fresh, jnp.asarray([P]))
        errs = [float(jnp.abs(lg[0] - full[P - 1]).max())]
        store.merge_slot_rows(slot, fresh, 0, 32)
        per = store.line_blocks_per_slot
        tables = jnp.asarray(np.arange(slot * per, (slot + 1) * per,
                                       dtype=np.int32)[None])
        state = store.state
        for i in range(P, len(toks)):
            lg, state = decode_step_paged(
                cfg, params, jnp.asarray(toks[None, i:i + 1]), state,
                jnp.asarray([i], jnp.int32), jnp.asarray([slot]), tables,
                store.block_lines)
            errs.append(float(jnp.abs(lg[0] - full[i]).max()))
    finally:
        set_kernel_backend("auto")
    assert max(errs) < LOGIT_TOL, errs


def test_served_tokens_are_the_reference_greedy_tokens(ref, model,
                                                      monkeypatch):
    """``build_cluster`` (AcceLLM pair, paged decode through the store,
    interpret-mode kernel): at every served position the served token is
    the reference's best, within the float32 tolerance on logits; the
    mirror's spans carry the latent lines' bytes."""
    cfg, params = model
    monkeypatch.setattr(obs, "RING", obs.Ring())
    set_kernel_backend("interpret")
    try:
        cluster = build_cluster(
            ServeSpec(arch=cfg.name, reduced=False, policy="accellm",
                      n_instances=2, num_slots=2, kv_capacity=64,
                      temperature=0.0), cfg=cfg, params=params)
        assert all(e.use_paged_decode for e in cluster.engines)
        rng = np.random.default_rng(3)
        reqs = [Request(prompt_len=n, max_new_tokens=m,
                        prompt_tokens=rng.integers(0, cfg.vocab_size, (1, n),
                                                   dtype=np.int32))
                for n, m in ((13, 9), (21, 6))]
        for r in reqs:
            cluster.submit(r)
        cluster.run(max_steps=200)
    finally:
        set_kernel_backend("auto")
    for r in reqs:
        assert len(r.output_tokens) == r.max_new_tokens
        seq = np.concatenate([np.asarray(r.prompt_tokens).reshape(-1),
                              np.asarray(r.output_tokens)])
        lg = np.asarray(ref.logits(TINY, params, jnp.asarray(seq[:-1])))
        at = lg[r.prompt_len - 1:]
        served = np.asarray(r.output_tokens)
        gap = at.max(-1) - at[np.arange(len(served)), served]
        assert gap.max() < LOGIT_TOL, gap
    line = LineCosts.from_config(cfg).line_bytes
    mirrored = [a["bytes"] for name, _, _, a in obs.RING.events()
                if name == "kv.mirror"]
    assert mirrored and all(b > 0 and b % line == 0 for b in mirrored)


def _noaux_tc_numpy(logits, bias, n_group, topk_group, top_k, scale):
    """DeepSeek-V3's noaux_tc gate, transcribed loop by loop in numpy."""
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    T, E = scores.shape
    per = E // n_group
    weights = np.zeros((T, top_k))
    ids = np.zeros((T, top_k), np.int64)
    for t in range(T):
        choice = scores[t] + bias
        group = [np.sort(choice[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(n_group)]
        kept = np.argsort(group)[::-1][:topk_group]
        masked = np.full(E, -np.inf)
        for g in kept:
            masked[g * per:(g + 1) * per] = choice[g * per:(g + 1) * per]
        ids[t] = np.argsort(masked)[::-1][:top_k]
        w = scores[t, ids[t]]
        weights[t] = w / w.sum() * scale
    return weights, ids


def test_noaux_tc_routing_matches_numpy(ref, model):
    cfg, _ = model
    m = cfg.moe
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(k1, (64, cfg.d_model))
    router = jax.random.normal(k2, (cfg.d_model, m.num_experts)) * 0.06
    bias = jax.random.normal(k3, (m.num_experts,)) * 0.3
    gates, eidx, _ = _route(m, x, router, bias)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(x @ router)
    w, ids = _noaux_tc_numpy(logits, np.asarray(bias, np.float64),
                             m.n_group, m.topk_group, m.top_k,
                             m.routed_scale)
    np.testing.assert_array_equal(np.sort(np.asarray(eidx), -1),
                                  np.sort(ids, -1))
    order = np.argsort(np.asarray(eidx), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(w, np.argsort(ids, -1), -1), rtol=1e-5)


def test_held_expert_shares_add_up_to_the_uncut_layer(model):
    """Four chips holding experts [0,8), [8,16), [16,24), [24,32): their
    routed parts, plus the shared expert once, equal the layer that
    holds all 32."""
    cfg, _ = model
    uncut = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, experts_held=0))
    params = init_moe(jax.random.PRNGKey(5), uncut, jnp.float32)
    params["router_bias"] = jax.random.normal(jax.random.PRNGKey(6),
                                              (cfg.moe.num_experts,)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg.d_model))
    whole, _ = moe_forward(uncut, params, x, dropless=True)
    routed = {k: v for k, v in params.items() if k != "shared"}
    parts = jnp.zeros_like(x)
    held = cfg.moe.experts_held
    for lo in range(0, cfg.moe.num_experts, held):
        # this chip's experts renumbered from 0: rotate the router's
        # outputs so its range comes first, as each chip's layer sees it
        share = dict(routed)
        for k in ("w_gate", "w_up", "w_down"):
            share[k] = params[k][lo:lo + held]
        share["router"] = jnp.roll(params["router"], -lo, axis=1)
        share["router_bias"] = jnp.roll(params["router_bias"], -lo)
        y, _ = moe_forward(cfg, share, x, dropless=True)
        parts = parts + y
    shared = dense_ffn(cfg, params["shared"], x)
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


@pytest.mark.parametrize("held,top_k", [(4, 4), (8, 4), (8, 2), (32, 4)])
def test_dropless_forms_agree_and_ignore_the_batch(model, held, top_k):
    """The dense form over the held experts (served where a layer holds
    no more experts than a token chooses) and the sorted ragged form
    (served where it holds more) give the same routed output, and a
    token's output does not move with what shares its batch."""
    cfg, _ = model
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=held, top_k=top_k))
    params = init_moe(jax.random.PRNGKey(8), cfg, jnp.float32)
    params["router_bias"] = jax.random.normal(jax.random.PRNGKey(9),
                                              (cfg.moe.num_experts,)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(10), (40, cfg.d_model))
    dense = np.asarray(_routed_held(cfg, params, x))
    ragged = np.asarray(_routed_sorted(cfg, params, x))
    assert np.abs(dense).max() > 0.1           # some held expert chosen
    np.testing.assert_allclose(ragged, dense, rtol=1e-5, atol=1e-5)
    # the first five tokens alone, and behind 35 others
    for form, whole in ((_routed_held, dense), (_routed_sorted, ragged)):
        alone = np.asarray(form(cfg, params, x[:5]))
        behind = np.asarray(form(cfg, params, jnp.roll(x, -5, 0)))[-5:]
        np.testing.assert_allclose(alone, whole[:5], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(behind, whole[:5], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("arch,layers", [("deepseek-v3-671b", 61),
                                         ("deepseek-v3.l5e8", 5)])
def test_line_costs_at_published_widths(ref, arch, layers):
    """A latent line is 512 + 64 bf16 values: 1,152 B per layer."""
    if arch == "deepseek-v3-671b":
        cfg = get_config(arch)
    else:
        import json
        cfg = ref.program_config(json.loads(
            (REF_PATH.parent / f"{arch}.json").read_text()))
    assert cfg.num_layers == layers
    assert LineCosts.from_config(cfg).line_bytes == 1152 * layers
