"""A whole run of the harness at a tiny latent-attention
mixture-of-experts size (``"reference": "mla_moe"``) on the CPU, with
the chip look skipped: a sound program comes out correct, and a fault
planted in the latent decode path underneath comes out not correct."""
import json

import pytest

import run as bench_run

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
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "vocab_size": 512, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "assumed": {"router_experts": 32},
    "serve": {"policy": "accellm", "num_slots": 2, "kv_capacity": 256},
    # the program in bfloat16 reads 0.03-0.06 at this size (0 in
    # float32), the float8 control 0.9-1.6
    "limits": {"logit_gap_max": 0.25, "compared_tokens_min": 20},
}

MIX = {
    "arrival": {"process": "poisson", "rate_per_s": 4.0},
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
               "min": 16, "max": 100},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
               "min": 4, "max": 24},
}

E2E = [{"name": n, "unit": u} for n, u in
       (("ttft_p90_s", "s"), ("tbt_mean_ms", "ms"), ("setup_s", "s"))]


@pytest.fixture(scope="module")
def info(tmp_path_factory):
    mix = tmp_path_factory.mktemp("mix") / "tiny.json"
    mix.write_text(json.dumps(MIX))
    return dict(cell={"name": "tiny-mla", "chips": 1}, cfg=TINY,
                ref=bench_run.load_module(bench_run.HERE / "configs"
                                          / "mla_moe.py"),
                mix=mix, end_to_end=E2E, per_layer=[])


def _run(info, seed=123456789012):
    return bench_run.run(info, seed, 4.0, trace=False)


def test_sound_run_is_correct(info):
    out = _run(info)
    assert out["correct"], out["check"]
    assert out["failed"] == 0
    assert out["check"]["compared_tokens"]["value"] >= 20
    assert set(out["metrics"]) == {m["name"] for m in E2E}


def test_control_comes_out_not_correct_where_the_program_passes(info):
    out = bench_run.run(info, 123456789012, 4.0, trace=False, control=True)
    assert not out["correct"], out["check"]
    gaps = out["notes"]["gaps"]
    assert gaps["logit_gap_max"] <= TINY["limits"]["logit_gap_max"]
    assert gaps["control_gap_max"] > TINY["limits"]["logit_gap_max"]


def test_key_rope_left_out_of_latent_decode(info, monkeypatch):
    """The paged latent decode scores with the latent alone: the shared
    roped key no longer tells positions apart."""
    from repro.kernels import decode_attention as da
    orig = da.paged_mla_decode

    def no_rope(q, *a, lat, **kw):
        return orig(q.at[..., lat:].set(0), *a, lat=lat, **kw)

    monkeypatch.setattr(da, "paged_mla_decode", no_rope)
    assert not _run(info)["correct"]
