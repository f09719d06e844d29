"""A whole run of the harness at a tiny size on the CPU, with the chip
look skipped: a sound program comes out correct, and each fault planted
in the timed path underneath comes out not correct."""
import json

import jax
import jax.numpy as jnp
import pytest

import run as bench_run

TINY = {
    "name": "tiny-dense", "paper": "test", "reference": "dense_gqa",
    "hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
    "hidden_act": "gelu_pytorch_tanh", "vocab_size": 512,
    "rope_theta": 10000.0, "norm_epsilon": 1e-5,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "serve": {"policy": "accellm", "num_slots": 2, "kv_capacity": 256},
    "limits": {"logit_gap_max": 0.05, "compared_tokens_min": 20},
}

MIX = {
    "arrival": {"process": "poisson", "rate_per_s": 4.0},
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
               "min": 16, "max": 100},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
               "min": 4, "max": 24},
}

E2E = [{"name": n, "unit": u} for n, u in
       (("ttft_p90_s", "s"), ("tbt_mean_ms", "ms"), ("tbt_p95_ms", "ms"),
        ("out_tok_s", "tokens/s"), ("setup_s", "s"))]


@pytest.fixture(scope="module")
def info(tmp_path_factory):
    mix = tmp_path_factory.mktemp("mix") / "tiny.json"
    mix.write_text(json.dumps(MIX))
    return dict(cell={"name": "tiny", "chips": 1}, cfg=TINY,
                ref=bench_run.load_module(bench_run.HERE / "configs"
                                          / "dense_gqa.py"),
                mix=mix, end_to_end=E2E, per_layer=[])


def _run(info, seed=123456789012):
    return bench_run.run(info, seed, 4.0, trace=False)


def test_sound_run_is_correct(info):
    out = _run(info)
    assert out["correct"], out["check"]
    assert out["attempted"] > 4
    assert set(out["metrics"]) == {m["name"] for m in E2E}
    assert list(out)[-1] == "check"
    assert out["check"]["compared_tokens"]["value"] >= 20


def test_token_altered_where_produced(info, monkeypatch):
    from repro.serving.engine import InstanceEngine
    orig = InstanceEngine.decode_multi

    def altered(self, *a, **kw):
        out = orig(self, *a, **kw)
        for slot, toks in out.items():
            req = self.slot_req.get(slot)
            if req is None or len(req.output_tokens) != 3:
                continue
            bad = (req.output_tokens[-1] + 1) % self.cfg.vocab_size
            req.output_tokens[-1] = bad
            self.last_tokens[slot] = bad
        return out

    monkeypatch.setattr(InstanceEngine, "decode_multi", altered)
    assert not _run(info)["correct"]


def test_exchange_between_instances_left_out(info, monkeypatch):
    from repro.kvstore.paged import PagedStore
    monkeypatch.setattr(PagedStore, "import_chunk",
                        lambda self, slot, path, chunk: None)
    assert not _run(info)["correct"]


def test_step_returns_state_unchanged(info, monkeypatch):
    from repro.serving.engine import InstanceEngine
    orig = InstanceEngine.decode_multi

    def frozen(self, *a, **kw):
        before = jax.tree_util.tree_map(jnp.copy, self.store.state)
        out = orig(self, *a, **kw)
        self.store.state = before
        return out

    monkeypatch.setattr(InstanceEngine, "decode_multi", frozen)
    assert not _run(info)["correct"]


def test_control_comes_out_not_correct_where_the_program_passes(info):
    """The float8 control, judged by the run's own comparison and limits
    on the same sample, fails; the program's reading there passes."""
    out = bench_run.run(info, 123456789012, 4.0, trace=False, control=True)
    assert not out["correct"], out["check"]
    gaps = out["notes"]["gaps"]
    assert gaps["tokens"] >= TINY["limits"]["compared_tokens_min"]
    assert gaps["logit_gap_max"] <= TINY["limits"]["logit_gap_max"]
    assert gaps["control_gap_max"] > TINY["limits"]["logit_gap_max"]
