"""The generator: the same work at the same times for every seed."""
import json

import numpy as np
import pytest

from chipbench.traffic import Mix

MIXES = ["code", "chat"]


def mix(name):
    from conftest import CHIP
    return Mix.load(CHIP / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    m = mix(name)
    a = m.requests(2**33 + 5, 30.0, 1000)
    b = m.requests(2**33 + 5, 30.0, 1000)
    assert [(r.due_s, r.prompt_len, r.output_len) for r in a] == \
        [(r.due_s, r.prompt_len, r.output_len) for r in b]
    assert all((x.tokens == y.tokens).all() for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_stratified_sizes(name):
    m = mix(name)
    n = m.count(50.0)
    rng = np.random.default_rng(0)
    for seed in rng.integers(0, 2**40, 3):
        reqs = m.requests(int(seed), 50.0, 1000)
        assert len(reqs) == n == round(m.rate_per_s * 50.0)
        assert 0 < reqs[0].due_s and reqs[-1].due_s < 50.0
        assert sorted(r.prompt_len for r in reqs) == \
            sorted(m.lengths("prompt", n).tolist())
        assert sorted(r.output_len for r in reqs) == \
            sorted(m.lengths("output", n).tolist())


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_rate_follow_the_file(name):
    m = mix(name)
    spec = json.loads((__import__("conftest").CHIP / "traffic"
                       / f"{name}.json").read_text())
    n = 4001
    p = m.lengths("prompt", n)
    assert p.min() >= spec["prompt"]["min"]
    assert p.max() <= spec["prompt"]["max"]
    assert np.median(p) == pytest.approx(spec["prompt"]["median"], rel=0.01)
    assert m.gaps(n).mean() == pytest.approx(
        1 / spec["arrival"]["rate_per_s"], rel=0.01)


def test_seeds_differ_only_in_token_ids():
    m = mix("code")
    a = m.requests(7, 30.0, 1000)
    b = m.requests(2**40 + 3, 30.0, 1000)
    assert [(r.due_s, r.prompt_len, r.output_len) for r in a] == \
        [(r.due_s, r.prompt_len, r.output_len) for r in b]
    assert any((x.tokens != y.tokens).any() for x, y in zip(a, b))
