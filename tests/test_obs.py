"""Host spans of the serving loop (repro.obs): one ``serve.step`` per
``LiveCluster.step`` with the documented phases nested inside it,
``sched_time_s`` as the sum of the ``sched.*`` spans, each request's
``request.queued`` wait from submit to the prefill dispatch, a ring that
stays bounded, and recording that changes nothing the program does."""
import dataclasses

import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.meshserve import STATS, MeshPlacement
from repro.models import init_params
from repro.scheduling.accellm import AcceLLMScheduler
from repro.scheduling.live import LiveCluster
from repro.serving import Request

#: the phases PERF.md section 3 documents inside ``serve.step``
PHASES = ("sched.route", "sched.plan", "sched.place", "sched.sync",
          "sched.rebalance", "engine.prefill.prep",
          "engine.prefill.dispatch", "engine.readback", "kv.merge",
          "engine.decode.prep", "engine.decode.dispatch",
          "engine.decode.tokens", "kv.stream", "kv.mirror")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("starcoder2-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n=6, seed=5):
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        plen = 6 + (i % 5)
        toks = jax.random.randint(jax.random.fold_in(key, i), (1, plen),
                                  0, cfg.vocab_size)
        out.append(Request(prompt_len=plen, max_new_tokens=5 + (i % 3),
                           prompt_tokens=toks))
    return out


def _serve(cfg, params, mesh=None):
    """A short AcceLLM serve over one pair; returns the cluster, its
    requests, how many steps ran and the events they recorded."""
    cluster = LiveCluster(cfg, params, n_instances=2, num_slots=4,
                          kv_capacity=64, policy=AcceLLMScheduler(),
                          mesh=mesh)
    reqs = _requests(cfg)
    mark = obs.RING.appended
    steps = 0
    for r in reqs[:3]:
        cluster.submit(r)
    while cluster.pending() and steps < 200:
        if steps == 2:
            for r in reqs[3:]:
                cluster.submit(r)
        cluster.step()
        steps += 1
    assert all(len(r.output_tokens) == r.max_new_tokens for r in reqs)
    return cluster, reqs, steps, obs.RING.events(since=mark)


@pytest.fixture(scope="module")
def served(setup):
    return _serve(*setup)


def test_one_serve_step_per_step(served):
    _, _, steps, events = served
    assert sum(1 for e in events if e[0] == "serve.step") == steps


@pytest.mark.parametrize("phase", PHASES)
def test_phase_is_recorded_inside_serve_step(served, phase):
    _, _, _, events = served
    roots = [(s, s + d) for n, s, d, _ in events if n == "serve.step"]
    found = [(s, s + d) for n, s, d, _ in events if n == phase]
    assert found, f"no {phase} span in a serve with prefill, decode, " \
                  "streaming and mirrors"
    for lo, hi in found:
        assert any(a <= lo and hi <= b for a, b in roots), \
            f"{phase} span outside every serve.step"


def test_readbacks_name_their_site(served):
    _, _, _, events = served
    sites = {e[3].get("site") for e in events if e[0] == "engine.readback"}
    assert sites == {"prefill", "decode"}


def test_sched_time_is_the_sum_of_the_sched_spans(served):
    cluster, _, _, events = served
    total_ns = sum(d for n, _, d, _ in events if n.startswith("sched."))
    assert total_ns > 0
    assert cluster.sched_time_s == pytest.approx(total_ns / 1e9, rel=1e-9)


def test_request_queued_runs_from_submit_to_prefill_dispatch(served):
    _, reqs, _, events = served
    queued = {e[3]["rid"]: e for e in events if e[0] == "request.queued"}
    dispatched = [s + d for n, s, d, _ in events
                  if n == "engine.prefill.dispatch"]
    read = [s for n, s, _, a in events
            if n == "engine.readback" and a["site"] == "prefill"]
    submitted = {e[3]["rid"]: e for e in events if e[0] == "request.submit"}
    assert set(queued) == set(submitted) == {r.rid for r in reqs}
    for r in reqs:
        assert submitted[r.rid][1:3] == (r.submit_ns, 0)
        _, start, dur, _ = queued[r.rid]
        assert start == r.submit_ns
        assert start + dur == r.dispatch_ns
        # stamped after a prefill dispatch returned and before its first
        # tokens were read back, with no other dispatch in between
        last = max(t for t in dispatched if t <= r.dispatch_ns)
        first_read = min(t for t in read if t >= r.dispatch_ns)
        assert not any(last < t < first_read for t in dispatched)
        assert r.submit_ns <= r.dispatch_ns <= r.first_token_ns


def test_a_copy_between_devices_is_one_event_at_full_depth(setup, mesh8):
    """At starcoder2-3b's 30 layers a mirror or stream between two
    devices moves 60 leaves and records one event: nothing nests inside
    a ``kv.mirror`` or ``kv.stream``, so the ring's events per step do
    not grow with depth."""
    cfg, _ = setup
    deep = dataclasses.replace(cfg, num_layers=30,
                               block_pattern=("attn",) * 30)
    params = init_params(jax.random.PRNGKey(0), deep)
    _, _, steps, events = _serve(deep, params,
                                 MeshPlacement.carve(2, tp=1))
    moves = [(s, s + d) for n, s, d, a in events
             if n in ("kv.mirror", "kv.stream")]
    assert moves and all(a["cross"] for n, _, _, a in events
                         if n in ("kv.mirror", "kv.stream"))
    for n, s, d, _ in events:
        if n not in ("kv.mirror", "kv.stream"):
            assert not any(lo <= s and s + d <= hi and (s, s + d) != (lo, hi)
                           for lo, hi in moves), f"{n} inside a KV copy"


@pytest.mark.parametrize("size,n", [(4, 3), (4, 10), (1, 5)])
def test_ring_stays_bounded_and_overwrites_the_oldest(size, n):
    ring = obs.Ring(size)
    for i in range(n):
        ring.append("e", i, 2, {"i": i})
    held = ring.events()
    assert len(held) == min(n, size)
    assert [e[1] for e in held] == list(range(max(0, n - size), n))
    assert [e[1] for e in ring.events(since=n - 1)] == [n - 1]
    assert ring.totals() == {"e": (n, 2 * n / 1e9)}


class _NoSpan:
    """Recording replaced by nothing at all."""
    dur_ns = 0

    def __init__(self, name, **attrs):
        self.attrs = attrs

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("placed", [False, True],
                         ids=["one-device", "two-devices"])
def test_recording_changes_nothing_the_program_does(setup, monkeypatch,
                                                    placed, mesh8):
    cfg, params = setup

    def run():
        STATS.reset()
        mesh = MeshPlacement.carve(2, tp=1) if placed else None
        cluster, reqs, _, events = _serve(cfg, params, mesh)
        return ([r.output_tokens for r in reqs],
                [e.host_syncs for e in cluster.engines],
                [dict(e.programs) for e in cluster.engines],
                STATS.host_copies, STATS.d2d_copies, events)

    *recorded, events = run()
    assert events
    if placed:
        crossed = [e for e in events if e[0] in ("kv.mirror", "kv.stream")]
        assert crossed and all(e[3]["cross"] for e in crossed)
    monkeypatch.setattr(obs, "span", _NoSpan)
    monkeypatch.setattr(obs, "record", lambda *a, **kw: None)
    *bare, events = run()
    assert not events
    assert bare == recorded


@pytest.mark.parametrize("jit,name", [("_jit_prefill_batched",
                                       "prefill_batched"),
                                      ("_jit_decode_multi", "decode_multi")])
def test_served_programs_carry_their_names(served, jit, name):
    """A device trace names each program after its jit: the served
    ones read ``jit_<function>``, not ``jit__unknown``."""
    cluster, _, _, _ = served
    assert getattr(cluster.engines[0], jit).__name__ == name
