"""Paged fused decode (ISSUE 5): the block-table attention kernel wired
into the model, decode-batch compaction, and the fused multi-step scan.

Covers the three bit-identity contracts of the issue:

* paged decode (both kernel backends) == the dense full-window oracle,
  for ragged lengths including ring-buffer wrap,
* ``decode_multi(steps=k)`` == k sequential ``decode()`` calls
  token-for-token, including EOS mid-scan,
* the compacted batch == the full batch when replica slots are resident,
* the in-place writes into the stacked K/V == the dense oracle, bit for
  bit over every layer and slot,

plus the planner's fuse gating, the repriced ``PerfModel.plan_time``
(block-granular gather bytes, per-dispatch amortization), and the
fused LiveCluster run.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention_pallas
from repro.models import attention, decode_multi, decode_step, init_params
from repro.models import init_state
from repro.models.attention import set_kernel_backend
from repro.scheduling import LiveCluster
from repro.scheduling.baselines import VLLMScheduler
from repro.serving import InstanceEngine, Request
from repro.serving.sampling import decode_keys, sample_slots
from repro.sim import H100, InstanceSpec, PerfModel
from repro.stepplan import DecodePlan, Planner


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("starcoder2-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk(cfg, i, plen, new=6):
    return Request(prompt_len=plen, max_new_tokens=new,
                   prompt_tokens=jax.random.randint(
                       jax.random.fold_in(jax.random.PRNGKey(23), i),
                       (1, plen), 0, cfg.vocab_size))


def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("kv_capacity", 16)
    return InstanceEngine(cfg, params, **kw)


def _serve(cfg, params, shapes, *, paged, steps=0, backend=None,
           eos=None, **kw):
    """Prefill ``shapes`` = [(plen, new), ...] and decode to completion;
    returns the requests' output token lists."""
    eng = _engine(cfg, params, paged_decode=paged, eos_token=eos, **kw)
    reqs = [_mk(cfg, i, p, n) for i, (p, n) in enumerate(shapes)]
    for r in reqs:
        eng.prefill_request(r)
    if backend is not None:
        set_kernel_backend(backend)
    try:
        for _ in range(200):
            if not eng.slot_req:
                break
            if steps:
                eng.decode_multi(steps=steps)
            else:
                eng.decode()
    finally:
        if backend is not None:
            set_kernel_backend("auto")
    return [r.output_tokens for r in reqs], eng


# ---------------------------------------------------------------------------
# bit-identity: paged vs dense oracle
# ---------------------------------------------------------------------------

# ragged lengths; 12+10 > 16 = kv_capacity exercises the ring wrap
RAGGED = [(5, 10), (12, 10), (9, 4)]


def test_paged_decode_matches_dense_oracle(setup):
    cfg, params = setup
    dense, eng_d = _serve(cfg, params, RAGGED, paged=False)
    assert not eng_d.use_paged_decode
    paged, eng_p = _serve(cfg, params, RAGGED, paged=True)
    assert eng_p.use_paged_decode and eng_p.supports_paged_decode
    assert paged == dense
    # dense pays one host sync per token; compacted single-step too
    # (the fused win is per-plan, tested below)
    assert eng_p.host_syncs == eng_d.host_syncs


def test_paged_decode_matches_dense_pallas_backend(setup):
    """Same contract on the Mosaic kernel body (Pallas interpret mode)."""
    cfg, params = setup
    dense, _ = _serve(cfg, params, RAGGED, paged=False)
    paged, _ = _serve(cfg, params, RAGGED, paged=True, backend="interpret")
    assert paged == dense


def test_decode_multi_matches_sequential_decode(setup):
    cfg, params = setup
    seq, eng_s = _serve(cfg, params, RAGGED, paged=True)
    fused, eng_f = _serve(cfg, params, RAGGED, paged=True, steps=4)
    assert fused == seq
    # host syncs drop from one per token to one per fused plan
    assert eng_f.host_syncs < eng_s.host_syncs


def test_decode_multi_eos_short_circuits_mid_scan(setup):
    cfg, params = setup
    ref, _ = _serve(cfg, params, RAGGED, paged=False)
    eos = ref[1][3]        # a token sampled mid-stream of request 1
    seq, _ = _serve(cfg, params, RAGGED, paged=False, eos=eos)
    fused, _ = _serve(cfg, params, RAGGED, paged=True, steps=6, eos=eos)
    assert fused == seq
    assert any(len(a) < len(b) for a, b in zip(seq, ref)), \
        "EOS never fired mid-stream; the test lost its teeth"


def test_empty_decode_skips_jitted_call(setup):
    """A batch emptied by release-mid-iteration must not pay a dispatch
    (and replica-only instances must not decode their garbage rows)."""
    cfg, params = setup
    eng = _engine(cfg, params, paged_decode=True)
    assert eng.decode() == {} and eng.decode_multi(steps=4) == {}
    assert eng.host_syncs == 0
    src = _engine(cfg, params, paged_decode=True, instance_id=1)
    req = _mk(cfg, 0, 5)
    slot = src.prefill_request(req)
    eng.import_slot(0, src.export_slot(slot), req, as_replica_of=(1, slot))
    assert eng.replica_of and eng.decode() == {}
    assert eng.host_syncs == 0


# ---------------------------------------------------------------------------
# in-place writes into the stacked K/V: every layer, every slot
# ---------------------------------------------------------------------------

W, BL = 32, 8                               # 4 line blocks per slot
SLOTS = jnp.asarray([3, 0, 2], jnp.int32)   # compacted rows, not sorted


def _planted(cfg, layers, num_slots):
    """A ``layers``-deep stack whose cache holds different K/V in every
    layer, slot and line, so a block table offset to the wrong layer
    reads other values."""
    cfg = dataclasses.replace(cfg, num_layers=layers,
                              block_pattern=("attn",) * layers)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = init_state(cfg, num_slots, W)
    leaves, tree = jax.tree_util.tree_flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    state = jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    return cfg, params, state


def _dense_multi(cfg, params, toks, state, t, budget, steps):
    """``decode_multi``'s contract through the dense decode path: each
    step decodes the compacted rows' own caches (gathered by slot) over
    the whole window and writes them back."""
    alive = jnp.ones(t.shape, bool)
    emitted = jnp.zeros(t.shape, jnp.int32)
    out = []
    for _ in range(steps):
        sub = jax.tree_util.tree_map(lambda a: a[:, SLOTS], state["layers"])
        logits, sub = decode_step(cfg, params, toks,
                                  dict(state, layers=sub), t)
        state = dict(state, layers=jax.tree_util.tree_map(
            lambda a, b: a.at[:, SLOTS].set(b), state["layers"],
            sub["layers"]))
        nxt = jnp.where(alive, jnp.argmax(logits, -1).astype(jnp.int32),
                        toks[:, 0])
        emitted = emitted + alive
        t = t + alive
        alive = alive & (emitted < budget)
        out.append(nxt)
        toks = nxt[:, None]
    return jnp.stack(out), state, emitted


def _kernel_dense_attention(q, k_cache, v_cache, *, scale, valid):
    """The dense decode attention in the paged kernel's own arithmetic:
    the kernel over each row's whole window through the identity table,
    in blocks of the paged path's ``block_lines``."""
    return decode_attention_pallas(q, k_cache, v_cache, valid.sum(-1),
                                   scale=scale, block_k=BL, interpret=True)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("t0,budget,steps", [
    # one step: a row starting block 1, a row past the ring's end
    ((5, BL, W + 3), (9, 9, 9), 1),
    # six steps: crosses a block boundary, wraps the ring, and row 1
    # dies after two steps (its frozen line is rewritten from then on)
    ((3, BL - 2, W - 2), (6, 2, 5), 6),
], ids=["step", "multi-row-dies"])
def test_paged_in_place_writes_match_dense_oracle(setup, monkeypatch,
                                                  backend, t0, budget,
                                                  steps):
    cfg, params, state = _planted(setup[0], layers=3, num_slots=4)
    toks = jnp.asarray([[5], [9], [11]], jnp.int32)
    t0, budget = (jnp.asarray(x, jnp.int32) for x in (t0, budget))
    tables = SLOTS[:, None] * (W // BL) + jnp.arange(W // BL)[None]
    keys = jax.random.split(jax.random.PRNGKey(1), steps)
    set_kernel_backend(backend)
    try:
        got = jax.jit(functools.partial(decode_multi, cfg, block_lines=BL))(
            params, toks, state, t0, SLOTS, tables, budget, keys)
    finally:
        set_kernel_backend("auto")
    if backend == "interpret":
        monkeypatch.setattr(attention, "decode_attention",
                            _kernel_dense_attention)
    want = _dense_multi(cfg, params, toks, state, t0, budget, steps)
    assert jnp.array_equal(got[0], want[0])
    assert jnp.array_equal(got[2], want[2])
    assert int(want[2][1]) == min(2, steps)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        assert a.shape[0] == 3 and jnp.array_equal(a, b)
    # the slot outside the batch is untouched
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(state)):
        assert jnp.array_equal(a[:, 1], b[:, 1])


# ---------------------------------------------------------------------------
# compaction: replica/free slots cost nothing and change nothing
# ---------------------------------------------------------------------------


def test_compacted_batch_matches_full_with_replicas_resident(setup):
    cfg, params = setup

    def run(with_replica):
        src = _engine(cfg, params, instance_id=1)
        eng = _engine(cfg, params, paged_decode=True)
        reqs = [_mk(cfg, i, p, n) for i, (p, n) in
                enumerate([(5, 6), (9, 6)])]
        for r in reqs:
            eng.prefill_request(r)
        if with_replica:
            other = _mk(cfg, 7, 11, 6)
            s = src.prefill_request(other)
            eng.import_slot(eng.free_slots()[0], src.export_slot(s),
                            other, as_replica_of=(1, s))
        while eng.slot_req:
            eng.decode_multi(steps=2)
        return [r.output_tokens for r in reqs]

    assert run(True) == run(False)


def test_sample_slots_invariant_to_batch_composition():
    """Per-slot fold_in keys: the token drawn at a slot is the same
    whether the batch holds every slot or only the active subset."""
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(jax.random.PRNGKey(4), (6, 40))
    slots = jnp.arange(6, dtype=jnp.int32)
    full = sample_slots(logits, key, slots, temperature=0.8)
    sel = jnp.asarray([1, 4, 5], jnp.int32)
    compact = sample_slots(logits[sel], key, sel, temperature=0.8)
    assert jnp.array_equal(full[sel], compact)


def test_decode_keys_match_sequential_splits():
    key = jax.random.PRNGKey(9)
    k_seq = key
    subs = []
    chain_ref = [key]
    for _ in range(3):
        k_seq, s = jax.random.split(k_seq)
        subs.append(s)
        chain_ref.append(k_seq)
    chain, stacked = decode_keys(key, 3)
    assert all(jnp.array_equal(a, b) for a, b in zip(chain, chain_ref))
    assert jnp.array_equal(stacked, jnp.stack(subs))


def test_fused_eos_key_consumption_matches_sequential(setup):
    """A fused span that EOS ends early must leave the engine key where
    the per-step path would (sequential decode stops splitting once the
    batch empties): the NEXT request's sampled tokens at temperature > 0
    are identical fused-vs-sequential."""
    cfg, params = setup

    def run(steps, eos):
        eng = _engine(cfg, params, paged_decode=True, temperature=0.7,
                      eos_token=eos, kv_capacity=32)
        first = _mk(cfg, 0, 5, 8)
        eng.prefill_request(first)
        while eng.slot_req:
            eng.decode_multi(steps=steps)
        second = _mk(cfg, 1, 7, 8)
        eng.prefill_request(second)
        while eng.slot_req:
            eng.decode_multi(steps=steps)
        return first.output_tokens, second.output_tokens

    probe, _ = run(1, None)
    eos = probe[3]            # first request dies mid-span under fusing
    seq = run(1, eos)
    fused = run(6, eos)
    assert len(seq[0]) == 4, "EOS did not fire early; test lost its teeth"
    assert fused == seq


# ---------------------------------------------------------------------------
# planner fuse gating
# ---------------------------------------------------------------------------


class _Inst:
    def __init__(self, lines, backlog=0, bl=16):
        self._lines, self._backlog, self._bl = lines, backlog, bl

    def request_lines(self):
        return dict(self._lines)

    def prefill_backlog(self):
        return self._backlog

    def block_lines(self):
        return self._bl


class _View:
    def __init__(self, insts, placements=None):
        self._insts, self._pl = insts, placements or {}

    def instances(self):
        return self._insts

    def placements(self):
        return self._pl


def test_planner_fuses_only_unmirrored_idle_decode():
    from repro.scheduling.actions import Decode
    planner = Planner(allow_mixed=False)
    planner.max_fuse_steps = 8
    # clean decode: fuses up to the horizon
    view = _View([_Inst({1: 10, 2: 12})])
    planner.fuse_horizon = 5
    plan = planner.compile([Decode(0)], view)[0]
    # spans floor to powers of two (the live scan's static shape)
    assert plan.steps == 4 and plan.block_lines == 16
    assert plan.lengths == (10, 12)
    # mirror-bound decode keeps per-step sync points
    view = _View([_Inst({1: 10, 2: 12})], placements={1: (0, 1)})
    assert planner.compile([Decode(0)], view)[0].steps == 1
    # prefill backlog: the role may flip next iteration
    view = _View([_Inst({1: 10}, backlog=2)])
    assert planner.compile([Decode(0)], view)[0].steps == 1
    # fusing disabled: seed behavior
    planner.max_fuse_steps = 1
    view = _View([_Inst({1: 10})])
    assert planner.compile([Decode(0)], view)[0].steps == 1


# ---------------------------------------------------------------------------
# repriced cost model
# ---------------------------------------------------------------------------


def test_plan_time_block_granular_and_amortized():
    cfg = get_config("llama2-70b")
    perf = PerfModel(cfg, InstanceSpec(H100, 4))
    # block-granular gather: lines round up to whole blocks
    exact = perf.plan_time(DecodePlan(0, lengths=(200, 300)))
    paged = perf.plan_time(DecodePlan(0, lengths=(200, 300),
                                      block_lines=16))
    assert paged == perf._decode_iter_time((208, 304))
    assert paged > exact
    # fused steps price each iteration at its grown lengths...
    fused = perf.plan_time(DecodePlan(0, lengths=(200, 300),
                                      block_lines=16, steps=4))
    assert fused == pytest.approx(sum(
        perf._decode_iter_time((200 + j, 300 + j), 16) for j in range(4)))
    # ...and amortize the fixed dispatch overhead once per plan
    disp = PerfModel(cfg, InstanceSpec(H100, 4, dispatch_s=50e-6))
    one = disp.plan_time(DecodePlan(0, lengths=(200,), block_lines=16))
    four = disp.plan_time(DecodePlan(0, lengths=(200,), block_lines=16,
                                     steps=4))
    per_tok_1 = one / 1
    per_tok_4 = four / 4
    assert per_tok_4 < per_tok_1
    assert four - 4 * (one - 50e-6) == pytest.approx(50e-6, rel=1e-6)


# ---------------------------------------------------------------------------
# fused serving on the live cluster
# ---------------------------------------------------------------------------


def test_fused_cluster_matches_unfused_tokens(setup):
    cfg, params = setup

    def run(fuse):
        cluster = LiveCluster(cfg, params, n_instances=1, num_slots=4,
                              kv_capacity=32, policy=VLLMScheduler(),
                              fuse_decode_steps=fuse)
        reqs = [_mk(cfg, i, p, n) for i, (p, n) in
                enumerate([(5, 8), (9, 8), (12, 8)])]
        for r in reqs:
            cluster.submit(r)
        done = cluster.run(max_steps=100)
        assert len(done) == len(reqs)
        return ([r.output_tokens for r in reqs], cluster)

    toks_1, c1 = run(1)
    toks_8, c8 = run(8)
    assert toks_8 == toks_1
    # the fused run executed the same number of decode iterations...
    assert c8.stats["decode_steps"] == c1.stats["decode_steps"]
    # ...in fewer dispatches/host syncs (1/plan, not 1/token)
    assert c8.engines[0].host_syncs < c1.engines[0].host_syncs
    # and the iteration clock stayed comparable
    assert c8.now == c1.now
    for a, b in zip(sorted(c1.finished, key=lambda r: r.rid),
                    sorted(c8.finished, key=lambda r: r.rid)):
        assert a.finish_time == b.finish_time


def test_fused_cluster_eos_mid_span_finish_times(setup):
    """A request sampling EOS mid-fused-span must report the iteration
    it really finished, not the end of the fused block."""
    cfg, params = setup

    def run(fuse, eos):
        cluster = LiveCluster(cfg, params, n_instances=1, num_slots=4,
                              kv_capacity=32, policy=VLLMScheduler(),
                              eos_token=eos, fuse_decode_steps=fuse)
        reqs = [_mk(cfg, i, p, 8) for i, p in enumerate([5, 9, 12])]
        for r in reqs:
            cluster.submit(r)
        cluster.run(max_steps=100)
        return [(r.output_tokens, r.finish_time) for r in reqs]

    ref = run(1, None)
    eos = ref[1][0][3]                 # fires mid-stream of request 1
    unfused = run(1, eos)
    fused = run(8, eos)
    assert fused == unfused
    assert any(len(t) < len(r[0]) for (t, _), r in zip(unfused, ref)), \
        "EOS never fired mid-stream; the test lost its teeth"


def test_sim_fused_decode_plans(setup):
    """The sim backend compiles and prices fused DecodePlans when its
    adapter opts in (same knob as LiveCluster.fuse_decode_steps)."""
    from repro.sim import Simulator
    from repro.sim.policies import VLLMPolicy
    from repro.sim.workload import SimRequest

    def run(fuse):
        pol = VLLMPolicy(fuse_decode_steps=fuse)
        pol.planner.trace = []
        perf = PerfModel(get_config("llama2-70b"), InstanceSpec(H100, 4))
        sim = Simulator(pol, perf, n_instances=1, max_batch=8)
        reqs = [SimRequest(rid=i, arrival=0.0, prompt_len=64,
                           decode_len=12) for i in range(3)]
        done = sim.run(list(reqs))
        return reqs, done, pol.planner.trace

    reqs1, done1, _ = run(1)
    reqs8, done8, trace = run(8)
    assert len(done8) == len(done1) == 3
    assert all(r.generated == 12 for r in reqs8)
    fused_steps = [e[4] for e in trace if e[0] == "decode"]
    assert max(fused_steps) > 1, "no fused decode plan was compiled"
    # the span cap: never past the shortest remaining budget
    assert all(s <= 12 for s in fused_steps)
