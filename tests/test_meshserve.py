"""Mesh serving (repro.meshserve): tensor-parallel paged decode with
device-to-device redundancy collectives.

Runs on the forced 8-device CPU pod (conftest sets
``--xla_force_host_platform_device_count=8``); the ``mesh8`` fixture
skips everything here when the platform ignored the flag.

Covered invariants:
* model-axis-sharded batched prefill + fused paged decode produce tokens
  bit-identical to a single-device engine (temperature-0 argmax);
* MirrorSync / StreamState between mesh slices move KV as device-to-
  device collectives — the transfer-guard counter proves no host
  round-trip on the serving fast path — and account the SAME bytes as
  the host-copy path and the simulator's ``LineCosts`` pricing;
* a heterogeneous pod (H100-class wide slice + 910B2-class narrow
  slice) drives the unchanged policy kernel to identical decisions on
  the live executor and the simulator adapter (golden trace).
"""
import jax
import pytest

from repro.configs import get_config
from repro.kvstore import LineCosts
from repro.meshserve import STATS, MeshError, MeshPlacement, carve_slices
from repro.models import init_params
from repro.scheduling.accellm import AcceLLMScheduler
from repro.scheduling.live import LiveCluster
from repro.serving import InstanceEngine, Request
from repro.sim import (ASCEND_910B2, H100, AcceLLMPolicy, InstanceSpec,
                       PerfModel, Simulator)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("starcoder2-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_requests(cfg, n, seed=3, steps=5):
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        plen = 6 + (i % 5)
        toks = jax.random.randint(jax.random.fold_in(key, i), (1, plen),
                                  0, cfg.vocab_size)
        reqs.append(Request(prompt_len=plen, max_new_tokens=steps,
                            prompt_tokens=toks))
    return reqs


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------


def test_carve_slices_disjoint(mesh8):
    slices = carve_slices(2, n_instances=3)
    assert [sl.tp for sl in slices] == [2, 2, 2]
    seen = set()
    for sl in slices:
        devs = set(sl.devices)
        assert not (devs & seen), "slices must be disjoint"
        seen |= devs
    # heterogeneous widths carve consecutively too
    wide, narrow = carve_slices([4, 2])
    assert wide.tp == 4 and narrow.tp == 2
    assert not (set(wide.devices) & set(narrow.devices))
    with pytest.raises(MeshError):
        carve_slices(4, n_instances=3)       # 12 devices > 8


def test_model_axis_gating(mesh8, setup):
    cfg, _ = setup
    two, four = carve_slices([2, 4])
    # reduced starcoder2 has 4 query heads: both widths divide
    assert two.model_axis_for(cfg) == "model"
    assert four.model_axis_for(cfg) == "model"
    (three,) = carve_slices(3, n_instances=1)
    assert three.model_axis_for(cfg) is None   # 4 % 3 != 0: replicate


# ---------------------------------------------------------------------------
# bit-identity: sharded prefill + fused paged decode vs single device
# ---------------------------------------------------------------------------


def _generate(cfg, params, mesh, steps=5, fused=True):
    eng = InstanceEngine(cfg, params, num_slots=4, kv_capacity=64,
                         temperature=0.0, mesh=mesh)
    reqs = _mk_requests(cfg, 3, steps=steps)
    for r in reqs:
        eng.prefill_request(r)
    if fused and eng.supports_paged_decode:
        eng.decode_multi(steps=steps - 1)
    else:
        for _ in range(steps - 1):
            eng.decode()
    return [list(r.output_tokens) for r in reqs]


@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_tokens_bit_identical(mesh8, setup, tp):
    cfg, params = setup
    base = _generate(cfg, params, mesh=None)
    (sl,) = carve_slices(tp, n_instances=1)
    sharded = _generate(cfg, params, mesh=sl)
    assert sharded == base, (
        f"tp={tp} sharded decode diverged from single-device greedy")


def test_indivisible_width_replicates_and_matches(mesh8, setup):
    cfg, params = setup
    base = _generate(cfg, params, mesh=None)
    (sl,) = carve_slices(3, n_instances=1)   # 4 heads % 3: replicated
    assert _generate(cfg, params, mesh=sl) == base


# ---------------------------------------------------------------------------
# collectives: device-to-device, no host round-trip, exact byte accounting
# ---------------------------------------------------------------------------


def test_cross_slice_mirror_is_d2d_and_priced_like_sim(mesh8, setup):
    cfg, params = setup
    a_sl, b_sl = carve_slices(2, n_instances=2)
    assert not (set(a_sl.devices) & set(b_sl.devices))
    a = InstanceEngine(cfg, params, num_slots=2, kv_capacity=64,
                       temperature=0.0, mesh=a_sl)
    b = InstanceEngine(cfg, params, num_slots=2, kv_capacity=64,
                       temperature=0.0, mesh=b_sl)
    (req,) = _mk_requests(cfg, 1, steps=4)
    slot = a.prefill_request(req)

    STATS.reset()
    # replica placement: per-layer streamed export lands on b's slice
    chunks, length, last, lines = a.export_stream(slot)
    b_slot = b.free_slots()[0]
    b.import_stream(b_slot, chunks, length, last, lines, req,
                    as_replica_of=(0, slot))
    assert STATS.d2d_copies > 0, "stream must cross slices on-device"
    assert STATS.host_copies == 0, "host round-trip on the stream path"

    # decode on the primary, then delta-mirror the new lines to b
    a.decode()
    from_line = b.store.lines(req.rid)
    STATS.reset()
    moved = b.sync_replica_from(a, slot, b_slot)
    assert STATS.d2d_copies > 0, "mirror must cross slices on-device"
    assert STATS.host_copies == 0, "host round-trip on the mirror path"

    # byte accounting: the live ledger's answer IS the simulator's
    delta = a.store.lines(req.rid) - from_line
    costs = LineCosts.from_config(cfg)
    assert moved == pytest.approx(costs.mirror_bytes(delta))
    sim_perf = PerfModel(cfg, InstanceSpec(H100, 2))
    assert moved == pytest.approx(
        sim_perf.line_costs.mirror_bytes(delta))


def test_mesh_cluster_byte_accounting_matches_host_copy(mesh8, setup):
    """The same trace through an unsharded pod and a mesh pod books
    identical mirror/stream bytes — the collective transport changes the
    wire, never the ledger."""
    cfg, params = setup

    def run(mesh):
        cluster = LiveCluster(cfg, params, n_instances=2, num_slots=6,
                              kv_capacity=64, policy=AcceLLMScheduler(),
                              mesh=mesh)
        for r in _mk_requests(cfg, 6, seed=11):
            cluster.submit(r)
        done = cluster.run(max_steps=200)
        assert len(done) == 6
        return cluster.stats

    host = run(None)
    STATS.reset()
    mesh = run(MeshPlacement.carve(2, tp=2))
    assert STATS.d2d_copies > 0 and STATS.host_copies == 0
    for key in ("mirror_syncs", "mirror_bytes", "stream_bytes",
                "replica_promotions", "prefills", "decode_steps"):
        assert mesh[key] == host[key], (
            f"{key}: mesh pod {mesh[key]} != host-copy pod {host[key]}")


@pytest.mark.parametrize("message,falls_back", [
    ("INVALID_ARGUMENT: Disallowed device-to-host transfer: x", True),
    ("RESOURCE_EXHAUSTED: out of memory on the destination", False),
])
def test_device_transfer_falls_back_only_on_the_guard(mesh8, monkeypatch,
                                                      message, falls_back):
    """Only the transfer guard's refusal takes the (counted) host copy;
    any other transfer failure propagates instead of hiding the device."""
    from repro.meshserve import collectives
    x = jax.device_put(jax.numpy.arange(8.0), mesh8[0])
    like = jax.device_put(jax.numpy.zeros(8), mesh8[1])
    real_put = jax.device_put

    def put(arr, target):
        if isinstance(arr, jax.Array):
            raise jax.errors.JaxRuntimeError(message)
        return real_put(arr, target)

    monkeypatch.setattr(collectives.jax, "device_put", put)
    STATS.reset()
    if not falls_back:
        with pytest.raises(jax.errors.JaxRuntimeError, match="out of memory"):
            collectives.device_transfer(x, like)
        assert STATS.host_copies == 0
        return
    out = collectives.device_transfer(x, like)
    assert STATS.host_copies == 1 and STATS.d2d_copies == 0
    assert out.devices() == {mesh8[1]}
    assert out.tolist() == list(range(8))


# ---------------------------------------------------------------------------
# golden trace: heterogeneous mesh pod, live vs sim
# ---------------------------------------------------------------------------

_TRACE = [("arrive", 8, 4), ("tick",), ("arrive", 12, 6), ("arrive", 6, 5),
          ("tick",), ("arrive", 10, 3), ("tick",), ("arrive", 7, 6),
          ("arrive", 9, 4), ("tick",)]

_HETERO_SPECS = (InstanceSpec(H100, 4, intra_link_gbps=H100.link_gbps),
                 InstanceSpec(ASCEND_910B2, 2,
                              intra_link_gbps=ASCEND_910B2.link_gbps))


def _run_live_trace(cfg, params, kernel):
    cluster = LiveCluster(cfg, params, n_instances=2, num_slots=8,
                          kv_capacity=256, policy=kernel,
                          mesh=MeshPlacement.carve(2, specs=_HETERO_SPECS))
    assert [sl.tp for sl in cluster.mesh.slices] == [4, 2]
    key = jax.random.PRNGKey(7)
    rids = []
    for i, op in enumerate(_TRACE):
        if op[0] == "arrive":
            plen, dlen = op[1], op[2]
            req = Request(prompt_len=plen, max_new_tokens=dlen,
                          prompt_tokens=jax.random.randint(
                              jax.random.fold_in(key, i), (1, plen), 0,
                              cfg.vocab_size))
            rids.append(req.rid)
            cluster.submit(req)
        cluster.step()
    steps = 0
    while cluster.pending() and steps < 50:
        cluster.step()
        steps += 1
    assert not cluster.pending()
    return cluster, rids, steps


def _run_sim_trace(cfg, rids, extra_ticks):
    """Same lock-step adapter drive as tests/test_scheduling.py, but each
    SimInstance is priced on its own heterogeneous slice spec."""
    from repro.sim.cluster import SimRequest

    kernel = AcceLLMScheduler()
    kernel.trace = []
    perfs = [PerfModel(cfg, s) for s in _HETERO_SPECS]
    sim = Simulator(AcceLLMPolicy(kernel=kernel), perfs, n_instances=2)
    sim.kick = lambda inst: None          # event mechanics not under test
    pol = sim.policy
    views = list(pol.view().instances())
    assert views[0].spec() is _HETERO_SPECS[0]
    assert views[1].spec() is _HETERO_SPECS[1]

    def tick(skip_iid=None):
        finished = {}
        for inst in sim.instances:
            if inst.iid == skip_iid:
                continue
            done_here = []
            for rid, r in list(inst.decode_batch.items()):
                r.generated += 1
                if r.done:
                    del inst.decode_batch[rid]
                    done_here.append(r)
            finished[inst.iid] = done_here
        for inst in sim.instances:
            if inst.iid in finished:
                pol.on_decode_done(inst, finished[inst.iid])

    arrivals = iter(rids)
    for op in _TRACE:
        skip = None
        if op[0] == "arrive":
            r = SimRequest(rid=next(arrivals), arrival=0.0,
                           prompt_len=op[1], decode_len=op[2])
            inst = pol.route(r)
            r.generated = 1               # the prefill's first token
            pol.on_prefill_done(inst, [r])
            skip = inst.iid
        tick(skip_iid=skip)
    for _ in range(extra_ticks):
        tick()
    return kernel.trace


def test_golden_trace_hetero_mesh_live_vs_sim(mesh8, setup):
    cfg, params = setup
    live_kernel = AcceLLMScheduler()
    live_kernel.trace = []
    cluster, rids, extra = _run_live_trace(cfg, params, live_kernel)
    sim_trace = _run_sim_trace(cfg, rids, extra)
    assert live_kernel.trace == sim_trace, (
        "shared kernel diverged between the hetero mesh pod and the sim:\n"
        f"live: {live_kernel.trace}\nsim:  {sim_trace}")
    kinds = {entry[0] for entry in live_kernel.trace}
    assert {"route", "place"} <= kinds
    # the live views expose the same hardware identity the sim priced
    assert cluster.engines[0].mesh.tp == 4
    assert cluster.engines[1].mesh.tp == 2
    from repro.scheduling.live import LiveInstanceView
    assert LiveInstanceView(cluster, 0).spec() is _HETERO_SPECS[0]
    assert LiveInstanceView(cluster, 1).spec() is _HETERO_SPECS[1]
    # redundancy ran across slice widths and booked real mirror traffic
    assert cluster.stats["mirror_syncs"] > 0
    assert cluster.stats["mirror_bytes"] > 0
