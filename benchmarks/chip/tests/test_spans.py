"""The program's spans lined up with a profile (``chipbench/spans.py``)
and the four readers over them: on a hand-made profile and a ring
shifted by a known offset, and in one tiny whole run on the CPU."""
import json
import sys
from types import SimpleNamespace as NS

import pytest

import run as bench_run
from chipbench import devtrace, spans
from repro import obs
from test_devtrace import ev
from test_readers import ctx, reader

#: the ring's clock runs this far behind the profile's
OFFSET = 10**12 + 7
#: the hand-made times below are in microseconds
US = 1000
NEW = ("queue_wait_ms.p90", "host_ms_per_step", "idle_host_pct",
       "mirror_ms_per_step")


@pytest.fixture(autouse=True)
def own_ring(monkeypatch):
    """Each test records into a ring of its own, so no reader here sees
    what another test left in the process's ring."""
    monkeypatch.setattr(obs, "RING", obs.Ring())


def profile():
    """Two driver steps around a wait; the chip runs three programs."""
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_decode_multi(1)", 1100, 300),
                                       ev("jit__copy_rows(2)", 1700, 200),
                                       ev("jit_prefill_batched(3)", 2600,
                                          400)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.step", 1000, 1000),
                                  ev("bench.wait", 2000, 500),
                                  ev("bench.step", 2500, 1000)])])
    return devtrace.summarize([_scaled(dev), _scaled(host)])


def _scaled(plane):
    return NS(name=plane.name, lines=[
        NS(name=line.name, events=[ev(e.name, e.start_ns * US,
                                      e.duration_ns * US)
                                   for e in line.events])
        for line in plane.lines])


def ring(shift_second_step=0):
    """The program's events in profile time, then moved to its clock."""
    a, b = 0, shift_second_step
    events = [
        ("serve.step", -5000, 900, {}),              # before the window
        ("sched.route", 1010, 40, {}),
        ("engine.decode.prep", 1050, 50, {"instance": 0}),
        ("engine.decode.dispatch", 1100, 50, {"instance": 0}),
        ("engine.readback", 1150, 300, {"site": "decode"}),
        ("engine.decode.tokens", 1450, 50, {"instance": 0}),
        ("kv.mirror", 1500, 150, {"bytes": 8, "cross": True}),
        ("sched.sync", 1650, 50, {}),
        ("serve.step", 1010 + a, 980, {}),
        ("request.submit", 900, 0, {"rid": 2}),     # submitted earlier
        ("request.queued", 900, 100, {"rid": 2}),
        ("engine.prefill.prep", 2510 + b, 40, {}),
        ("request.submit", 1005, 0, {"rid": 1}),
        ("request.queued", 1005, 1545, {"rid": 1}),
        ("request.submit", 2100, 0, {"rid": 3}),
        ("request.queued", 2100, 450, {"rid": 3}),
        ("request.submit", 3000, 0, {"rid": 4}),    # still waiting at 3500
        ("engine.prefill.dispatch", 2550 + b, 50, {}),
        ("engine.readback", 2600 + b, 450, {"site": "prefill"}),
        ("kv.merge", 3050 + b, 50, {}),
        ("serve.step", 2510 + b, 980, {}),
    ]
    return [(n, s * US - OFFSET, d * US, at) for n, s, d, at in events]


def test_offset_recovered_from_the_steps():
    al = spans.align(ring(), profile())
    assert al.offset_ns == OFFSET
    assert al.slack_ns == 20 * US                # 10 us either side
    assert al.window == (1000 * US, 3500 * US)
    assert [s for _, s, _, _ in al.steps] == [1010 * US, 2510 * US]


@pytest.mark.parametrize("shift,lined_up", [(80, True), (-150, True),
                                            (250, False), (-250, False)])
def test_a_step_outside_its_bench_step_lines_up_nothing(shift, lined_up):
    """The second step moved by ``shift`` us: the offset splits the
    difference, so each step sticks out by about half of it, against a
    tolerance of 100 us."""
    al = spans.align(ring(shift), profile())
    assert (al is not None) == lined_up


def test_a_ring_that_lost_the_window_lines_up_nothing():
    short = [e for e in ring() if not (e[0] == "serve.step"
                                       and e[1] + OFFSET != 2510 * US)]
    assert spans.align(short, profile()) is None
    assert spans.align([], profile()) is None
    assert spans.align(ring(), None) is None


def test_idle_of_the_first_chip_by_span():
    al = spans.align(ring(), profile())
    idle = {k: v / US for k, v in spans.idle_by_span(al, profile()).items()}
    assert idle == {None: 540, "sched.route": 40, "engine.decode.prep": 50,
                    "engine.readback": 100, "engine.decode.tokens": 50,
                    "kv.mirror": 150, "sched.sync": 50,
                    "serve.step": 480, "engine.prefill.prep": 40,
                    "engine.prefill.dispatch": 50, "kv.merge": 50}
    # the parts add up to the chip's idle time in the window
    assert sum(idle.values()) == 2500 - 300 - 200 - 400


@pytest.mark.parametrize("name,value", [
    # rid 4 still waits when the window closes: counted at 3500 - 3000
    ("queue_wait_ms.p90", (500 + 0.8 * (1545 - 500)) / 1e3),
    ("host_ms_per_step", ((980 - 300) + (980 - 450)) / 2 / 1e3),
    ("idle_host_pct", 100 * (1600 - 540 - 100) / 2500),
    ("mirror_ms_per_step", 150 / 2 / 1e3),
])
def test_reader_on_a_hand_made_run(monkeypatch, name, value):
    monkeypatch.setattr(spans, "ring_events", ring)
    assert reader(name).read(ctx(trace=profile())) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_reader_reports_nothing_without_program_spans(monkeypatch, name):
    """No ``--trace 1``, a program that keeps no spans (the parent of the
    spans), or a run with no mirror."""
    monkeypatch.setattr(spans, "ring_events", ring)
    assert reader(name).read(ctx()) is None
    if name == "mirror_ms_per_step":
        monkeypatch.setattr(spans, "ring_events", lambda: [
            e for e in ring() if e[0] != "kv.mirror"])
        assert reader(name).read(ctx(trace=profile())) is None
    monkeypatch.undo()
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert spans.ring_events() is None
    assert reader(name).read(ctx(trace=profile())) is None


# -- one whole run on the CPU -------------------------------------------------

#: CPU executions, which stand in for the chip's programs here
CPU_EXECUTE = "ThunkExecutor::Execute"


def _cpu_programs_as_chip(summarize):
    """``devtrace.summarize`` with the CPU's program executions (on the
    XLA threads of the host plane) presented as chip 0's programs."""
    def wrapped(planes):
        planes = list(planes)
        summary = summarize(planes)
        runs = [(e.name, float(e.start_ns), float(e.duration_ns))
                for plane in planes if plane.name.startswith("/host:")
                for line in plane.lines if line.name.startswith("tf_XLA")
                for e in line.events if e.name == CPU_EXECUTE]
        summary.devices["/device:TPU:0"] = devtrace.DeviceLines(modules=runs)
        return summary
    return wrapped


def test_whole_cpu_run_reads_all_four(monkeypatch, tmp_path):
    from chipbench import peaks
    from test_run_cpu import E2E, MIX, TINY
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"] if m["name"] in NEW]
    mix = tmp_path / "tiny.json"
    mix.write_text(json.dumps(MIX))
    info = dict(cell={"name": "tiny", "chips": 1}, cfg=TINY,
                ref=bench_run.load_module(bench_run.HERE / "configs"
                                          / "dense_gqa.py"),
                mix=mix, end_to_end=E2E, per_layer=per_layer)
    monkeypatch.setattr(devtrace, "summarize",
                        _cpu_programs_as_chip(devtrace.summarize))
    # the CPU has no entry in the peaks table, and none of the four reads it
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: peaks.TPU_V5E)
    out = bench_run.run(info, 987654321012, 3.0, trace=True)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == set(NEW), out["metrics"]
    for name in NEW:
        assert out["metrics"][name]["value"] >= 0
    assert 0 < out["metrics"]["idle_host_pct"]["value"] < 100


# -- the tools over the same pieces --------------------------------------------


@pytest.mark.parametrize("grows,knee", [
    ((False, False, True), (1.0 + 1.4) / 2),
    ((True,), 0.6 / 2),
    ((False, False, False), None),
])
def test_knee_lies_between_the_last_rate_held_and_the_first_that_grew(
        grows, knee):
    import sweep
    lines = [{"rate_per_s": r, "grows": g}
             for r, g in zip((0.6, 1.0, 1.4), grows)]
    assert sweep.knee(lines) == knee


def test_idle_table_parts_add_up_to_the_chips_idle(monkeypatch):
    import idle_by_span
    monkeypatch.setattr(spans, "ring_events", ring)
    out = idle_by_span.analyse(profile(), {"device": {"window_s": 2500e-6}})
    assert out["alignment"]["worst_overhang_ns"] == 0
    assert out["idle"]["parts_pct"] == pytest.approx(
        {"host_spans": 100 * 960 / 2500, "readback": 100 * 100 / 2500,
         "outside": 100 * 540 / 2500})
    assert out["idle"]["parts_sum_pct"] == pytest.approx(
        out["idle"]["device_idle_pct_chip0"])
    assert out["spans_per_step"] == pytest.approx(18 / 2)
