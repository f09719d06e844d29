"""Trace reduction: on synthetic planes, and on a small trace recorded on
a TPU v5e (two steps of the flash prefill and paged decode kernels)."""
from types import SimpleNamespace as NS

import pytest

from chipbench import devtrace, readers

DATA = __import__("conftest").HERE / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def synthetic():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_prefill_batched(1)", 0, 100),
                                       ev("jit_decode_multi(2)", 150, 50),
                                       ev("jit_decode_multi(2)", 180, 40)]),
        NS(name="XLA Ops", events=[
            ev("%while.4 = (s32[]) while(%tuple.1)", 5, 90),
            ev("%closed_call.11 = bf16[1,4,256,64] custom-call(bf16[1,4,256,"
               "64] %q)", 10, 60),
            ev("%fusion.1 = bf16[256,512] fusion(%x)", 70, 30),
            ev("%while.17 = (s32[]) while(%tuple.2)", 150, 50),
            ev("%closed_call.13 = bf16[2,2,2,64] custom-call(s32[2] %len, "
               "s32[2,4] %tables)", 150, 20),
            ev("%while.17 = (s32[]) while(%tuple.2)", 180, 40),
            ev("%closed_call.13 = bf16[2,2,2,64] custom-call(s32[2] %len, "
               "s32[2,4] %tables)", 185, 20)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.step", 0, 110),
                                  ev("bench.wait", 110, 40),
                                  ev("other", 0, 500)])])
    return devtrace.summarize([dev, host])


def test_busy_is_the_union_of_program_intervals():
    s = synthetic()
    # [0,100) and [150,220): overlapping decodes count once
    assert s.busy_s("/device:TPU:0") == pytest.approx(170e-9)
    assert s.mean_busy_s() == pytest.approx(170e-9)


def test_programs_and_kernels_by_what_runs_inside():
    s = synthetic()
    assert s.module_s(readers.decode_program) == pytest.approx(90e-9)
    assert s.module_s(readers.prefill_program) == pytest.approx(100e-9)
    assert s.op_s(readers.flash_kernel) == pytest.approx(60e-9)
    assert s.op_s(readers.paged_kernel) == pytest.approx(40e-9)
    # loops hold other operations: they are no entry of their own
    names = [n for n, _ in s.top_ops(10)]
    assert names[0].startswith("%closed_call.11")
    assert not any(" while(" in n for n in names)


def test_idle_gaps_are_named_by_the_host_span_around_them():
    s = synthetic()
    assert s.idle_gaps() == [["bench.wait", pytest.approx(50e-9)]]
    assert [n for n, _, _ in s.host] == ["bench.step", "bench.wait"]


def test_recorded_v5e_trace():
    s = devtrace.load(str(DATA))
    assert list(s.devices) == ["/device:TPU:0"]
    busy = s.busy_s("/device:TPU:0")
    assert busy > 0
    # each kernel ran twice, each in a program of its own
    assert len(s.devices["/device:TPU:0"].modules) == 4
    assert 0 < s.op_s(readers.flash_kernel) <= busy
    assert 0 < s.op_s(readers.paged_kernel) <= busy
    assert s.module_s(readers.decode_program) == pytest.approx(
        s.op_s(readers.paged_kernel), rel=0.2)
    assert {n for n, _, _ in s.host} == {"bench.step"}
