"""Every per-layer reader named in BENCHMARK.json exists, reads a
synthetic run, and returns None (never 0 for a share) when the run holds
nothing for it."""
import json

import pytest

import run as bench_run
from chipbench import costs, devtrace, readers
from chipbench.peaks import TPU_V5E, peaks_for
from chipbench.stats import RequestRecord
from test_devtrace import synthetic

BENCH = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
NAMES = [m["name"] for m in BENCH["per_layer"]]
DIMS = costs.Dims(d=256, layers=2, heads=4, kv_heads=2, head_dim=64,
                  ffn=512, ffn_mats=2, vocab=512)


def ctx(trace=None, records=None, counters=None, chips=1):
    return readers.RunContext(
        records=records or [], window_s=10.0,
        counters=counters or {}, compiles=0, trace=trace, dims=DIMS,
        peaks=TPU_V5E, chips=chips)


def reader(name):
    return bench_run.load_module(bench_run.HERE / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", NAMES)
def test_reader_with_nothing_to_read(name):
    value = reader(name).read(ctx())
    assert value is None or name == "compiles_in_window"


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_synthetic_run(name):
    r = RequestRecord(due_s=0.0, prompt_len=300, output_len=3,
                      submit_s=0.001, token_s=[1e-7, 1.5e-7, 2e-7])
    c = ctx(trace=synthetic(), records=[r], chips=4,
            counters={"host_syncs": 2, "sched_time_s": 0.01,
                      "n_iterations": 5, "mirror_syncs": 2,
                      "d2d_bytes": 3e6})
    value = reader(name).read(c)
    assert value is not None and value >= 0


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v4")
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12


def test_flop_counts_match_the_shapes():
    # one token, no attention context beyond itself
    d = DIMS
    per_layer = 2 * d.layer_matmul_params + 4 * d.heads * d.head_dim
    assert costs.decode_flops(d, 1) == d.layers * per_layer \
        + 2 * d.d * d.vocab
    assert costs.prefill_flops(d, 1) == costs.decode_flops(d, 1)
    f, b = costs.paged_decode_cost(d, 10)
    assert b == d.layers * 2 * d.head_dim * (2 * d.kv_heads * 10
                                             + 2 * d.heads)
