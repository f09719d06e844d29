"""The two readers of the latent-attention cell on hand-made runs:
``mla_decode_roofline`` against its cost function, and
``mirror_kb_per_tok`` over the program's ``kv.mirror`` spans."""
import json
from types import SimpleNamespace as NS

import pytest

from chipbench import costs_mla, devtrace, spans
from chipbench.peaks import TPU_V5E
from chipbench.stats import RequestRecord
from test_devtrace import ev
from test_readers import ctx, reader
from test_spans import profile, ring

DSV3 = costs_mla.MlaDims(layers=5, heads=128, kv_lora=512, rope=64)


def test_mla_cost_counts_lines_once_for_all_heads():
    flops, nbytes = costs_mla.paged_mla_decode_cost(DSV3, 1000)
    assert flops == 5 * 2 * 128 * 1000 * (576 + 512)
    assert nbytes == 5 * 2 * (576 * 1000 + 128 * 576 + 128 * 512)
    # about 242 operations per byte at long contexts: the v5e's ridge
    flops, nbytes = costs_mla.paged_mla_decode_cost(DSV3, 10**6)
    assert 241 < flops / nbytes < 242


def _trace(kernel_ns):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_decode_multi(2)", 0, 10**6)]),
        NS(name="XLA Ops", events=[
            ev("%paged_mla_decode.3 = bf16[8,128,512] custom-call(s32[8] "
               "%len, s32[8,256] %tables)", 10, kernel_ns),
            ev("%paged_decode.1 = bf16[8,2,12,128] custom-call(s32[8] "
               "%len, s32[8,256] %tables)", 10, 999)])])
    return devtrace.summarize([dev])


def test_mla_roofline_reads_only_its_kernel():
    # one request: prompt 1000, outputs at contexts 1001 and 1002
    r = RequestRecord(due_s=0.0, prompt_len=1000, output_len=3,
                      submit_s=0.0, token_s=[0.1, 0.2, 0.3])
    dims = costs_mla.MlaDims.from_config(
        json.loads(reader("mla_decode_roofline").CONFIG.read_text()))
    assert dims == DSV3
    need = [costs_mla.paged_mla_decode_cost(DSV3, n) for n in (1001, 1002)]
    least = max(sum(f for f, _ in need) / TPU_V5E.bf16_flops,
                sum(b for _, b in need) / TPU_V5E.hbm_bytes_s)
    got = reader("mla_decode_roofline").read(
        ctx(trace=_trace(20_000), records=[r]))
    assert got == pytest.approx(100 * least / 20e-6)
    assert reader("mla_decode_roofline").read(
        ctx(trace=_trace(0), records=[r])) is None


def test_mirror_kb_per_tok_on_a_hand_made_run(monkeypatch):
    monkeypatch.setattr(spans, "ring_events", ring)
    # the hand-made window holds one kv.mirror of 8 bytes; 4 tokens
    r = RequestRecord(due_s=0.0, prompt_len=10, output_len=4, submit_s=0.0,
                      token_s=[1.0, 2.0, 3.0, 4.0])
    assert reader("mirror_kb_per_tok").read(
        ctx(trace=profile(), records=[r])) == pytest.approx(8 / 4 / 1e3)
    monkeypatch.setattr(spans, "ring_events", lambda: [
        e for e in ring() if e[0] != "kv.mirror"])
    assert reader("mirror_kb_per_tok").read(
        ctx(trace=profile(), records=[r])) is None
