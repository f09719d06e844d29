"""End-to-end arithmetic on synthetic request records."""
import pytest

from chipbench import stats
from chipbench.stats import RequestRecord


def rec(due, tokens, submit=None):
    r = RequestRecord(due_s=due, prompt_len=10, output_len=len(tokens) or 4)
    r.token_s = list(tokens)
    r.submit_s = due if submit is None else submit
    return r


def test_ttft_counts_censored_requests_at_the_window_end():
    window = 10.0
    served = [rec(float(i), [i + 0.5, i + 0.6]) for i in range(9)]
    stuck = rec(9.0, [])                  # no first token by the end
    ttfts = [r.ttft(window) for r in served + [stuck]]
    assert ttfts == pytest.approx([0.5] * 9 + [1.0])
    # p90 over all ten, the censored one included, never dropped
    assert stats.ttft_p90_s(served + [stuck], window) == pytest.approx(
        0.55)


def test_ttft_is_timed_from_due_not_from_submit():
    r = rec(1.0, [3.0], submit=2.5)
    assert r.ttft(10.0) == pytest.approx(2.0)


def test_requests_due_after_the_window_are_left_out():
    inside = rec(1.0, [1.2])
    after = rec(11.0, [11.1])
    assert stats.due_in_window([inside, after], 10.0) == [inside]


def test_gaps_mean_and_tail_over_all_requests():
    a = rec(0.0, [0.1, 0.2, 0.3, 0.4])     # gaps 0.1 x3
    b = rec(0.0, [0.1, 1.1])               # one stall of 1.0
    window = 10.0
    assert stats.tbt_mean_ms([a, b], window) == pytest.approx(
        1e3 * (0.3 + 1.0) / 4)
    # sorted gaps 0.1, 0.1, 0.1, 1.0: rank 0.95 * 3 = 2.85
    assert stats.tbt_p95_ms([a, b], window) == pytest.approx(
        1e3 * (0.1 + 0.85 * 0.9))


def test_gaps_and_tokens_stop_at_the_window_end():
    a = rec(0.0, [1.0, 2.0, 12.0])
    assert a.gaps(10.0) == [1.0]
    assert stats.out_tok_s([a], 10.0) == pytest.approx(0.2)


def test_generator_lateness_p99():
    late = [rec(float(i), [i + 1.0], submit=i + 0.001 * i) for i in range(100)]
    assert stats.gen_late_ms_p99(late, 1000.0) == pytest.approx(98.01)


def test_generator_lateness_reader():
    from conftest import CHIP
    from run import load_module
    reader = load_module(CHIP / "metrics" / "gen_late_ms.p99.py")

    class Ctx:
        window_s = 1000.0
        records = [rec(float(i), [i + 1.0], submit=i + 0.001 * i)
                   for i in range(100)]
    assert reader.read(Ctx) == pytest.approx(98.01)
    Ctx.records = []
    assert reader.read(Ctx) is None
