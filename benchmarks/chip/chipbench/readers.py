"""What the per-layer readers (``metrics/<name>.py``) read, and the
arithmetic they share.

Each reader is a file named after its metric with one function,
``read(ctx) -> float | None``.  It returns None when the run holds
nothing for it to read; the harness then leaves the metric out of the
result line.  A share of a peak or a roofline is never reported as 0.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from chipbench import costs
from chipbench.devtrace import TraceSummary
from chipbench.peaks import Peaks
from chipbench.stats import RequestRecord

# The program gives its jits and kernels no names yet: in a v5e trace
# every served program is "jit__unknown(<hash>)" and both Pallas kernels
# are anonymous custom calls.  They are told apart by what the trace
# does carry: the paged decode kernel takes its scalar-prefetched
# lengths (s32) first, the flash kernel its bf16 queries.


def paged_kernel(op: str) -> bool:
    return "custom-call(s32[" in op


def flash_kernel(op: str) -> bool:
    return "custom-call(bf16[" in op


def decode_program(ops: List[str]) -> bool:
    """A program that runs the paged decode kernel."""
    return any(paged_kernel(op) for op in ops)


def prefill_program(ops: List[str]) -> bool:
    """A program with a layer loop and no paged decode kernel: the
    batched prefill (which holds the flash kernel from bucket 128 up)."""
    return (any(" while(" in op for op in ops)
            and not decode_program(ops))


@dataclass
class RunContext:
    records: List[RequestRecord]
    window_s: float
    #: program counters: their change over the window
    counters: Dict[str, float]
    #: backend compiles and compile-cache loads in the whole window
    compiles: int
    trace: Optional[TraceSummary]
    dims: costs.Dims
    peaks: Peaks
    chips: int

    # -- the work of the window, from the request records -----------------
    def prefilled_prompts(self) -> List[int]:
        return [r.prompt_len for r in self.records
                if r.token_s and r.token_s[0] <= self.window_s]

    def decode_contexts(self) -> List[int]:
        """Keys each decode token of the window attended to: output
        token k (k >= 1) is made from position prompt + k - 1."""
        return [r.prompt_len + k for r in self.records
                for k, t in enumerate(r.token_s)
                if k >= 1 and t <= self.window_s]

    def window_tokens(self) -> int:
        return sum(1 for r in self.records for t in r.token_s
                   if t <= self.window_s)


def per_token(ctx: RunContext, counter: str) -> Optional[float]:
    n = ctx.window_tokens()
    if not n or counter not in ctx.counters:
        return None
    return ctx.counters[counter] / n


def sched_ms_per_iter(ctx: RunContext) -> Optional[float]:
    iters = ctx.counters.get("n_iterations", 0)
    if not iters:
        return None
    return 1e3 * ctx.counters["sched_time_s"] / iters


def share(needed_s: float, device_s: float) -> Optional[float]:
    """Percent of ``device_s`` that the least time ``needed_s`` is."""
    if not (needed_s > 0 and device_s > 0):
        return None
    return 100.0 * needed_s / device_s


def step_mfu(ctx: RunContext, programs: Callable[[List[str]], bool],
             flops: float) -> Optional[float]:
    if ctx.trace is None:
        return None
    return share(flops / ctx.peaks.bf16_flops, ctx.trace.module_s(programs))


def roofline(ctx: RunContext, kernel: Callable[[str], bool], flops: float,
             nbytes: float) -> Optional[float]:
    if ctx.trace is None:
        return None
    least = max(flops / ctx.peaks.bf16_flops, nbytes / ctx.peaks.hbm_bytes_s)
    return share(least, ctx.trace.op_s(kernel))


def prefill_flops(ctx: RunContext) -> float:
    return sum(costs.prefill_flops(ctx.dims, p)
               for p in ctx.prefilled_prompts())


def decode_flops(ctx: RunContext) -> float:
    return sum(costs.decode_flops(ctx.dims, c) for c in ctx.decode_contexts())


def idle_pct(ctx: RunContext) -> Optional[float]:
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_s() / ctx.window_s)
