"""Whole prefill step against the chip's peak: operations the prompts
prefilled in the window need (real lengths, not buckets) over the
device time of the batched-prefill programs times the bf16 peak."""
from chipbench import readers


def read(ctx):
    return readers.step_mfu(ctx, readers.prefill_program,
                            readers.prefill_flops(ctx))
