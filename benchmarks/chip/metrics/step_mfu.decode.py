"""Whole decode step against the chip's peak: operations the decode
tokens of the window need over the device time of the paged
decode programs times the bf16 peak."""
from chipbench import readers


def read(ctx):
    return readers.step_mfu(ctx, readers.decode_program,
                            readers.decode_flops(ctx))
