"""Paged decode kernel against its roofline: K/V lines of the live
lengths read plus q and o, and its operations, over the kernel's
device time."""
from chipbench import costs, readers


def read(ctx):
    cost = [costs.paged_decode_cost(ctx.dims, c)
            for c in ctx.decode_contexts()]
    return readers.roofline(ctx, readers.paged_kernel,
                            sum(c[0] for c in cost), sum(c[1] for c in cost))
