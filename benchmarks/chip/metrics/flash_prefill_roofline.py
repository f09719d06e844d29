"""Flash prefill kernel against its roofline: the larger of its
operations over the bf16 peak and its bytes over HBM bandwidth (real
prompt lengths), over the kernel's device time."""
from chipbench import costs, readers


def read(ctx):
    cost = [costs.flash_prefill_cost(ctx.dims, p)
            for p in ctx.prefilled_prompts()]
    return readers.roofline(ctx, readers.flash_kernel,
                            sum(c[0] for c in cost), sum(c[1] for c in cost))
