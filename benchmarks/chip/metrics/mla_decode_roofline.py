"""Paged latent-attention decode kernel (``paged_mla_decode``) against
its roofline: the latent lines of the live lengths read once for all
heads plus q and o, and its operations, over the kernel's device time.
The latent widths are read from ``deepseek-v3.l5e8.json``, the one
configuration the metric is listed for: it serves that configuration
only (the run's context carries no configuration).  None where the
trace holds no such kernel."""
import json
from pathlib import Path

from chipbench import costs_mla, readers

CONFIG = (Path(__file__).resolve().parent.parent / "configs"
          / "deepseek-v3.l5e8.json")


def kernel(op: str) -> bool:
    return "%paged_mla_decode." in op


def read(ctx):
    dims = costs_mla.MlaDims.from_config(json.loads(CONFIG.read_text()))
    cost = [costs_mla.paged_mla_decode_cost(dims, c)
            for c in ctx.decode_contexts()]
    return readers.roofline(ctx, kernel, sum(c[0] for c in cost),
                            sum(c[1] for c in cost))
