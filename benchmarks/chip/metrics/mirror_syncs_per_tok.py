"""Redundancy mirror copies per output token (LiveCluster.stats
"mirror_syncs" over the window)."""
from chipbench.readers import per_token


def read(ctx):
    return per_token(ctx, "mirror_syncs")
