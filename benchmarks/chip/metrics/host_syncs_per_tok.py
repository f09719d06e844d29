"""Device-to-host reads of the engines' decode path per output token
(InstanceEngine.host_syncs over the window)."""
from chipbench.readers import per_token


def read(ctx):
    return per_token(ctx, "host_syncs")
