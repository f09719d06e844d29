"""How late the benchmark's own loop submitted requests: the 99th
percentile, over the requests due in the window, of submit time minus
due time, in ms on the host's clock.  Every request is timed from when
it was due, so this lateness is already inside each TTFT; a step that
holds the loop (a long prefill) makes it."""
from chipbench.stats import gen_late_ms_p99


def read(ctx):
    return gen_late_ms_p99(ctx.records, ctx.window_s)
