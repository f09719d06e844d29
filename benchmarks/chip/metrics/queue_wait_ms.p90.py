"""How long requests wait in the program before their prefill starts:
the 90th percentile, over the requests submitted in the window (the
program's ``request.submit`` events), of the program's
``request.queued`` span (``LiveCluster.submit`` to the dispatch of the
prefill that completes the request), in ms on the host's clock.  A
request still waiting when the window closes counts at the window's end
less its submit, as ``ttft_p90_s`` counts a request with no first token."""
from chipbench import spans
from chipbench.stats import percentile


def read(ctx):
    al = spans.aligned(ctx)
    if al is None:
        return None
    lo, hi = al.window
    queued = {(a.get("rid"), s): d for _, s, d, a in al.named("request.queued")}
    waits = [min(queued.get((a.get("rid"), s), hi - s), hi - s)
             for _, s, _, a in al.named("request.submit") if lo <= s <= hi]
    return percentile(waits, 90) / 1e6 if waits else None
