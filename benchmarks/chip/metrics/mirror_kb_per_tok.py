"""Bytes the redundancy mirror copies per output token: the ``bytes``
of the program's ``kv.mirror`` spans (MirrorSync, and a replica's
catch-up before its promotion) that start inside the window's steps,
over the window's output tokens, in KB (1e3 B).  None where the window
held no mirror."""
from chipbench import spans


def read(ctx):
    al = spans.aligned(ctx)
    if al is None:
        return None
    mirrors = al.in_steps("kv.mirror")
    tokens = ctx.window_tokens()
    if not mirrors or not tokens:
        return None
    return sum(a.get("bytes", 0) for _, _, _, a in mirrors) / tokens / 1e3
