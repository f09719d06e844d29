"""Host time of the redundancy mirror per serving step: the program's
``kv.mirror`` spans (MirrorSync, and a replica's catch-up before its
promotion) that start inside the window's steps, in ms per step.  None
where the window held no mirror."""
from chipbench import spans


def read(ctx):
    al = spans.aligned(ctx)
    if al is None:
        return None
    mirrors = al.in_steps("kv.mirror")
    if not mirrors:
        return None
    return sum(d for _, _, d, _ in mirrors) / len(al.steps) / 1e6
