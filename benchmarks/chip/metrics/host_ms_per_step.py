"""Host time of one serving step: the program's ``serve.step`` span less
the time inside its ``engine.readback`` spans (where the host waits for
the chip), averaged over the window's steps, in ms.  A copy between chips
waits for its transfer inside ``kv.mirror``, ``kv.stream`` or
``kv.merge``; on a multi-chip cell that wait counts here as host time."""
from chipbench import spans


def read(ctx):
    al = spans.aligned(ctx)
    if al is None:
        return None
    wait = sum(d for _, _, d, _ in al.in_steps(spans.READBACK))
    step = sum(d for _, _, d, _ in al.steps)
    return (step - wait) / len(al.steps) / 1e6
