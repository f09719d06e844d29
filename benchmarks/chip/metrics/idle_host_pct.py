"""Share of the window in which the first chip ran no program while the
host was inside a program span other than ``engine.readback``: idle
time the host's own work (scheduling, preparing, dispatching, copying)
leaves on the chip.  Idle inside readbacks and outside every program
span (``bench.wait``, the open loop between steps) is left out.  A copy
between chips waits for its transfer inside ``kv.mirror``, ``kv.stream``
or ``kv.merge``; on a multi-chip cell that wait counts here as the
host's."""
from chipbench import spans


def read(ctx):
    al = spans.aligned(ctx)
    if al is None or not ctx.trace.devices:
        return None
    idle = spans.idle_by_span(al, ctx.trace)
    host = sum(ns for name, ns in idle.items()
               if name not in (None, spans.READBACK))
    return 100.0 * host / al.window_ns
