"""The program's own spans (``repro.obs``), lined up with the profile.

The program records each phase of ``LiveCluster.step`` in a ring of
events on its own host clock.  The profile (``RunContext.trace``) holds
the chips' programs and the benchmark's ``bench.*`` host spans on the
profiler's clock.  The two clocks share no origin, so this module never
assumes they agree: each program ``serve.step`` runs inside the
``bench.step`` that the open loop (``chipbench/driver.py``) wraps around
``LiveCluster.step``, and matching them in order (the last
``serve.step`` with the last ``bench.step``) bounds the offset from one
clock to the other.  If no offset puts every matched ``serve.step``
inside its ``bench.step`` within ``TOLERANCE_NS``, nothing is lined up
and every reader reports nothing.

The window is the profile's ``bench.*`` spans, first start to last end.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.devtrace import TraceSummary

#: how far a matched serve.step may stick out of its bench.step
TOLERANCE_NS = 1e5

#: (name, start_ns, dur_ns, attrs)
Span = Tuple[str, float, float, dict]

#: events that are no phase of the host's work (a wait across steps, a
#: moment)
NOT_PHASES = ("request.queued", "request.submit")

READBACK = "engine.readback"


@dataclass
class Aligned:
    #: add to a ring time to get a profile time
    offset_ns: float
    #: how far the offset could move with every matched step nested
    slack_ns: float
    #: (start, end) of the window, profile clock
    window: Tuple[float, float]
    #: the window's serve.step spans, profile clock, in order
    steps: List[Span]
    #: every ring event, profile clock
    events: List[Span]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def named(self, name: str) -> List[Span]:
        return [e for e in self.events if e[0] == name]

    def in_steps(self, name: str) -> List[Span]:
        """``name`` events that start between the first of the window's
        steps and the end of its last."""
        lo, hi = self.steps[0][1], self.steps[-1][1] + self.steps[-1][2]
        return [e for e in self.named(name) if lo <= e[1] <= hi]


def ring_events() -> Optional[List[Span]]:
    """The program's span ring, or None where the program keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.RING.events()


def align(ring: Optional[Sequence[Span]],
          trace: Optional[TraceSummary]) -> Optional[Aligned]:
    if not ring or trace is None:
        return None
    bench = [e for e in trace.host if e[0].startswith("bench.")]
    wrappers = sorted((e for e in bench if e[0] == "bench.step"),
                      key=lambda e: e[1])
    steps = sorted((e for e in ring if e[0] == "serve.step"),
                   key=lambda e: e[1])
    if not wrappers or len(steps) < len(wrappers):
        return None           # the ring no longer holds the whole window
    steps = steps[len(steps) - len(wrappers):]
    lo, hi = -float("inf"), float("inf")
    for (_, bs, bd), (_, ss, sd, _) in zip(wrappers, steps):
        lo = max(lo, bs - ss)
        hi = min(hi, (bs + bd) - (ss + sd))
    if lo - hi > 2 * TOLERANCE_NS:
        return None
    offset = (lo + hi) / 2
    events = [(n, s + offset, d, a) for n, s, d, a in ring]
    return Aligned(offset_ns=offset, slack_ns=hi - lo,
                   window=(min(e[1] for e in bench),
                           max(e[1] + e[2] for e in bench)),
                   steps=[(n, s + offset, d, a) for n, s, d, a in steps],
                   events=events)


def aligned(ctx) -> Optional[Aligned]:
    return align(ring_events(), ctx.trace)


# -- idle time of the first chip, by host phase ------------------------------


def _merged(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_intervals(trace: TraceSummary, window: Tuple[float, float]
                   ) -> List[Tuple[float, float]]:
    """Where no program ran on the first chip inside ``window``."""
    lines = trace.devices[sorted(trace.devices)[0]]
    lo, hi = window
    busy = _merged((max(s, lo), min(s + d, hi))
                   for _, s, d in (lines.modules or lines.ops)
                   if s + d > lo and s < hi)
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    return idle


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """Consecutive ``(start, end, name)`` pieces of time, each under the
    innermost phase span that covers it (one thread's spans nest)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []
    t = 0.0
    for name, s, d, _ in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        stack.append((name, s + d))
        t = s
    while stack:
        top, end = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


def idle_by_span(al: Aligned, trace: TraceSummary) -> Dict[Optional[str],
                                                            float]:
    """Nanoseconds the first chip sat idle in the window, by the
    innermost program span the host was in; ``None`` collects the idle
    time outside every program span (``bench.wait``, the open loop
    between steps)."""
    idle = idle_intervals(trace, al.window)
    pieces = innermost([e for e in al.events if e[0] not in NOT_PHASES])
    out: Dict[Optional[str], float] = {}
    total = sum(e - s for s, e in idle)
    i = 0
    for s, e, name in pieces:
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < e:
            overlap = min(e, idle[j][1]) - max(s, idle[j][0])
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap
            j += 1
    out[None] = total - sum(out.values())
    return out
