"""Host spans of the serving loop, on one clock.

``span(name, **attrs)`` times one phase of the host's work.  It opens a
``jax.profiler.TraceAnnotation`` of the same name, so the phase sits on
the host line of any profile, and on exit appends ``(name, start_ns,
dur_ns, attrs)`` to :data:`RING`.  ``record`` appends an event whose
ends the caller stamped itself (a request's wait spans many steps).

Every event is stamped with :func:`clock_ns` (``time.perf_counter_ns``).
That clock is not the profiler's: a reader lines the two up by matching
spans that both record (see ``benchmarks/chip/chipbench/spans.py``).

Recording is always on and costs a few microseconds of host time per
span.  A span never waits for the device and never reads a device
value, so it measures host time only.  The serving loop records from
one thread.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

from jax.profiler import TraceAnnotation

#: the one host clock of every event
clock_ns = time.perf_counter_ns

#: (name, start_ns, dur_ns, attrs)
Event = Tuple[str, int, int, dict]

#: events the process-wide ring holds before it overwrites the oldest
RING_EVENTS = 1 << 16


class Ring:
    """A bounded record of events that overwrites the oldest, with
    per-name totals (count and nanoseconds) that never wrap."""

    def __init__(self, size: int = RING_EVENTS):
        self.size = size
        self._buf: List[Event] = []
        #: events ever appended; the newest sits at (appended - 1) % size
        self.appended = 0
        self._totals: Dict[str, List[int]] = {}

    def append(self, name: str, start_ns: int, dur_ns: int, attrs: dict):
        ev = (name, start_ns, dur_ns, attrs)
        if len(self._buf) < self.size:
            self._buf.append(ev)
        else:
            self._buf[self.appended % self.size] = ev
        self.appended += 1
        tot = self._totals.get(name)
        if tot is None:
            self._totals[name] = [1, dur_ns]
        else:
            tot[0] += 1
            tot[1] += dur_ns

    def events(self, since: int = 0) -> List[Event]:
        """The events held, oldest first; with ``since``, only those
        appended after ``appended`` read ``since``."""
        if self.appended <= self.size:
            held = list(self._buf)
        else:
            cut = self.appended % self.size
            held = self._buf[cut:] + self._buf[:cut]
        first = self.appended - len(held)
        return held[max(0, since - first):]

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """Per name: (events, seconds) since the process started."""
        return {name: (n, ns / 1e9) for name, (n, ns) in self._totals.items()}


#: the process's ring, as ``repro.meshserve.STATS`` is its transfer count
RING = Ring()


class span:
    """Context manager: one host phase.  ``attrs`` may be added to
    inside the block (bytes known only once the copy ran); after the
    block ``dur_ns`` holds the phase's length."""

    __slots__ = ("name", "attrs", "start_ns", "dur_ns", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.dur_ns = 0

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = clock_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_ns = clock_ns() - self.start_ns
        self._ann.__exit__(*exc)
        RING.append(self.name, self.start_ns, self.dur_ns, self.attrs)
        return False


def record(name: str, start_ns: int, end_ns: int, **attrs):
    """Append an event the caller stamped with :func:`clock_ns` (ring
    only: it is no phase of one thread's work)."""
    RING.append(name, start_ns, end_ns - start_ns, attrs)
