"""Reduce a JAX profiler trace to what the per-layer readers need.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` the profiler
writes.  Each chip is a plane named ``/device:TPU:<n>``; its line
``XLA Modules`` holds one event per program execution (named after the
jitted function) and ``XLA Ops`` one per operation inside it (a Pallas
kernel appears as its own operation).  Host threads are ``/host:CPU``
lines, where the benchmark's own ``TraceAnnotation`` spans sit.

Busy time is the union of a chip's module intervals.  Readers select
operations with a predicate on their name (the HLO instruction text) and
programs with a predicate on the names of the operations that run inside
them; nothing here knows a program or kernel.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
MODULES = "XLA Modules"
OPS = "XLA Ops"

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

#: operations that only contain others (their time is their children's)
_CONTAINERS = re.compile(r" (while|conditional|call)\(")


@dataclass
class DeviceLines:
    modules: List[Event] = field(default_factory=list)
    ops: List[Event] = field(default_factory=list)


@dataclass
class TraceSummary:
    devices: Dict[str, DeviceLines]
    host: List[Event]                     # host annotation spans

    # -- busy / idle -------------------------------------------------------
    def busy_s(self, device: str) -> float:
        lines = self.devices[device]
        return union_ns(lines.modules or lines.ops) / 1e9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    # -- time by operation and by program ----------------------------------
    def op_s(self, pred: Callable[[str], bool]) -> float:
        return sum(dur for lines in self.devices.values()
                   for name, _, dur in lines.ops if pred(name)) / 1e9

    def module_s(self, pred: Callable[[List[str]], bool]) -> float:
        """Device time of the program executions whose operations' names
        satisfy ``pred``."""
        total = 0.0
        for lines in self.devices.values():
            ops = sorted(lines.ops, key=lambda e: e[1])
            starts = [e[1] for e in ops]
            for _, s, d in lines.modules:
                lo = bisect.bisect_left(starts, s)
                hi = bisect.bisect_right(starts, s + d)
                if pred([name for name, _, _ in ops[lo:hi]]):
                    total += d
        return total / 1e9

    def top_ops(self, n: int = 10) -> List[list]:
        """Leaf operations by total device time (loops and calls, which
        only hold other operations, left out); names cut at 160
        characters."""
        by: Dict[str, float] = {}
        for lines in self.devices.values():
            for name, _, dur in lines.ops:
                if _CONTAINERS.search(name):
                    continue
                key = name[:160]
                by[key] = by.get(key, 0.0) + dur
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest gaps between programs on the first chip, each named
        by the innermost host span that covers its middle."""
        if not self.devices:
            return []
        dev = sorted(self.devices)[0]
        lines = self.devices[dev]
        spans = sorted((s, s + d) for _, s, d in (lines.modules or lines.ops))
        gaps = []
        end = None
        for s, e in spans:
            if end is not None and s > end:
                gaps.append((s - end, end, s))
            end = e if end is None else max(end, e)
        gaps.sort(reverse=True)
        out = []
        for length, lo, hi in gaps[:n]:
            out.append([self.host_span_at((lo + hi) / 2), length / 1e9])
        return out

    def host_span_at(self, t_ns: float) -> str:
        best: Optional[Event] = None
        for ev in self.host:
            name, s, d = ev
            if s <= t_ns <= s + d and (best is None or d < best[2]):
                best = ev
        return best[0] if best is not None else "none"


def union_ns(events: Iterable[Event]) -> float:
    total, end = 0.0, None
    for s, e in sorted((s, s + d) for _, s, d in events):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def summarize(planes) -> TraceSummary:
    """``planes``: iterable of objects with ``name`` and ``lines``, each
    line with ``name`` and ``events`` of ``name``, ``start_ns`` and
    ``duration_ns`` (``ProfileData``'s shape)."""
    devices: Dict[str, DeviceLines] = {}
    host: List[Event] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = DeviceLines()
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events]
                if line.name == MODULES:
                    lines.modules.extend(evs)
                elif line.name == OPS:
                    lines.ops.extend(evs)
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return TraceSummary(devices, host)


def load(trace_dir: str) -> TraceSummary:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(ProfileData.from_file(paths[-1]).planes)
