"""End-to-end metric arithmetic over per-request wall-clock records.

Every time here is seconds on the host's clock, relative to the opening
of the measured window.  A request is timed from when it was *due*, not
from when the generator got round to submitting it, so a stall that
delays later submissions counts against them.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class RequestRecord:
    due_s: float
    prompt_len: int
    output_len: int
    submit_s: Optional[float] = None
    #: wall stamp of each output token, taken after the step that
    #: emitted it returned (the step reads its tokens back to the host)
    token_s: List[float] = field(default_factory=list)
    finished: bool = False
    failed: bool = False

    def ttft(self, window_s: float) -> float:
        """First-token time minus due time; a request with no first token
        by the window's end counts at that censored value."""
        first = self.token_s[0] if self.token_s else window_s
        return min(first, window_s) - self.due_s

    def gaps(self, window_s: float) -> List[float]:
        """Inter-token gaps that end inside the window."""
        ts = [t for t in self.token_s if t <= window_s]
        return [b - a for a, b in zip(ts, ts[1:])]


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (``numpy.percentile``'s default)."""
    if not len(values):
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in_window(records: Sequence[RequestRecord], window_s: float
                  ) -> List[RequestRecord]:
    return [r for r in records if r.due_s < window_s]


def ttft_p90_s(records, window_s: float) -> float:
    due = due_in_window(records, window_s)
    return percentile([r.ttft(window_s) for r in due], 90)


def all_gaps(records, window_s: float) -> List[float]:
    return [g for r in due_in_window(records, window_s)
            for g in r.gaps(window_s)]


def tbt_mean_ms(records, window_s: float) -> float:
    gaps = all_gaps(records, window_s)
    if not gaps:
        raise ValueError("no inter-token gap in the window")
    return 1e3 * float(sum(gaps)) / len(gaps)


def tbt_p95_ms(records, window_s: float) -> float:
    return 1e3 * percentile(all_gaps(records, window_s), 95)


def out_tok_s(records, window_s: float) -> float:
    n = sum(1 for r in records for t in r.token_s if t <= window_s)
    return n / window_s


def gen_late_ms_p99(records, window_s: float) -> Optional[float]:
    late = [r.submit_s - r.due_s for r in due_in_window(records, window_s)
            if r.submit_s is not None]
    return 1e3 * percentile(late, 99) if late else None


#: end-to-end metric name -> function of (records, window seconds)
END_TO_END = {
    "ttft_p90_s": ttft_p90_s,
    "tbt_mean_ms": tbt_mean_ms,
    "tbt_p95_ms": tbt_p95_ms,
    "out_tok_s": out_tok_s,
}
