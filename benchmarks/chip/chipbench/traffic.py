"""The one traffic generator: a mix file of parameters -> timed requests.

A mix (``traffic/<name>.json``) gives the arrival process and the length
distributions.  A window of ``seconds`` offers exactly ``rate * seconds``
requests.  Prompt lengths, output lengths and inter-arrival gaps are
drawn at the stratified quantiles of their distributions, the gaps
scaled so that the last request falls due half a mean gap before the
window closes, and put in one fixed order; the seed draws only the
prompt token ids.  So every seed offers the same work at the same times,
and the spread between runs is the system's, not the dice's (an order
drawn per seed moved the TTFT tail by tens of percent on its own).

The distributions follow ``repro.workloads.arrivals.Poisson`` (exponential
gaps at ``rate_per_s``) and ``repro.workloads.lengths.LognormalLengths``
(``exp(N(log median, sigma))`` clipped to ``[min, max]``), copied here so
that no change to the program can move the yardstick.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

#: seed of the one order in which every window offers its requests
ORDER_SEED = 0


@dataclass(frozen=True)
class TimedRequest:
    due_s: float          # seconds after the window opens
    prompt_len: int
    output_len: int
    tokens: np.ndarray    # (1, prompt_len) int32 prompt ids


@dataclass(frozen=True)
class Mix:
    name: str
    rate_per_s: float
    prompt: dict
    output: dict

    @classmethod
    def load(cls, path: Path) -> "Mix":
        spec = json.loads(Path(path).read_text())
        arrival = spec["arrival"]
        if arrival["process"] != "poisson":
            raise ValueError(f"{path}: unknown arrival process "
                             f"{arrival['process']!r}")
        return cls(name=Path(path).stem,
                   rate_per_s=float(arrival["rate_per_s"]),
                   prompt=spec["prompt"], output=spec["output"])

    def lengths(self, which: str, n: int) -> np.ndarray:
        """``n`` lengths at the stratified quantiles ``(i + 1/2) / n`` of
        the clipped lognormal."""
        d = self.prompt if which == "prompt" else self.output
        if d["dist"] != "lognormal":
            raise ValueError(f"unknown length distribution {d['dist']!r}")
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        raw = np.exp(math.log(d["median"]) + d["sigma"] * z).astype(np.int64)
        return np.clip(raw, d["min"], d["max"])

    def gaps(self, n: int) -> np.ndarray:
        """``n`` exponential inter-arrival gaps at stratified quantiles."""
        u = (np.arange(n) + 0.5) / n
        return -np.log1p(-u) / self.rate_per_s

    def count(self, seconds: float) -> int:
        return max(1, round(self.rate_per_s * seconds))

    def requests(self, seed: int, seconds: float,
                 vocab: int) -> List[TimedRequest]:
        """The ``count(seconds)`` requests of one window, in due order."""
        n = self.count(seconds)
        order = np.random.default_rng(ORDER_SEED)
        prompts = order.permutation(self.lengths("prompt", n))
        outputs = order.permutation(self.lengths("output", n))
        gaps = order.permutation(self.gaps(n))
        rng = np.random.default_rng(np.random.SeedSequence([seed]))
        due = np.cumsum(gaps) * (seconds * (n - 0.5) / n) / gaps.sum()
        return [TimedRequest(float(t), int(p), int(o),
                             rng.integers(0, vocab, (1, int(p)),
                                          dtype=np.int32))
                for t, p, o in zip(due, prompts, outputs)]

    def shapes(self) -> dict:
        """Bounds of the lengths this mix can produce."""
        return {"prompt": (int(self.prompt["min"]), int(self.prompt["max"])),
                "output": (int(self.output["min"]), int(self.output["max"]))}
