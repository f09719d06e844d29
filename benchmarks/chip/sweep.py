#!/usr/bin/env python3
"""Forced-rate sweep of a cell: the rate from which its queue grows.

    python3 benchmarks/chip/sweep.py --workload sc2-3b.x4.chat --seed 7 \
        --rates 0.6 1.0 1.4 1.8 --seconds 25

Builds and warms the cell's cluster once (as ``run.run`` does), then
offers the cell's traffic mix at each rate in turn, in ascending order,
for ``--seconds`` each: the same lengths and the same order, the arrival
gaps scaled to the rate.  After each window every request still in the
program is aborted, so the next rate starts from an empty cluster.  One
JSON line per rate: ``ttft_p90_s``, ``tbt_mean_ms``, the requests due, the
ones with a first token and the ones finished, and ``grows``.  The queue
grows at a rate where ``ttft_p90_s`` passes ``GROWS_TTFT_S`` or more than
``GROWS_WAITING`` of the requests due are still without a first token at
the window's end; the sweep stops at the first such rate.  The last line
gives the knee (midway between the highest rate that held and the first
that grew) and 0.8 of it, the rate a cell runs at.

Exits nonzero when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import run as bench

GROWS_TTFT_S = 2.0
GROWS_WAITING = 0.25


def sweep(info: dict, seed: int, rates, seconds: float) -> list:
    from chipbench import driver, stats
    from chipbench.traffic import Mix
    from repro.serving.request import Phase
    cfg = info["cfg"]
    mix = Mix.load(info["mix"])
    _, cluster = driver.build(cfg, info["ref"], seed,
                              int(info["cell"]["chips"]))
    driver.warm_programs(cluster, mix)
    out = []
    for rate in sorted(rates):
        timed = dataclasses.replace(mix, rate_per_s=rate).requests(
            seed, seconds, cfg["vocab_size"])
        d = driver.drive(cluster, timed, seconds)
        due = stats.due_in_window(d.records, seconds)
        waiting = sum(1 for r in due if not r.token_s)
        ttft = stats.ttft_p90_s(d.records, seconds)
        line = {"rate_per_s": rate, "ttft_p90_s": ttft,
                "tbt_mean_ms": stats.tbt_mean_ms(d.records, seconds),
                "due": len(due), "first_token": len(due) - waiting,
                "finished": sum(1 for r in due if r.finished),
                "grows": (ttft > GROWS_TTFT_S
                          or waiting > GROWS_WAITING * len(due))}
        print(json.dumps(line), flush=True)
        out.append(line)
        for req in d.requests:
            if req is not None and req.phase not in (
                    Phase.DONE, Phase.SHED, Phase.ABORTED):
                cluster.abort(req.rid)
        assert not cluster.pending(), "requests left after the aborts"
        if line["grows"]:
            break
    return out


def knee(lines: list):
    """Midway between the highest rate that held and the first that
    grew; None when none grew."""
    held = [ln["rate_per_s"] for ln in lines if not ln["grows"]]
    grew = [ln["rate_per_s"] for ln in lines if ln["grows"]]
    if not grew:
        return None
    return (max(held) + min(grew)) / 2 if held else min(grew) / 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    info = bench.resolve(args.workload)
    bench.require_chips(int(info["cell"]["chips"]))
    bench.use_compile_cache()
    lines = sweep(info, args.seed, args.rates, args.seconds)
    k = knee(lines)
    print(json.dumps({"knee_per_s": k,
                      "cell_rate_per_s": None if k is None
                      else round(0.8 * k, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
