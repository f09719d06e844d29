#!/usr/bin/env python3
"""The control of a cell's correctness check, on the chip.

    python3 benchmarks/chip/control.py --workload sc2-3b.code \
        --seeds 11 12 13 --seconds 50

For each seed, in one process, one whole run of the cell (``run.run``:
weights and cluster from the seed, program warm-up, the cell's traffic
offered open-loop for ``--seconds``) with the float8 control in the
program's place in the comparison: the same sample of finished requests,
the same limits, the same decision.  A sound check comes out
``correct: false`` here on every seed.  Each line also carries the
program's own reading on the same sample (``program_gap_max``), the
lower reading a limit is set from.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as bench


def free_device():
    """Drop every array the finished run left on the chips, so the next
    seed's weights and cluster find the memory the first one found."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    info = bench.resolve(args.workload)
    bench.require_chips(int(info["cell"]["chips"]))
    bench.use_compile_cache()
    for seed in args.seeds:
        out = bench.run(info, seed, args.seconds, trace=False, control=True,
                        t_start=time.perf_counter())
        notes = out["notes"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"],
            "control_gap_max": notes["gaps"]["control_gap_max"],
            "program_gap_max": notes["gaps"]["logit_gap_max"],
            "tokens": notes["gaps"]["tokens"],
            "compiles_in_window": notes["compiles_in_window"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "check": out["check"]}), flush=True)
        free_device()
    return 0


if __name__ == "__main__":
    sys.exit(main())
