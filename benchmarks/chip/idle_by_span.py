#!/usr/bin/env python3
"""One traced run of a cell, with the first chip's idle time put down to
the program's host spans.

    python3 benchmarks/chip/idle_by_span.py --workload sc2-3b.code \
        --seed 7 --seconds 50 [--out idle.json]

Runs ``run.run`` as ``run.py --trace 1`` does, keeps the trace
summary, and lines the program's span ring up with it
(``chipbench/spans.py``).  Prints, and writes to ``--out``:

- ``result``: the run's own result object (per-layer metrics, ``correct``);
- ``alignment``: the clock offset, its slack and the worst overhang of a
  matched step, and how many steps were matched;
- ``idle``: chip 0's idle time in the window by innermost program span
  (``null``: outside every program span), in seconds and in percent of
  the window, and the three parts (non-readback spans, readbacks,
  outside) against ``device_idle_pct``'s reading of chip 0;
- ``spans``: count and host seconds of each span name in the window;
- ``programs`` / ``kernels``: the chips' program names and the custom
  calls the readers' kernel predicates select, with device seconds;
- ``cost``: host microseconds per span, with the profiler off and on.

Exits nonzero when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

import run as bench  # noqa: E402


def span_cost_us(n: int) -> float:
    """Host microseconds one ``repro.obs.span`` costs, over ``n``."""
    from repro import obs
    t = time.perf_counter()
    for _ in range(n):
        with obs.span("cost.probe"):
            pass
    return 1e6 * (time.perf_counter() - t) / n


def cost(n: int = 100_000) -> dict:
    import shutil
    import jax
    span_cost_us(n // 10)                          # warm
    off = span_cost_us(n)
    trace_dir = bench.ROOT / ".bench" / "cost_trace"
    jax.profiler.start_trace(str(trace_dir))
    on = span_cost_us(n // 5)
    jax.profiler.stop_trace()
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"us_per_span_profiler_off": off, "us_per_span_profiler_on": on,
            "spans": n}


def analyse(summary, result: dict) -> dict:
    from chipbench import readers, spans
    al = spans.align(spans.ring_events(), summary)
    out = {"result": result}
    if al is None:
        out["alignment"] = None
        return out
    lo, hi = al.window
    window_s = (hi - lo) / 1e9
    # each matched serve.step against its bench.step, after the offset
    wrappers = sorted((e for e in summary.host if e[0] == "bench.step"),
                      key=lambda e: e[1])
    over = max(max(b[1] - s[1], (s[1] + s[2]) - (b[1] + b[2]), 0.0)
               for b, s in zip(wrappers, al.steps))
    out["alignment"] = {"offset_ns": al.offset_ns, "slack_ns": al.slack_ns,
                        "worst_overhang_ns": over, "steps": len(al.steps),
                        "window_s": window_s}
    idle = spans.idle_by_span(al, summary)
    chip0 = sorted(summary.devices)[0]
    idle_pct_chip0 = 100.0 * (1.0 - summary.busy_s(chip0)
                              / result["device"]["window_s"])
    parts = {"host_spans": sum(v for k, v in idle.items()
                               if k not in (None, spans.READBACK)),
             "readback": idle.get(spans.READBACK, 0.0),
             "outside": idle[None]}
    out["idle"] = {
        "by_span_s": {str(k): v / 1e9 for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])},
        "by_span_pct": {str(k): 100 * v / (hi - lo) for k, v in
                        sorted(idle.items(), key=lambda kv: -kv[1])},
        "parts_pct": {k: 100 * v / (hi - lo) for k, v in parts.items()},
        "parts_sum_pct": 100 * sum(parts.values()) / (hi - lo),
        "device_idle_pct_chip0": idle_pct_chip0}
    count, host = defaultdict(int), defaultdict(float)
    for name, s, d, _ in al.events:
        if lo <= s <= hi:
            count[name] += 1
            host[name] += d / 1e9
    out["spans"] = {n: {"count": count[n], "host_s": host[n]}
                    for n in sorted(host, key=lambda n: -host[n])}
    out["spans_per_step"] = sum(count.values()) / len(al.steps)
    programs = defaultdict(float)
    kernels = defaultdict(float)
    for lines in summary.devices.values():
        for name, _, d in lines.modules:
            programs[name.split("(")[0]] += d / 1e9
        for name, _, d in lines.ops:
            if readers.flash_kernel(name) or readers.paged_kernel(name):
                kind = "flash" if readers.flash_kernel(name) else "paged"
                kernels[f"{kind}: {name.split(' = ')[0]}"] += d / 1e9
    out["programs"] = dict(sorted(programs.items(), key=lambda kv: -kv[1]))
    out["kernels"] = dict(sorted(kernels.items(), key=lambda kv: -kv[1]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    info = bench.resolve(args.workload)
    bench.require_chips(int(info["cell"]["chips"]))
    bench.use_compile_cache()
    from chipbench import devtrace
    kept = {}
    load = devtrace.load

    def keep(trace_dir):
        kept["summary"] = load(trace_dir)
        return kept["summary"]

    devtrace.load = keep
    result = bench.run(info, args.seed, args.seconds, True,
                       t_start=T_PROCESS)
    out = analyse(kept["summary"], result)
    out["cost"] = cost()
    text = json.dumps(out, indent=1)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(text)
    print(json.dumps({k: out[k] for k in out if k != "programs"}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
