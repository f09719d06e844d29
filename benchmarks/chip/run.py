#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload sc2-3b.code --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` at the checkout root) names a configuration
(``configs/<name>.json`` with its plain reference ``configs/<reference>.py``)
and a traffic mix (``traffic/<name>.json``).  Set-up makes the weights
from the seed on the chip, builds the cluster through
``repro.api.build_cluster`` and warms every program shape the mix can
reach; the window then offers the mix open-loop on the wall clock for
``--seconds``.  With ``--trace 0`` the last stdout line reports the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
(``metrics/<name>.py``) from a profiled window.  After the window a
sample of the finished requests is compared with the plain reference;
that decides ``correct``; with ``control=True`` (``control.py``) the
float8 control takes the program's place in that comparison.

Exits nonzero with no result line when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

#: scratch for the profiler's trace, inside the checkout
TRACE_DIR = ROOT / ".bench" / "trace"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: Path = ROOT) -> dict:
    """Everything BENCHMARK.json and the files it names say of one cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((root / conf["file"]).read_text())

    def applies(m):
        return workload in m.get("workloads", [workload])

    return dict(cell=cell, cfg=cfg,
                ref=load_module(HERE / "configs" / f"{cfg['reference']}.py"),
                mix=HERE / "traffic" / f"{cell['traffic']}.json",
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


class CompileCounter:
    """Programs built, from jax.monitoring: the backend-compile event
    wraps both a compile and a load from the persistent cache."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def counters(cluster) -> dict:
    from repro.meshserve import STATS
    return {"host_syncs": sum(e.host_syncs for e in cluster.engines),
            "sched_time_s": cluster.sched_time_s,
            "n_iterations": cluster.n_iterations,
            "mirror_syncs": cluster.stats["mirror_syncs"],
            "d2d_bytes": STATS.d2d_bytes}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(info: dict, seed: int, seconds: float, trace: bool,
        control: bool = False, t_start: float = T_PROCESS) -> dict:
    """Set up, measure and check one run; returns the result object.
    With ``control`` the comparison judges the float8 control's tokens
    in place of the program's, under the same limits."""
    import jax
    from chipbench import check, driver, readers, stats
    from chipbench.costs import Dims
    from chipbench.traffic import Mix

    compiles = CompileCounter()
    cell, cfg = info["cell"], info["cfg"]
    chips = int(cell["chips"])
    devices = jax.devices()[:chips]
    mix = Mix.load(info["mix"])

    # -- set-up -------------------------------------------------------------
    parts = {"imports": time.perf_counter() - t_start}
    params, cluster = driver.build(cfg, info["ref"], seed, chips)
    parts["build"] = time.perf_counter() - t_start
    driver.warm_programs(cluster, mix)
    parts["warm_programs"] = time.perf_counter() - t_start
    timed = mix.requests(seed, seconds, cfg["vocab_size"])
    before = counters(cluster)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    setup_s = time.perf_counter() - t_start

    # -- the window ---------------------------------------------------------
    compiles_at_open = compiles.n
    if trace:
        jax.profiler.start_trace(str(TRACE_DIR))
    d = driver.drive(cluster, timed, seconds, annotate=trace)
    after = counters(cluster)
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.n - compiles_at_open
    peak = memory_peak(devices)
    st = dict(cluster.stats)

    # -- free the cluster, then compare with the reference -----------------
    finished = [r for r, rec in zip(d.requests, d.records)
                if r is not None and rec.finished]
    del cluster
    gc.collect()
    picked = check.sample(finished, seed)
    short = sum(1 for r in picked if len(r.output_tokens) != r.max_new_tokens)
    t_check = time.perf_counter()
    cmp = check.compare(check.Checker(info["ref"], cfg, params,
                                      control=control), picked)
    check_s = time.perf_counter() - t_check
    limits = cfg["limits"]
    gap = cmp["control_gap_max" if control else "logit_gap_max"]
    compared = {
        "logit_gap_max": {"value": gap, "limit": limits["logit_gap_max"]},
        "short_requests": {"value": short, "limit": 0},
        "compared_tokens": {"value": cmp["tokens"],
                            "limit": limits["compared_tokens_min"]}}
    correct = bool(picked and short == 0
                   and cmp["tokens"] >= limits["compared_tokens_min"]
                   and gap <= limits["logit_gap_max"])

    # -- metrics ------------------------------------------------------------
    due = stats.due_in_window(d.records, seconds)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(due),
           "failed": sum(1 for r in due if r.failed)}
    if not trace:
        metrics = {}
        for m in info["end_to_end"]:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = stats.END_TO_END[m["name"]](d.records, seconds)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from chipbench import devtrace
        from chipbench.peaks import peaks_for
        summary = devtrace.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = readers.RunContext(
            records=d.records, window_s=seconds,
            counters={k: after[k] - before[k] for k in after},
            compiles=in_window, trace=summary,
            dims=Dims.from_config(cfg), peaks=peaks_for(dev.device_kind),
            chips=chips)
        metrics = {}
        for m in info["per_layer"]:
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = seconds
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.idle_gaps(10)}
    out["metrics"] = metrics
    out["device"] = device
    out["notes"] = {"setup_parts": parts, "compiles_in_window": in_window,
                    "gaps": cmp, "steps": d.steps,
                    "step_s": d.step_s, "check_s": check_s,
                    "served": len(finished), "stats": st,
                    "gen_late_ms_p99": stats.gen_late_ms_p99(d.records,
                                                             seconds)}
    out["check"] = compared
    return out


def use_compile_cache():
    """JAX's persistent compilation cache at one fixed path in this
    checkout (or where JAX_COMPILATION_CACHE_DIR says), holding every
    program however fast it compiled, so a second run builds nothing."""
    import jax
    from repro.launch.cache import use_compile_cache as at_checkout
    at_checkout(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    info = resolve(args.workload)
    require_chips(int(info["cell"]["chips"]))
    use_compile_cache()
    result = run(info, args.seed, args.seconds, bool(args.trace))
    for name, c in result["check"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
