#!/usr/bin/env python3
"""Bring-up run of the live serving path on a TPU chip.

    python chip_smoke.py             # one chip: one AcceLLM pair
    python chip_smoke.py --chips 4   # four one-chip instances, accellm vs vllm

Serves full-width ``starcoder2-3b`` in bf16 (random weights from a
seed) through ``repro.api.serve`` -> ``LiveCluster`` -> ``InstanceEngine``
-> paged store -> Pallas kernels, with prompts of 200-1,000 tokens so the
flash prefill kernel (buckets 256-1,024) and the paged decode kernel
both run.  Everything runs in this one process; it starts no child.

One chip: every request must finish with its ``max_new_tokens``.  Every
program the engines served is re-lowered from their own jits at the
shapes they ran and must contain the Mosaic kernel (``tpu_custom_call``)
where the shape rule puts one.  Four more requests then go through the
same cluster; while they decode, the paged kernel must match its jnp
oracle over their own block tables and written lines (and two planted
faults must not), and a decode step over them must give finite logits.

Four chips: each engine's params and pool must sit on a chip of its
own, AcceLLM must mirror KV device-to-device with no host copy, and
every request must finish under both ``accellm`` and ``vllm`` on the
same traffic.

Exits nonzero with no result line when JAX finds no TPU.  The last line
of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent
sys.path.insert(0, str(CHECKOUT / "src"))

ARCH = "starcoder2-3b"
NUM_SLOTS = 8
KV_CAPACITY = 4096
NEW_TOKENS = 32
SEED = 0
#: prompts of the requests checked mid-decode (buckets 512 and 1,024)
LIVE_PROMPTS = (300, 517, 768, 1000)
#: tokens each has decoded when the live checks run
LIVE_DECODED = 4


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def traffic(n_requests: int):
    from repro.workloads import Batch, UniformLengths, WorkloadSpec
    return WorkloadSpec(arrival=Batch(n_requests),
                        lengths=UniformLengths(prompt=(200, 1000),
                                               decode=(NEW_TOKENS,
                                                       NEW_TOKENS)),
                        name="smoke")


def serve_spec(policy: str, n_instances: int, n_requests: int,
               mesh_tp=None):
    from repro.api import ServeSpec
    return ServeSpec(arch=ARCH, reduced=False, policy=policy,
                     n_instances=n_instances, num_slots=NUM_SLOTS,
                     kv_capacity=KV_CAPACITY, seed=SEED, max_steps=4000,
                     traffic=traffic(n_requests), mesh_tp=mesh_tp)


def engine_devices(eng) -> set:
    import jax
    leaves = jax.tree_util.tree_leaves((eng.params, eng.store.state))
    return {d for x in leaves for d in x.devices()}


def run_serve(spec, cfg=None, params=None):
    """One ``repro.api.serve`` run; checks every request finished whole."""
    import jax
    from repro.api import serve
    t0 = time.perf_counter()
    report = serve(spec, cfg=cfg, params=params)
    for eng in report.cluster.engines:
        jax.block_until_ready(eng.store.state)
    wall = time.perf_counter() - t0
    print(report.describe(), flush=True)
    check(report.all_finished and not report.n_shed
          and not report.n_aborted
          and len(report.finished) == report.n_submitted,
          f"{spec.policy}: {report.describe().splitlines()[0]}")
    check_whole(report.finished, spec.policy)
    log(phase=f"serve_{spec.policy}", wall_s=wall,
        requests=report.n_submitted,
        prompt_lens=sorted(r.prompt_len for r in report.finished),
        host_syncs=sum(e.host_syncs for e in report.cluster.engines))
    return report


def check_whole(reqs, what: str) -> None:
    """Every request emitted exactly its ``max_new_tokens``."""
    short = [(r.rid, r.generated, r.max_new_tokens) for r in reqs
             if r.finish_time is None or r.generated != r.max_new_tokens
             or len(r.output_tokens) != r.max_new_tokens]
    check(not short, f"{what}: requests cut short {short}")


def compile_checked(fn, *args, what: str = ""):
    """Lower and compile ``fn`` (a jitted function) for the chip; the
    program must hold a Mosaic kernel (``tpu_custom_call``), else a
    kernel silently did not run."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    secs = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          f"{what or fn}: no tpu_custom_call in the compiled program")
    return compiled, secs


def abstract(tree):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def served_program(eng, key):
    """The engine's own jit behind ``key`` (an ``InstanceEngine.programs``
    key) and abstract arguments of the shapes it was dispatched at."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_state
    from repro.serving.sampling import decode_keys

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if key[0] == "prefill_batched":
        _, rows, bucket = key
        fresh = jax.eval_shape(lambda: init_state(eng.cfg, rows, bucket))
        return eng._jit_prefill_batched, (eng.params, i32(rows, bucket),
                                          fresh, i32(rows))
    if key[0] == "decode_multi":
        _, rows, blocks, steps = key
        keys = jax.eval_shape(
            lambda: decode_keys(jax.random.PRNGKey(0), steps)[1])
        return eng._jit_decode_multi, (
            eng.params, i32(rows, 1), abstract(eng.store.state), i32(rows),
            i32(rows), i32(rows, blocks), i32(rows), keys)
    raise SmokeFailure(f"engine {eng.instance_id} served {key}, a program "
                       f"this check cannot re-lower")


def programs_phase(engines) -> None:
    """Every program the engines served, re-lowered from the engines' own
    jits at the shapes they were dispatched at and compiled for the chip:
    each paged-decode program and each prefill program at a flash-kernel
    bucket must hold the Mosaic kernel (a prefill below it must not), and
    the widest served prefill, run on random prompts, gives finite
    logits.  Prefills are counted by the path their compiled program
    took."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_state
    from repro.models.attention import prefill_kernel
    t0 = time.perf_counter()
    paths = {"jnp": 0, "pallas": 0}
    compile_s = 0.0
    widest = None
    for eng in engines:
        for key, n in sorted(eng.programs.items()):
            fn, args = served_program(eng, key)
            c0 = time.perf_counter()
            with eng._mesh_ctx():
                compiled = fn.lower(*args).compile()
            compile_s += time.perf_counter() - c0
            kernel = "tpu_custom_call" in compiled.as_text()
            if key[0] == "prefill_batched":
                check(kernel == (prefill_kernel(key[2]) == "pallas"),
                      f"engine {eng.instance_id} {key}: flash kernel "
                      f"{'present' if kernel else 'missing'} against the "
                      f"shape rule")
                paths["pallas" if kernel else "jnp"] += n
                if widest is None or key[2] > widest[1][2]:
                    widest = (eng, key, compiled)
            else:
                check(kernel, f"engine {eng.instance_id} {key}: no "
                              f"tpu_custom_call in the served program")
    check(widest is not None, "no batched prefill was served")
    check(paths["pallas"] > 0, f"no prefill took the flash kernel: {paths}")
    eng, (_, rows, bucket), compiled = widest
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 1), (rows, bucket),
                              0, eng.cfg.vocab_size, jnp.int32)
    lens = jnp.full((rows,), bucket - 1, jnp.int32)
    logits, _ = compiled(eng.params, toks, init_state(eng.cfg, rows, bucket),
                         lens)
    check(logits.shape == (rows, eng.cfg.vocab_size),
          f"prefill logits {logits.shape}")
    check(bool(jnp.isfinite(logits).all()), "prefill logits not finite")
    log(phase="programs", wall_s=time.perf_counter() - t0,
        programs=sum(len(e.programs) for e in engines), compile_s=compile_s,
        prefill_paths=json.dumps(paths, sort_keys=True),
        prefill_logit_absmax=float(jnp.abs(logits).max()))


def kernel_check(eng) -> dict:
    """The paged Pallas kernel against its jnp oracle over ``eng``'s live
    requests: their own block tables (``line_block_table``) and written
    lines, in the served pool of the first and last attention layers.
    Two planted faults — the KV heads swapped, and each table moved to
    the next slot's region — must fail the same check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import (paged_decode_attention_pallas,
                                                paged_decode_attention_ref)
    store, cfg = eng.store, eng.cfg
    slots = eng.active_slots()
    lengths = np.asarray(eng.lengths[slots], np.int32)
    tabs = [store.line_block_table(eng.slot_req[s].rid) for s in slots]
    check(all(len(t) * store.block_lines >= n
              for t, n in zip(tabs, lengths)),
          f"engine {eng.instance_id}: tables shorter than written lines")
    tables = np.zeros((len(slots), max(map(len, tabs))), np.int32)
    for i, t in enumerate(tabs):
        tables[i, :len(t)] = t
    n_pool_blocks = store.num_slots * store.line_blocks_per_slot
    other_slot = (tables + store.line_blocks_per_slot) % n_pool_blocks
    tables, other_slot, lengths = map(jnp.asarray,
                                      (tables, other_slot, lengths))
    q = jax.random.normal(jax.random.PRNGKey(SEED + 2),
                          (len(slots), 1, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    layer = store.state["layers"][0]["p0"]
    kernel = oracle = None
    worst = {"ok": 0.0, "heads_swapped": np.inf, "other_slot": np.inf}
    for r in (0, layer["k"].shape[0] - 1):
        k_pool = store.pool_view(layer["k"][r])
        v_pool = store.pool_view(layer["v"][r])
        if kernel is None:
            kernel, _ = compile_checked(
                jax.jit(paged_decode_attention_pallas), q, k_pool, v_pool,
                tables, lengths, what="paged_decode_attention_pallas")
            oracle = jax.jit(paged_decode_attention_ref)
        ref = oracle(q, k_pool, v_pool, tables, lengths).astype(jnp.float32)
        # Tolerance, per output element.  Each side returns bf16 of
        # o = sum_i p_i v_i over the same bf16 K/V with f32 scores and
        # softmax; |o| <= A = sum_i p_i |v_i| (computed below with the
        # oracle over |V|).  A bf16 rounding of the weights p before the
        # PV product (the TPU's default matmul precision, on either side)
        # moves o by at most 2^-9 * A, and the final cast to bf16 by at
        # most 2^-9 * |o| <= 2^-9 * A: each side is within 2^-8 * A of the
        # exact o, so the two differ by at most 2^-7 * A.  Allow twice
        # that, 2^-6 * A, plus a floor for f32 roundoff where A ~ 0.
        a = oracle(q, k_pool, jnp.abs(v_pool), tables,
                   lengths).astype(jnp.float32)
        tol = 2.0 ** -6 * a + 1e-6

        def err(*args):
            out = kernel(q, *args).astype(jnp.float32)
            check(bool(jnp.isfinite(out).all()),
                  "paged kernel output not finite")
            return float((jnp.abs(out - ref) / tol).max())

        worst["ok"] = max(worst["ok"],
                          err(k_pool, v_pool, tables, lengths))
        worst["heads_swapped"] = min(
            worst["heads_swapped"],
            err(k_pool[:, ::-1], v_pool[:, ::-1], tables, lengths))
        worst["other_slot"] = min(worst["other_slot"],
                                  err(k_pool, v_pool, other_slot, lengths))
    check(worst["ok"] <= 1.0,
          f"paged kernel vs oracle: error {worst['ok']} x tolerance")
    for fault in ("heads_swapped", "other_slot"):
        check(worst[fault] > 1.0,
              f"planted fault {fault} stayed within tolerance "
              f"({worst[fault]} x): the check cannot fail")
    return dict(rows=len(slots), lengths=lengths.tolist(),
                table_blocks=int(tables.shape[1]),
                err_over_tol=worst["ok"],
                heads_swapped_err_over_tol=worst["heads_swapped"],
                other_slot_err_over_tol=worst["other_slot"])


def decode_logits_check(eng) -> float:
    """One paged decode step over ``eng``'s live requests, at the served
    state and the engine's own block tables, compiled with the kernel;
    its logits must be finite.  The step's state is discarded."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import decode_step_paged
    slots = eng.active_slots()
    t = np.asarray(eng.lengths[slots], np.int32)
    bl = eng.store.block_lines
    tables = eng.store.decode_block_tables(
        [eng.slot_req[s].rid for s in slots], -(-(int(t.max()) + 1) // bl))
    args = (eng.params, jnp.asarray(eng.last_tokens[slots])[:, None],
            eng.store.state, jnp.asarray(t), jnp.asarray(slots, jnp.int32),
            jnp.asarray(tables))
    step, _ = compile_checked(
        jax.jit(lambda p, tok, s, tt, sl, tb: decode_step_paged(
            eng.cfg, p, tok, s, tt, sl, tb, bl)), *args,
        what="decode_step_paged")
    logits, _ = step(*args)
    check(logits.shape == (len(slots), eng.cfg.vocab_size),
          f"decode logits {logits.shape}")
    check(bool(jnp.isfinite(logits).all()), "decode logits not finite")
    return float(jnp.abs(logits).max())


def live_phase(cluster) -> None:
    """Four more requests through the served cluster.  Once each has
    decoded a few tokens, every engine holding live primaries gets the
    kernel-vs-oracle check over their tables and one decode step whose
    logits must be finite; then the requests must finish whole."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.request import Phase, Request
    t0 = time.perf_counter()
    cfg = cluster.cfg
    rng = np.random.default_rng(SEED + 3)
    reqs = [Request(prompt_len=n, max_new_tokens=NEW_TOKENS,
                    prompt_tokens=jnp.asarray(
                        rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32))
            for n in LIVE_PROMPTS]
    for r in reqs:
        cluster.submit(r)
    for _ in range(200):
        if all(r.phase is Phase.DECODE and r.generated >= LIVE_DECODED
               for r in reqs):
            break
        cluster.step()
    check(all(r.phase is Phase.DECODE for r in reqs),
          f"live requests not decoding: {[r.phase for r in reqs]}")
    checked = 0
    for eng in cluster.engines:
        if not eng.slot_req:
            continue
        out = kernel_check(eng)
        out["decode_logit_absmax"] = decode_logits_check(eng)
        log(phase="kernel", engine=eng.instance_id,
            **{k: json.dumps(v) for k, v in out.items()})
        checked += out["rows"]
    check(checked == len(reqs), f"{checked} live primaries checked, "
                                f"{len(reqs)} submitted")
    cluster.run(max_steps=4000)
    check_whole(reqs, "live")
    log(phase="live", wall_s=time.perf_counter() - t0,
        prompt_lens=list(LIVE_PROMPTS))


def one_chip(cfg) -> None:
    from repro.models.attention import kernel_backend
    check(kernel_backend() == "pallas",
          f"kernel backend {kernel_backend()!r}, not the compiled kernels")
    report = run_serve(serve_spec("accellm", n_instances=2, n_requests=6),
                       cfg=cfg)
    engines = report.cluster.engines
    for eng in engines:
        devs = engine_devices(eng)
        check(all(d.platform == "tpu" for d in devs),
              f"engine {eng.instance_id} holds state off the chip: {devs}")
    stats = report.stats
    log(phase="stats", **{k: v for k, v in stats.items()
                          if k in ("prefills", "decode_steps",
                                   "mirror_syncs", "mirror_bytes",
                                   "replica_promotions", "rebalances")})
    programs_phase(engines)
    live_phase(report.cluster)


def four_chips(cfg) -> None:
    """Two AcceLLM pairs, one instance per chip, against vllm on the same
    chips and traffic."""
    import jax
    from repro.meshserve import STATS
    from repro.models import init_params
    # one host copy that every engine places on its own chip (a device
    # copy would hold a second set of weights on the first chip)
    params = jax.device_get(init_params(jax.random.PRNGKey(SEED), cfg))
    for policy in ("accellm", "vllm"):
        STATS.reset()
        report = run_serve(serve_spec(policy, n_instances=4,
                                         n_requests=8, mesh_tp=1),
                              cfg=cfg, params=params)
        placed = [engine_devices(eng) for eng in report.cluster.engines]
        check(all(len(d) == 1 for d in placed),
              f"{policy}: an engine spans several devices: {placed}")
        check(len(set.union(*placed)) == len(placed),
              f"{policy}: engines share a device: {placed}")
        log(phase=f"mesh_{policy}",
            devices=[str(next(iter(d))) for d in placed],
            d2d_copies=STATS.d2d_copies, d2d_bytes=STATS.d2d_bytes,
            host_copies=STATS.host_copies,
            mirror_syncs=report.stats["mirror_syncs"])
        check(STATS.host_copies == 0,
              f"{policy}: {STATS.host_copies} host copies")
        if policy == "accellm":
            check(STATS.d2d_copies > 0,
                  "accellm made no device-to-device mirror copy")
        del report
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-instance mesh phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.cache import use_compile_cache
    log(device_kind=repr(dev.device_kind), devices=len(devices),
        jax=jax.__version__, compile_cache=use_compile_cache(CHECKOUT))
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(total_wall_s=time.perf_counter() - t0,
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
