"""Build the served cluster through the program's normal path, warm it,
and drive it open-loop on the wall clock.

``repro.api.build_cluster`` -> ``LiveCluster`` -> ``InstanceEngine`` ->
``PagedStore`` -> Pallas kernels, with weights the benchmark makes.  The
benchmark's own loop submits each request when it falls due and calls
``LiveCluster.step``; the iteration clock inside the program plays no
part in any number reported.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.stats import RequestRecord
from chipbench.traffic import Mix, TimedRequest


def build(cfg_json: dict, ref_module, seed: int, chips: int):
    """(weights, cluster) for one cell."""
    from repro.api import ServeSpec, build_cluster
    from repro.models import init_params
    from chipbench.weights import key32, make_params
    pcfg = ref_module.program_config(cfg_json)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), pcfg))
    params = make_params(seed, shapes, cfg_json["num_hidden_layers"],
                         device=jax.devices()[0])
    jax.block_until_ready(params)
    serve = cfg_json["serve"]
    spec = ServeSpec(arch=pcfg.name, reduced=False, policy=serve["policy"],
                     n_instances=2 if chips == 1 else 2 * (chips // 2),
                     num_slots=serve["num_slots"],
                     kv_capacity=serve["kv_capacity"], seed=key32(seed, 2),
                     temperature=0.0, mesh_tp=1 if chips > 1 else None)
    return params, build_cluster(spec, cfg=pcfg, params=params)


# -- warm-up ----------------------------------------------------------------


def _buckets(lo: int, hi: int, **kw) -> List[int]:
    """The program's own buckets (``stepplan.bucket_len``) of every value
    in ``[lo, hi]``."""
    from repro.stepplan import bucket_len
    return sorted({bucket_len(n, **kw) for n in range(lo, hi + 1)})


def _prefill_buckets(mix: Mix, cap: int) -> List[int]:
    """Whole prompts plan at the planner's floor of 16 (``Planner``), and
    the engine clamps the bucket to its cache window."""
    lo, hi = mix.shapes()["prompt"]
    return _buckets(lo, hi, floor=16, cap=cap)


def _decode_blocks(mix: Mix, block_lines: int, blocks_per_slot: int,
                   cap: int) -> List[int]:
    """Block buckets of a decode step whose longest row holds from the
    shortest prompt to the longest prompt plus answer
    (``InstanceEngine.decode_multi``)."""
    p_lo, p_hi = mix.shapes()["prompt"]
    o_hi = mix.shapes()["output"][1]
    need_lo = -(-min(p_lo + 1, cap) // block_lines)
    need_hi = -(-min(p_hi + o_hi, cap) // block_lines)
    return _buckets(need_lo, need_hi, floor=1, cap=blocks_per_slot)


def warm_programs(cluster, mix: Mix) -> int:
    """Dispatch every program shape the mix can reach through each
    engine's own jits: the one-row batched prefill at each bucket, the
    paged decode step at every row count and block bucket, and the
    store's slot-indexed copies for every slot.  Writes only rows of
    free slots, which no request reads before it writes them itself.
    Returns the number of shapes dispatched."""
    from repro.models import init_state
    from repro.serving.sampling import decode_keys, sample_slots
    n = 0
    engines = cluster.engines
    for eng in engines:
        store, cfg = eng.store, eng.cfg
        cap = eng.kv_capacity
        for bucket in _prefill_buckets(mix, cap):
            fresh = init_state(cfg, 1, bucket)
            with eng._mesh_ctx():
                logits, fresh = eng._jit_prefill_batched(
                    eng.params, jnp.zeros((1, bucket), jnp.int32), fresh,
                    jnp.ones((1,), jnp.int32))
            np.asarray(sample_slots(logits, jax.random.PRNGKey(0),
                                    jnp.asarray([0], jnp.int32), 0.0))
            for slot in range(eng.num_slots):
                store.merge_slot_rows(slot, fresh, 0, bucket)
            n += 1
        _, keys = decode_keys(jax.random.PRNGKey(0), 1)
        for blocks in _decode_blocks(mix, store.block_lines,
                                     store.line_blocks_per_slot, cap):
            for rows in range(1, eng.num_slots + 1):
                slots = list(range(rows))
                tables = jnp.asarray(np.stack(
                    [np.arange(s * store.line_blocks_per_slot,
                               s * store.line_blocks_per_slot + blocks,
                               dtype=np.int32) for s in slots]))
                # the engine's own eager lift of the token column
                tok_in = jnp.asarray(np.zeros((rows,), np.int32))[:, None]
                with eng._mesh_ctx():
                    toks, store.state, emitted = eng._jit_decode_multi(
                        eng.params, tok_in, store.state,
                        jnp.full((rows,), blocks * store.block_lines - 1,
                                 jnp.int32),
                        jnp.asarray(slots), tables,
                        jnp.ones((rows,), jnp.int32), keys)
                np.asarray(toks)
                np.asarray(emitted)
                n += 1
    # slot-indexed state movement between every pair of partners
    for a, b in zip(engines[0::2], engines[1::2]):
        for src, dst in ((a, b), (b, a)):
            for slot in range(src.num_slots):
                for path, chunk in src.store.stream_slot(slot):
                    dst.store.import_chunk(slot, path, chunk)
                dst.store.copy_lines(src.store, slot, slot, 1, 2)
    for eng in engines:
        jax.block_until_ready(eng.store.state)
    return n


# -- the open loop ----------------------------------------------------------


@dataclass
class Drive:
    """What one pass of the open loop saw."""
    records: List[RequestRecord]
    requests: list                       # the program's Request objects
    steps: int = 0
    step_s: float = 0.0


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


def drive(cluster, timed: Sequence[TimedRequest], seconds: float,
          annotate: bool = False) -> Drive:
    """Offer ``timed`` on the wall clock for ``seconds`` and stamp every
    output token."""
    from repro.serving.request import Phase, Request
    records = [RequestRecord(t.due_s, t.prompt_len, t.output_len)
               for t in timed]
    reqs: List[Optional[object]] = [None] * len(timed)
    live: List[int] = []
    seen: Dict[int, int] = {}
    out = Drive(records, reqs)
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        while i < len(timed) and timed[i].due_s <= now:
            t = timed[i]
            with _annotate(annotate, "bench.submit"):
                req = Request(prompt_len=t.prompt_len,
                              max_new_tokens=t.output_len,
                              prompt_tokens=t.tokens)
                cluster.submit(req)
            records[i].submit_s = time.perf_counter() - t0
            reqs[i] = req
            live.append(i)
            seen[i] = 0
            i += 1
        if cluster.pending():
            s0 = time.perf_counter()
            with _annotate(annotate, "bench.step"):
                cluster.step()
            stamp = time.perf_counter() - t0
            out.step_s += stamp - (s0 - t0)
            out.steps += 1
            keep = []
            for j in live:
                req = reqs[j]
                n = len(req.output_tokens)
                if n > seen[j]:
                    records[j].token_s.extend([stamp] * (n - seen[j]))
                    seen[j] = n
                if req.phase is Phase.DONE:
                    records[j].finished = True
                elif req.phase in (Phase.SHED, Phase.ABORTED):
                    records[j].failed = True
                else:
                    keep.append(j)
            live = keep
        else:
            nxt = timed[i].due_s if i < len(timed) else seconds
            with _annotate(annotate, "bench.wait"):
                time.sleep(min(max(0.0, min(nxt, seconds) - now), 0.05))
    return out
