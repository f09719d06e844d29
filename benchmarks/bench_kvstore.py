"""KV-store microbenchmarks: what the paged refactor buys.

Measures, on the reduced live engine (CPU):

* mirror-sync traffic per decode step — dense whole-slot copy (the old
  O(kv_capacity) semantics) vs the paged delta (one KV line, §4.1.2),
* mirror-sync wall time — full export/import vs ``sync_replica_from``
  delta copy,
* decode step time on the paged engine,
* paged vs dense decode-attention kernel (interpret mode, tiny shape).

Writes a ``BENCH_kvstore.json`` snapshot next to the repo root so CI
keeps a machine-readable record of mirror bytes/step.
"""
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import SMOKE, emit
from repro.configs import get_config
from repro.core.kvbytes import bytes_per_token, state_bytes_at
from repro.models import init_params
from repro.serving import InstanceEngine, Request

SNAPSHOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_kvstore.json")


def _mk(cfg, i, plen=32, new=64):
    return Request(prompt_len=plen, max_new_tokens=new,
                   prompt_tokens=jax.random.randint(
                       jax.random.fold_in(jax.random.PRNGKey(9), i),
                       (1, plen), 0, cfg.vocab_size))


def main():
    cfg = get_config("starcoder2-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    kv_capacity = 128 if SMOKE else 256
    snap = {}

    a = InstanceEngine(cfg, params, num_slots=4, kv_capacity=kv_capacity)
    b = InstanceEngine(cfg, params, num_slots=4, kv_capacity=kv_capacity,
                       instance_id=1)
    req = _mk(cfg, 0)
    slot = a.prefill_request(req)
    chunks, length, last, lines = a.export_stream(slot)
    b.import_stream(0, chunks, length, last, lines, req,
                    as_replica_of=(0, slot))

    # -- mirror traffic: dense whole-slot vs paged delta ----------------------
    dense_bytes = state_bytes_at(cfg, kv_capacity)   # old MirrorSync cost
    delta_bytes = bytes_per_token(cfg)               # one KV line
    emit("kvstore_mirror_bytes_dense", 0.0, f"bytes={dense_bytes:.0f}")
    emit("kvstore_mirror_bytes_paged", 0.0,
         f"bytes={delta_bytes:.0f};reduction={dense_bytes / delta_bytes:.0f}x")
    snap["mirror_bytes_per_step_dense"] = dense_bytes
    snap["mirror_bytes_per_step_paged"] = delta_bytes

    # -- mirror wall time: full copy vs delta copy ----------------------------
    n = 3 if SMOKE else 10
    a.decode()
    t0 = time.perf_counter()
    for _ in range(n):
        ex = a.export_slot(slot)
        b.store.merge_slot(0, ex[0])
        jax.block_until_ready(jax.tree_util.tree_leaves(b.state)[0])
    full_us = (time.perf_counter() - t0) / n * 1e6
    emit("kvstore_mirror_full_copy", full_us, f"kv_capacity={kv_capacity}")
    for _ in range(2):                    # warm the 1-line delta shape
        a.decode()
        b.sync_replica_from(a, slot, 0)
    total = 0.0
    for _ in range(n):
        a.decode()                        # untimed: grow one line
        t0 = time.perf_counter()
        b.sync_replica_from(a, slot, 0)
        jax.block_until_ready(jax.tree_util.tree_leaves(b.state)[0])
        total += time.perf_counter() - t0
    delta_us = total / n * 1e6
    emit("kvstore_mirror_delta_sync", delta_us,
         "1-line delta copy (ledger-bounded)")
    snap["mirror_full_copy_us"] = full_us
    snap["mirror_delta_sync_us"] = delta_us

    # -- decode step time on the paged engine ---------------------------------
    for i in range(1, 4):
        a.prefill_request(_mk(cfg, i))
    a.decode()
    t0 = time.perf_counter()
    for _ in range(n):
        a.decode()
    us = (time.perf_counter() - t0) / n * 1e6
    emit("kvstore_decode_step_b4", us,
         f"free_blocks={a.free_blocks()};used_GB={a.used_bytes() / 1e9:.4f}")
    snap["decode_step_us_b4"] = us

    # -- paged vs dense decode kernel (interpret mode, tiny) ------------------
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                paged_decode_attention_pallas)
    B, H, KVH, hd, W, bl = 2, 4, 2, 64, 128, 64
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, 1, H, hd))
    kc = jax.random.normal(k2, (B, KVH, W, hd))
    vc = jax.random.normal(k3, (B, KVH, W, hd))
    lengths = jnp.full((B,), W, jnp.int32)
    nb = W // bl
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    pool_k = kc.reshape(B, KVH, nb, bl, hd)
    pool_v = vc.reshape(B, KVH, nb, bl, hd)
    t0 = time.perf_counter()
    jax.block_until_ready(decode_attention_pallas(
        q, kc, vc, lengths, block_k=bl, interpret=True))
    emit("kvstore_kernel_dense_interp", (time.perf_counter() - t0) * 1e6,
         f"B={B};W={W}")
    t0 = time.perf_counter()
    jax.block_until_ready(paged_decode_attention_pallas(
        q, pool_k, pool_v, tables, lengths, interpret=True))
    emit("kvstore_kernel_paged_interp", (time.perf_counter() - t0) * 1e6,
         f"blocks={B * nb};block_lines={bl}")

    with open(SNAPSHOT, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("kvstore_snapshot", 0.0, SNAPSHOT)


if __name__ == "__main__":
    main()
