"""The attention kernels compile for a TPU v5e at starcoder2-3b widths.

Interpret mode runs a kernel body on the CPU but never asks Mosaic
whether the block shapes tile the chip's memory; these tests do, by
compiling against a described (not attached) v5e.  Nothing runs, so the
tests check only that each program compiles and holds the kernel
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas

CFG = get_config("starcoder2-3b")
H, KVH, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
DT = jnp.bfloat16
SLOTS, CAP = 8, 4096          # one engine's store in chip_smoke.py


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_decode_compiles_for_v5e(one_chip):
    bl = 16
    per_page = CAP // bl
    pool = _spec((SLOTS, KVH, per_page, bl, HD), DT, one_chip)
    _assert_kernel(paged_decode_attention_pallas,
                   _spec((SLOTS, 1, H, HD), DT, one_chip), pool, pool,
                   _spec((SLOTS, per_page), jnp.int32, one_chip),
                   _spec((SLOTS,), jnp.int32, one_chip))


def test_flash_prefill_compiles_for_v5e(one_chip):
    bucket = 1024
    kv = _spec((1, bucket, KVH, HD), DT, one_chip)
    _assert_kernel(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               window=CFG.sliding_window),
        _spec((1, bucket, H, HD), DT, one_chip), kv, kv)


def test_dense_decode_compiles_for_v5e(one_chip):
    cache = _spec((SLOTS, KVH, CAP, HD), DT, one_chip)
    _assert_kernel(decode_attention_pallas,
                   _spec((SLOTS, 1, H, HD), DT, one_chip), cache, cache,
                   _spec((SLOTS,), jnp.int32, one_chip))
