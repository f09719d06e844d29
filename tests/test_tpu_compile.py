"""The attention kernels compile for a TPU v5e at starcoder2-3b widths.

Interpret mode runs a kernel body on the CPU but never asks Mosaic
whether the block shapes tile the chip's memory; these tests do, by
compiling against a described (not attached) v5e.  Nothing runs, so the
tests check only that each program compiles and holds the kernel
(``tpu_custom_call``), and what the compiled decode program does with
its KV state.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import decode_multi, init_params, init_state
from repro.models.attention import set_kernel_backend
from repro.models.state import mla_line_width
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


def _assert_kernel(fn, *args, name):
    """The program holds the Mosaic kernel, under its ``pallas_call``
    name (what a device trace shows for the kernel's operation)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"%{name}." in text


def test_paged_decode_compiles_for_v5e(one_chip):
    bl = 16
    per_page = CAP // bl
    pool = _spec((SLOTS, KVH, per_page, bl, HD), DT, one_chip)
    _assert_kernel(paged_decode_attention_pallas,
                   _spec((SLOTS, 1, H, HD), DT, one_chip), pool, pool,
                   _spec((SLOTS, per_page), jnp.int32, one_chip),
                   _spec((SLOTS,), jnp.int32, one_chip),
                   name="paged_decode")


def test_flash_prefill_compiles_for_v5e(one_chip):
    bucket = 1024
    kv = _spec((1, bucket, KVH, HD), DT, one_chip)
    _assert_kernel(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               window=CFG.sliding_window),
        _spec((1, bucket, H, HD), DT, one_chip), kv, kv,
        name="flash_prefill")


def test_dense_decode_compiles_for_v5e(one_chip):
    cache = _spec((SLOTS, KVH, CAP, HD), DT, one_chip)
    _assert_kernel(decode_attention_pallas,
                   _spec((SLOTS, 1, H, HD), DT, one_chip), cache, cache,
                   _spec((SLOTS,), jnp.int32, one_chip),
                   name="paged_decode")


def _computations(text):
    """HLO computation name -> its body lines; the entry computation
    under ``"ENTRY"``."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _decode_multi_text(cfg, sharding, rows=4, steps=2, bl=16):
    """The v5e program text of the served ``decode_multi`` (pallas
    backend, 8 slots x 4,096 lines, ``block_lines`` 16, donated state)."""

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(a.shape, a.dtype, sharding), tree)

    params = described(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = described(jax.eval_shape(lambda: init_state(cfg, SLOTS, CAP)))
    rows_i32 = _spec((rows,), jnp.int32, sharding)
    fn = jax.jit(functools.partial(decode_multi, cfg, block_lines=bl),
                 donate_argnums=(2,))
    set_kernel_backend("pallas")
    try:
        return fn.lower(
            params, _spec((rows, 1), jnp.int32, sharding), state, rows_i32,
            rows_i32, _spec((rows, CAP // bl), jnp.int32, sharding),
            rows_i32, _spec((steps, 2), jnp.uint32, sharding)
        ).compile().as_text()
    finally:
        set_kernel_backend("auto")


def _assert_lines_in_place(text, leaf: str, n_leaves: int):
    """No operation whose result has the shape ``leaf`` (a regex of a
    per-token cache layer or stack) copies or relayouts it — no
    ``copy``, no ``copy*`` fusion, no scatter — and each of the
    ``n_leaves`` such stacks the program takes in is aliased to an
    output."""
    comps = _computations(text)
    bad = []
    for lines in comps.values():
        for line in lines:
            m = re.match(rf"\s*(?:ROOT )?%(\S+) = {leaf}\S* ([\w-]+)\(", line)
            if not m:
                continue
            name, op = m.groups()
            called = re.search(r"calls=%([\w.-]+)", line)
            body = comps.get(called.group(1), []) if called else []
            if (op in ("copy", "scatter")
                    or (op == "fusion" and (name.startswith("copy")
                                            or any(" scatter(" in b
                                                   for b in body)))):
                bad.append(f"{name} = {op}")
    assert not bad, bad

    params = {int(n) for n in re.findall(
        rf"= {leaf}\S* parameter\((\d+)\)", "\n".join(comps["ENTRY"]))}
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    aliased = {int(n) for n in re.findall(r"\((\d+), \{\}", alias.group(1))}
    assert len(params) == n_leaves and params <= aliased


@pytest.mark.parametrize("arch,layers", [
    ("starcoder2-3b", 2), ("phi3-medium-14b", 2),
    ("starcoder2-3b", 30), ("phi3-medium-14b", 10)])
def test_paged_decode_writes_kv_in_place_for_v5e(one_chip, arch, layers):
    """The served ``decode_multi`` changes one line per row per layer, so
    no operation whose result is a layer's or the stack's K/V may copy
    or relayout it, and the donated state is aliased to the output.  (A
    compile-time prefetch of a stack small enough for the chip's on-core
    memory is an async ``copy-start``/``copy-done`` into that memory,
    not a relayout; at 2 layers of starcoder2-3b the compiler makes
    one.)"""
    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, num_layers=layers,
                              block_pattern=("attn",) * layers)
    text = _decode_multi_text(cfg, one_chip)
    assert "%paged_decode." in text
    _assert_lines_in_place(
        text, rf"bf16\[(?:\d+,)?{SLOTS},{cfg.num_kv_heads},{CAP},"
              rf"{cfg.head_dim}\]", 2)


def test_paged_mla_decode_writes_latent_in_place_for_v5e(one_chip):
    """DeepSeek-V3 at published widths, 2 layers (the dense one and an
    expert layer holding 8 of 256 experts, so two segments): the decode
    program runs the ``paged_mla_decode`` kernel, writes its lines
    (latent 512, roped key 64, padded to 640) in place and aliases the
    two stacks it takes in."""
    cfg = get_config("deepseek-v3-671b")
    cfg = dataclasses.replace(
        cfg, num_layers=2, block_pattern=("attn",) * 2, mtp_depth=0,
        moe=dataclasses.replace(cfg.moe, first_dense_layers=1,
                                experts_held=8))
    text = _decode_multi_text(cfg, one_chip)
    assert "%paged_mla_decode." in text
    width = mla_line_width(cfg)
    _assert_lines_in_place(text, rf"bf16\[(?:\d+,)?{SLOTS},{CAP},{width}\]", 2)
