"""Pallas TPU fused selective-scan kernel (Mamba-1 recurrence).

The naive ``lax.scan`` implementation round-trips the SSM state
(B, d_in, d_state) through HBM every timestep — the dominant memory term of
the hybrid arch's train/prefill roofline (EXPERIMENTS.md §Perf iteration
8). This kernel keeps the state tile resident in VMEM scratch for the
whole sequence: inputs stream in time-blocks, the time loop runs inside
the kernel, and state only touches HBM once at the end.

Grid: (batch, channel_blocks, time_blocks) — time innermost and sequential
("arbitrary") so the scratch state persists across time blocks.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) outer B_t
    y_t = h_t · C_t + D * x_t

Shapes per tile: state (C_BLK, N); N = d_state (16) packs the lane dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, hout_ref, h_scr, *, time_blk: int,
                 num_time_blocks: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0]                      # (C_BLK, N)

    a = a_ref[...]                                  # (C_BLK, N)
    d = d_ref[...]                                  # (C_BLK,)

    def step(t, h):
        x_t = x_ref[0, t]                           # (C_BLK,)
        dt_t = dt_ref[0, t]                         # (C_BLK,)
        b_t = b_ref[0, t]                           # (N,)
        c_t = c_ref[0, t]                           # (N,)
        da = jnp.exp(dt_t[:, None] * a)             # (C_BLK, N)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t] = (h * c_t[None, :]).sum(axis=1) + d * x_t
        return h

    h_scr[...] = jax.lax.fori_loop(0, time_blk, step, h_scr[...])

    @pl.when(ti == num_time_blocks - 1)
    def _finalize():
        hout_ref[0] = h_scr[...]


def mamba_scan_pallas(
    x: jax.Array,        # (B, S, C) gated/conv'd input, f32
    dt: jax.Array,       # (B, S, C) softplus'd step sizes
    b_ssm: jax.Array,    # (B, S, N)
    c_ssm: jax.Array,    # (B, S, N)
    a: jax.Array,        # (C, N)  negative decay rates
    d: jax.Array,        # (C,)    skip weights
    h0: jax.Array,       # (B, C, N) initial state
    *,
    channel_blk: int = 128,
    time_blk: int = 256,
    interpret: bool = False,
):
    """Returns (y (B,S,C), h_final (B,C,N))."""
    B, S, C = x.shape
    N = b_ssm.shape[-1]
    channel_blk = min(channel_blk, C)
    time_blk = min(time_blk, S)
    assert C % channel_blk == 0 and S % time_blk == 0
    nc, nt = C // channel_blk, S // time_blk

    kernel = functools.partial(_scan_kernel, time_blk=time_blk,
                               num_time_blocks=nt)
    y, h_out = pl.pallas_call(
        kernel,
        grid=(B, nc, nt),
        in_specs=[
            pl.BlockSpec((1, time_blk, channel_blk),
                         lambda b, ci, ti: (b, ti, ci)),   # x
            pl.BlockSpec((1, time_blk, channel_blk),
                         lambda b, ci, ti: (b, ti, ci)),   # dt
            pl.BlockSpec((1, time_blk, N),
                         lambda b, ci, ti: (b, ti, 0)),    # B_ssm
            pl.BlockSpec((1, time_blk, N),
                         lambda b, ci, ti: (b, ti, 0)),    # C_ssm
            pl.BlockSpec((channel_blk, N),
                         lambda b, ci, ti: (ci, 0)),       # A
            pl.BlockSpec((channel_blk,),
                         lambda b, ci, ti: (ci,)),         # D
            pl.BlockSpec((1, channel_blk, N),
                         lambda b, ci, ti: (b, ci, 0)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, time_blk, channel_blk),
                         lambda b, ci, ti: (b, ti, ci)),   # y
            pl.BlockSpec((1, channel_blk, N),
                         lambda b, ci, ti: (b, ci, 0)),    # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, C), jnp.float32),
            jax.ShapeDtypeStruct((B, C, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((channel_blk, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.astype(jnp.float32), dt.astype(jnp.float32),
      b_ssm.astype(jnp.float32), c_ssm.astype(jnp.float32),
      a.astype(jnp.float32), d.astype(jnp.float32), h0.astype(jnp.float32))
    return y, h_out
