"""Mamba-1 selective SSM block (Jamba's sequence mixer).

Full mode runs a ``lax.scan`` over time with the per-step discretization
computed inside the step (never materializing (B, S, d_in, d_state)).
Decode mode advances one step from stored (conv window, ssm state).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import KeyGen, dense_init

Array = jax.Array


def _dims(cfg: ModelConfig):
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    dt_rank = mc.dt_rank or -(-cfg.d_model // 16)
    return mc, d_in, dt_rank


def init_mamba(key, cfg: ModelConfig, dtype):
    mc, d_in, dt_rank = _dims(cfg)
    kg = KeyGen(key)
    d = cfg.d_model
    # S4D-real initialization for A
    a_init = jnp.broadcast_to(
        jnp.arange(1, mc.d_state + 1, dtype=jnp.float32), (d_in, mc.d_state))
    return {
        "in_proj": dense_init(kg(), d, 2 * d_in, dtype),
        "conv_w": (jax.random.normal(kg(), (mc.d_conv, d_in), jnp.float32)
                   * mc.d_conv ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "x_proj": dense_init(kg(), d_in, dt_rank + 2 * mc.d_state, dtype),
        "dt_w": dense_init(kg(), dt_rank, d_in, dtype),
        "dt_b": jnp.full((d_in,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "A_log": jnp.log(a_init),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(kg(), d_in, d, dtype),
    }


def _split_proj(cfg, params, x):
    d_in = cfg.mamba.expand * cfg.d_model
    xz = x @ params["in_proj"]
    return xz[..., :d_in], xz[..., d_in:]


def _causal_conv_full(params, xp: Array, d_conv: int) -> Array:
    """Depthwise causal conv via shifted adds; xp (B, S, d_in)."""
    w = params["conv_w"].astype(jnp.float32)          # (d_conv, d_in)
    acc = jnp.zeros_like(xp, jnp.float32)
    for i in range(d_conv):
        shift = d_conv - 1 - i
        rolled = jnp.pad(xp, ((0, 0), (shift, 0), (0, 0)))[:, : xp.shape[1]]
        acc += rolled.astype(jnp.float32) * w[i]
    return acc + params["conv_b"].astype(jnp.float32)


def _ssm_inputs(cfg, params, x_c, dt_rank):
    mc = cfg.mamba
    dbc = x_c.astype(params["x_proj"].dtype) @ params["x_proj"]
    dt = dbc[..., :dt_rank]
    b_ssm = dbc[..., dt_rank: dt_rank + mc.d_state].astype(jnp.float32)
    c_ssm = dbc[..., dt_rank + mc.d_state:].astype(jnp.float32)
    dt = jax.nn.softplus(
        (dt @ params["dt_w"]).astype(jnp.float32) + params["dt_b"])
    return dt, b_ssm, c_ssm


def _ssm_step(A, D, h, x_t, dt_t, b_t, c_t):
    """One selective-scan step. h (B, d_in, N); x_t/dt_t (B, d_in);
    b_t/c_t (B, N)."""
    dA = jnp.exp(dt_t[..., None] * A)                       # (B, d_in, N)
    dBx = (dt_t * x_t)[..., None] * b_t[:, None, :]
    h = dA * h + dBx
    y = jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t
    return h, y


def mamba_forward(
    cfg: ModelConfig,
    params,
    x: Array,                       # (B, S, D)
    *,
    mode: str,                      # "full" | "decode"
    state=None,
    update_cache: bool = False,
) -> Tuple[Array, Optional[dict]]:
    mc, d_in, dt_rank = _dims(cfg)
    B, S, _ = x.shape
    xp, z = _split_proj(cfg, params, x)
    A = -jnp.exp(params["A_log"])
    D = params["D"]

    if mode == "full":
        x_c = jax.nn.silu(_causal_conv_full(params, xp, mc.d_conv))
        dt, b_ssm, c_ssm = _ssm_inputs(cfg, params, x_c, dt_rank)
        h0 = (state["ssm"] if state is not None
              else jnp.zeros((B, d_in, mc.d_state), jnp.float32))

        from repro.models.attention import kernel_backend
        backend = kernel_backend()
        if backend != "jnp" and S % 256 == 0 and d_in % 128 == 0:
            # fused Pallas selective scan: state stays in VMEM across the
            # whole sequence instead of an HBM round-trip per step
            # (§Perf iteration 8)
            from repro.kernels.mamba_scan import mamba_scan_pallas
            y, hT = mamba_scan_pallas(x_c, dt, b_ssm, c_ssm, A, D, h0,
                                      interpret=backend == "interpret")
        else:
            def step(h, inp):
                x_t, dt_t, b_t, c_t = inp
                h, yt = _ssm_step(A, D, h, x_t, dt_t, b_t, c_t)
                return h, yt

            hT, ys = jax.lax.scan(
                step, h0,
                (x_c.swapaxes(0, 1), dt.swapaxes(0, 1),
                 b_ssm.swapaxes(0, 1), c_ssm.swapaxes(0, 1)))
            y = ys.swapaxes(0, 1)                            # (B, S, d_in)
        new_state = state
        if update_cache and state is not None:
            tail = xp[:, -mc.d_conv:]
            pad = mc.d_conv - tail.shape[1]
            if pad > 0:
                tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
            new_state = dict(state, ssm=hT,
                             conv=tail.astype(state["conv"].dtype))
    elif mode == "decode":
        assert state is not None and S == 1
        conv = jnp.concatenate(
            [state["conv"][:, 1:], xp.astype(state["conv"].dtype)], axis=1)
        w = params["conv_w"].astype(jnp.float32)
        x_c = jax.nn.silu(
            jnp.einsum("bkd,kd->bd", conv.astype(jnp.float32), w)
            + params["conv_b"].astype(jnp.float32))[:, None]  # (B,1,d_in)
        dt, b_ssm, c_ssm = _ssm_inputs(cfg, params, x_c, dt_rank)
        h, y = _ssm_step(A, D, state["ssm"], x_c[:, 0], dt[:, 0],
                         b_ssm[:, 0], c_ssm[:, 0])
        y = y[:, None]
        new_state = dict(state, conv=conv, ssm=h)
    else:
        raise ValueError(mode)

    y = (y.astype(x.dtype) * jax.nn.silu(z)).astype(x.dtype)
    return y @ params["out_proj"], new_state
