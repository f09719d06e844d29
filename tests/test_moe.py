"""MoE dispatch correctness: scatter/gather dispatch vs a naive dense
all-experts reference, capacity-drop bounds, aux-loss properties."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models.common import swiglu
from repro.models.moe import (_capacity, _ranks_of, _route, init_moe,
                              moe_forward)


def naive_moe(cfg, params, x2):
    """Dense reference: every expert on every token, mix by gates."""
    gates, eidx, _ = _route(cfg.moe, x2, params["router"],
                            params.get("router_bias"))
    h = swiglu(jnp.einsum("td,edf->tef", x2, params["w_gate"]),
               jnp.einsum("td,edf->tef", x2, params["w_up"]))
    y_all = jnp.einsum("tef,efd->ted", h, params["w_down"])  # (T,E,d)
    oh = jax.nn.one_hot(eidx, params["w_gate"].shape[0])     # (T,k,E)
    w = (gates[..., None] * oh).sum(1)                        # (T,E)
    return jnp.einsum("te,ted->td", w, y_all.astype(jnp.float32))


@pytest.mark.parametrize("arch", ["arctic-480b", "deepseek-v3-671b"])
def test_moe_matches_dense_reference(arch, rng_key):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = init_moe(rng_key, cfg, jnp.float32)
    routed = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}
    T = 32
    x = jax.random.normal(jax.random.fold_in(rng_key, 2),
                          (1, T, cfg.d_model))
    y, aux = moe_forward(cfg, routed, x)
    exp = naive_moe(cfg, routed, x[0])
    err = float(jnp.abs(y[0] - exp.astype(y.dtype)).max())
    assert err < 1e-4, err
    assert float(aux) > 0


def test_ranks_within_expert():
    e = jnp.array([2, 0, 2, 1, 0, 2])
    r = _ranks_of(e, 3)
    # expert 0 at idx 1,4 -> ranks 0,1 ; expert 2 at idx 0,2,5 -> 0,1,2
    assert list(map(int, r)) == [0, 0, 1, 0, 1, 2]


def test_capacity_drops_bounded(rng_key):
    """With cf=1.0 and adversarial routing, at most C tokens per expert."""
    cfg = get_config("arctic-480b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    params = init_moe(rng_key, cfg, jnp.float32)
    routed = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}
    T = 64
    x = jnp.broadcast_to(
        jax.random.normal(rng_key, (1, 1, cfg.d_model)), (1, T, cfg.d_model))
    # identical tokens -> all route to the same experts -> heavy drops; must
    # still be finite and bounded
    y, aux = moe_forward(cfg, routed, x)
    assert bool(jnp.isfinite(y).all())
    C = _capacity(T, cfg.moe.top_k, cfg.moe.num_experts,
                  cfg.moe.capacity_factor)
    assert C == -(-T * cfg.moe.top_k * 1.0 // cfg.moe.num_experts)


def test_aux_loss_uniform_is_minimal(rng_key):
    """Perfectly uniform routing gives aux == coef (the theoretical min)."""
    cfg = get_config("arctic-480b").reduced()
    m = cfg.moe
    from repro.models.moe import _aux_loss
    E, T, k = m.num_experts, 64, m.top_k
    eidx = (jnp.arange(T * k) % E).reshape(T, k)
    probs = jnp.full((T, E), 1.0 / E)
    a_uniform = _aux_loss(eidx, probs, E, 1.0)
    # concentrated routing must be larger
    eidx_bad = jnp.zeros((T, k), jnp.int32)
    probs_bad = jnp.zeros((T, E)).at[:, 0].set(1.0)
    a_bad = _aux_loss(eidx_bad, probs_bad, E, 1.0)
    assert float(a_uniform) == pytest.approx(1.0, rel=1e-5)
    assert float(a_bad) > float(a_uniform) * (E / 2)


def test_shared_expert_and_dense_residual(rng_key):
    cfg = get_config("arctic-480b").reduced()
    params = init_moe(rng_key, cfg, jnp.float32)
    assert "dense_residual" in params
    x = jax.random.normal(rng_key, (1, 8, cfg.d_model))
    y, _ = moe_forward(cfg, params, x)
    assert y.shape == x.shape

    cfg2 = get_config("deepseek-v3-671b").reduced()
    params2 = init_moe(rng_key, cfg2, jnp.float32)
    assert "shared" in params2
