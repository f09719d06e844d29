"""Random weights from the seed, made on the device in one jitted call.

The tree is the program's parameter layout, read from
``jax.eval_shape`` of its ``init_params``: only shapes and key names are
taken, never values.  Norm weights are ones; the embedding is
N(0, 4/d_model); every other matrix is N(0, 1/fan_in), fan-in being its
second-to-last axis, with two changes that keep a deep random stack's
greedy tokens depending on the prompt (with N(0, 1/fan_in) throughout,
a 30-layer stack puts one token first at every position, and no
rounding or fault can change what it serves):

- the two matrices that write into the residual stream (``wo``,
  ``w_down``) are scaled by ``1/sqrt(2 * layers)``, so no layer swamps
  the ones before it;
- ``w_down`` is centred over its input axis, so the positive mean of a
  GeLU's outputs adds no direction shared by every position.

A leaf stacked over layers is drawn one layer at a time (``lax.map``), so
the only transient is one layer's random bits.  Every leaf is drawn in
the served dtype but ``w_down``, which is drawn and centred in float32
one layer at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: matrices whose product is added to the residual stream
RESIDUAL_OUT = ("wo", "w_down")


def key32(seed: int, stream: int = 0) -> int:
    """A 32-bit PRNG seed from any whole-number ``seed`` (the driver's
    seeds exceed 32 signed bits)."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _draw(key, sds, name, layers):
    shape, dtype = sds.shape, sds.dtype
    if name.startswith("norm") or name == "final_norm":
        return jnp.ones(shape, dtype)
    if name == "embed":
        std = 2.0 * shape[-1] ** -0.5
    else:
        std = shape[-2] ** -0.5
    if name in RESIDUAL_OUT:
        std /= (2 * layers) ** 0.5

    def one(k, shp):
        if name != "w_down":
            return jax.random.normal(k, shp, dtype) * jnp.asarray(std, dtype)
        w = jax.random.normal(k, shp, jnp.float32)
        return ((w - w.mean(-2, keepdims=True)) * std).astype(dtype)

    if len(shape) == 3:
        return jax.lax.map(lambda k: one(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    return one(key, shape)


def make_params(seed: int, shapes, layers: int, device=None):
    """Weights of the tree ``shapes`` (ShapeDtypeStructs) of a model of
    ``layers`` layers for ``seed``, committed to ``device`` (default: the
    first device)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = [_draw(jax.random.fold_in(key, i), sds, _leaf_name(path),
                     layers)
               for i, (path, sds) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.PRNGKey(key32(seed))
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)
