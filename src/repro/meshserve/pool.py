"""Sharded KV pool + parameter placement for one mesh slice.

``shard_params`` reuses the launch layer's TP rules
(``repro.launch.specs.param_pspecs``) at the slice's width, so serving
shards exactly the dims training would (column-parallel q/k/v and FFN
up, row-parallel output projections, vocab-sharded embed/lm_head).

``shard_store`` places a live :class:`repro.kvstore.PagedStore`'s state
arrays on the slice: the attention K/V leaves — stacked head-major
layout ``(R, B, KVH, W, hd)``, enc-dec cross caches ``(R, B, S, KVH,
hd)`` — shard their KV-head dim over the ``model`` axis when divisible
(the paged decode kernel gathers per head, so each shard reads only its
own heads' line blocks); everything else replicates.
Block tables stay host-side numpy and are replicated into each dispatch,
exactly as on a single device.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.specs import param_pspecs
from repro.meshserve.topology import MeshSlice
from repro.models.state import HEAD_AXIS


def shard_params(cfg, params, sl: MeshSlice):
    """Place (a copy of) ``params`` on the slice under its TP layout.
    The input pytree is untouched — every engine of a pod shards the
    same host copy onto its own devices."""
    specs = param_pspecs(cfg, params, mode="serve", model_n=sl.tp)
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(sl.mesh, s)),
        params, specs)


def shard_store(store, sl: MeshSlice) -> None:
    """Place ``store``'s state arrays on the slice, in place."""
    for i, pj, key, kind in store._paths:
        arr = store.state["layers"][i][pj][key]
        spec = [None] * arr.ndim
        dim = HEAD_AXIS.get(key)
        if dim is not None and arr.shape[dim] % sl.tp == 0:
            spec[dim] = "model"
        store.state["layers"][i][pj][key] = jax.device_put(
            arr, NamedSharding(sl.mesh, P(*spec)))
    if "enc_out" in store.state:
        store.state["enc_out"] = jax.device_put(
            store.state["enc_out"], NamedSharding(sl.mesh, P()))
