"""Launcher spec rules: input ShapeDtypeStructs, param/state PartitionSpecs
(divisibility-checked), layout selection."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch.specs import (input_specs, param_pspecs, pick_layout,
                                state_pspecs, token_layout)
from repro.models import init_params, init_state


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_shapes(arch, shape_name):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    sds, specs = input_specs(cfg, shape)
    assert set(sds) == set(specs)
    if shape.kind == "decode":
        assert sds["tokens"].shape == (shape.global_batch, 1)
        assert sds["t"].shape == (shape.global_batch,)
    else:
        B, S = sds["tokens"].shape
        assert B == shape.global_batch
        layout = token_layout(cfg, shape)
        assert S == layout["text_len"]
        if cfg.frontend is not None and cfg.frontend.kind == "vision":
            # patches + text == requested seq_len
            assert S + cfg.frontend.num_prefix_tokens == shape.seq_len


def test_param_pspecs_structure_and_divisibility():
    cfg = get_config("phi3-medium-14b")
    ps = jax.eval_shape(lambda k: init_params(k, cfg.reduced()),
                        jax.random.PRNGKey(0))
    specs = param_pspecs(cfg.reduced(), ps, mode="serve")
    # same treedef
    assert jax.tree_util.tree_structure(ps) == \
        jax.tree_util.tree_structure(specs)
    flat_p = jax.tree_util.tree_leaves(ps)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= len(leaf.shape)
        for dim, ax in enumerate(spec):
            if ax == "model":
                assert leaf.shape[dim] % 16 == 0, (
                    f"non-divisible shard: {leaf.shape} {spec}")


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "jamba-1.5-large-398b"])
def test_state_pspecs_decode(arch):
    cfg = get_config(arch)
    shape = INPUT_SHAPES["decode_32k"]
    st = jax.eval_shape(lambda: init_state(cfg.reduced(), shape.global_batch,
                                           256))
    specs = state_pspecs(cfg.reduced(), st, shape, long_context=False)
    assert jax.tree_util.tree_structure(st) == \
        jax.tree_util.tree_structure(specs)


def test_mla_latent_cache_sequence_sharded():
    """§Perf iteration 5: the MLA latent cache shards its seq dim on model."""
    cfg = get_config("deepseek-v3-671b")
    shape = INPUT_SHAPES["decode_32k"]
    st = jax.eval_shape(lambda: init_state(cfg, shape.global_batch,
                                           shape.seq_len))
    specs = state_pspecs(cfg, st, shape, long_context=False)

    found = []

    def walk(path, spec):
        found.append((jax.tree_util.keystr(path), spec))

    jax.tree_util.tree_map_with_path(
        walk, specs, is_leaf=lambda x: isinstance(x, P))
    ckv = [s for p, s in found if "c_kv" in p]
    assert ckv and all(s[2] == "model" for s in ckv)


def test_long_context_kv_data_sharded_for_hybrid():
    cfg = get_config("jamba-1.5-large-398b")
    shape = INPUT_SHAPES["long_500k"]
    st = jax.eval_shape(lambda: init_state(cfg, 1, shape.seq_len, True))
    specs = state_pspecs(cfg, st, shape, long_context=True)
    flat = []
    jax.tree_util.tree_map_with_path(
        lambda p, s: flat.append((jax.tree_util.keystr(p), s)), specs,
        is_leaf=lambda x: isinstance(x, P))
    # head-major k (R, B, KVH, W, hd): the sequence is dim 3
    ks = [s for p, s in flat if p.endswith("['k']")]
    assert ks and all(s[3] in ("data", ("data",)) for s in ks), ks


def test_pick_layout_default_tp():
    for arch in list_archs():
        for shape in INPUT_SHAPES.values():
            assert pick_layout(get_config(arch), shape) == "tp"
