"""Sharding rule engine: divisibility guard + expected placements.

Uses AbstractMesh — no devices needed, so these run on the 1-CPU test
environment while still exercising the exact production mesh shapes.
"""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS
from repro.launch import sharding as shd
from repro.models.decode import init_cache
from repro.models.transformer import param_specs


def _mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _check_divisibility(shapes, specs, mesh):
    def ok(path, leaf, spec):
        for dim, axis in zip(leaf.shape, spec):
            if axis is None:
                continue
            names = axis if isinstance(axis, tuple) else (axis,)
            size = 1
            for n in names:
                size *= mesh.shape[n]
            assert dim % size == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: ok(p, l, s), shapes, specs,
        is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("multi", [False, True])
def test_param_specs_always_divisible(arch, multi):
    cfg = ARCHS[arch]
    mesh = _mesh(multi)
    shapes = param_specs(cfg)
    specs = shd.param_pspecs(cfg, shapes, mesh)
    _check_divisibility(shapes, specs, mesh)


def test_vocab_padding_makes_embeddings_shardable():
    cfg = ARCHS["internvl2-2b"]          # raw vocab 92553 is not /16
    assert cfg.padded_vocab % 2048 == 0
    shapes = param_specs(cfg)
    specs = shd.param_pspecs(cfg, shapes, _mesh())
    assert specs["embed"] == P("model", None)   # tp mode: no fsdp dim


def test_fsdp_mode_shards_both_axes():
    cfg = ARCHS["llama4-maverick-400b-a17b"]
    shapes = param_specs(cfg)
    specs = shd.param_pspecs(cfg, shapes, _mesh())
    assert specs["embed"] == P("model", "data")
    # MoE expert tables: (P, E, D, F) stacked -> (None, None, data, model)
    moe_spec = specs["layers"][1]["moe"]["w_gate"]
    assert moe_spec == P(None, None, "data", "model")


def test_cache_specs_decode_vs_long_context():
    cfg = ARCHS["gemma3-4b"]
    mesh = _mesh()
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 32768))
    specs = shd.cache_pspecs(cfg, cache, mesh, shard_batch=True)
    # batched decode (stacked periods axis first): batch over data, seq over model
    assert specs["layers"][0]["k"] == P(None, ("data",), "model", None, None)
    long_cache = jax.eval_shape(lambda: init_cache(cfg, 1, 524288))
    lspecs = shd.cache_pspecs(cfg, long_cache, mesh, shard_batch=False)
    # long-context: sequence over data+model
    assert lspecs["layers"][0]["k"] == P(None, None, ("data", "model"), None, None)
    _check_divisibility(long_cache, lspecs, mesh)


def test_rwkv_non_divisible_heads_guarded():
    cfg = ARCHS["rwkv6-3b"]             # 40 heads not /16
    mesh = _mesh()
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 32768))
    specs = shd.cache_pspecs(cfg, cache, mesh, shard_batch=True)
    wkv = specs["layers"][0]["wkv"]
    assert wkv[2] is None or wkv[2] != "model"  # head axis dropped by guard
    _check_divisibility(cache, specs, mesh)
