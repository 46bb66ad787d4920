"""Sharding rules: TP-divisibility padding and spec validity for all archs."""
import jax
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.distributed import sharding as shd
from repro.models import Runtime, build_model

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_shardable_padding(name):
    cfg, changes = shd.shardable(get_config(name), 16)
    if cfg.uses_attention:
        assert cfg.num_heads % 16 == 0
        assert cfg.num_heads % cfg.num_kv_heads == 0
    if cfg.uses_ssm:
        assert cfg.ssm_heads % 16 == 0
    assert cfg.vocab_size % 16 == 0
    # padding is bounded: ≤ 2x any original dimension
    orig = get_config(name)
    assert cfg.num_heads <= max(2 * orig.num_heads, orig.num_heads + 16)
    if orig.uses_moe:
        assert cfg.num_experts <= orig.num_experts + 16


@pytest.mark.parametrize("name", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD], ids=["1pod", "2pod"])
def test_param_specs_divide_mesh(name, mesh):
    cfg, _ = shd.shardable(get_config(name), mesh.shape["model"])
    model = build_model(cfg, Runtime())
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    specs = shd.param_specs(cfg, mesh, shapes)

    def check(path, leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (name, jax.tree_util.keystr(path), leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), shapes, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def test_zero_extend():
    spec = shd.zero_extend(P(None, "model"), (4096, 1024), MESH_1POD)
    assert spec == P("data", "model")
    # non-divisible first dim skips to next
    spec = shd.zero_extend(P(None, None), (7, 64), MESH_1POD)
    assert spec == P(None, "data")
    # nothing divisible: unchanged
    spec = shd.zero_extend(P(None,), (7,), MESH_1POD)
    assert spec == P(None)


def test_batch_and_cache_specs():
    cfg, _ = shd.shardable(get_config("qwen3-32b"), 16)
    bs = shd.batch_specs(cfg, MESH_1POD, {"tokens": (256, 4096)})
    assert bs["tokens"] == P("data", None)
    bs1 = shd.batch_specs(cfg, MESH_1POD, {"tokens": (1, 4096)})
    assert bs1["tokens"] == P(None, None)  # batch=1 can't shard
    cs = shd.cache_specs(
        cfg, MESH_1POD,
        {"k": (64, 128, 32768, 8, 128), "v": (64, 128, 32768, 8, 128)},
    )
    assert cs["k"] == P(None, "data", "model", None, None)


def test_mesh_helpers():
    assert shd.mesh_dp_size(MESH_2POD) == 32
    assert shd.mesh_dp_axes(MESH_2POD) == ("pod", "data")
    assert shd.mesh_model_size(MESH_1POD) == 16
