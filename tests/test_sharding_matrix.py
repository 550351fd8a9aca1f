"""Sharding-rule matrix coverage: every (arch x shape) cell's param, batch,
and cache shardings are well-formed on abstract production meshes (fast --
no device allocation, no compile)."""
import jax
import jax.numpy as jnp
import math
import pytest

from repro import configs
from jax.sharding import AbstractMesh, AxisType
from repro.distributed import sharding
from repro.models import registry


def _meshes():
    at = (AxisType.Auto,)
    return [
        AbstractMesh((16, 16), ("data", "model"), axis_types=at * 2),
        AbstractMesh((2, 16, 16), ("pod", "data", "model"), axis_types=at * 3),
    ]


def _check_divisible(tree_sds, tree_sh, mesh):
    for (path, leaf), sh in zip(
        jax.tree_util.tree_leaves_with_path(tree_sds),
        jax.tree.leaves(tree_sh, is_leaf=lambda x: hasattr(x, "spec")),
    ):
        spec = sh.spec
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            n = 1
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                n *= mesh.shape[ax]
            assert leaf.shape[dim] % n == 0, (
                jax.tree_util.keystr(path), leaf.shape, spec)


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
@pytest.mark.parametrize("serve_2d", [False, True])
def test_param_shardings_divisible(arch, serve_2d):
    cfg = configs.get_config(arch)
    params = registry.param_specs(cfg)
    for mesh in _meshes():
        sh = sharding.param_shardings(cfg, params, mesh, serve_2d=serve_2d)
        _check_divisible(params, sh, mesh)


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_batch_and_cache_shardings_divisible(arch):
    cfg = configs.get_config(arch)
    model = registry.build_model(cfg)
    for mesh in _meshes():
        for shape_name in registry.SHAPES:
            if not registry.supports(cfg, shape_name):
                continue
            specs = registry.input_specs(cfg, shape_name)
            if "batch" in specs:
                sh = sharding.batch_shardings(cfg, specs["batch"], mesh)
                _check_divisible(specs["batch"], sh, mesh)
            if "cache" in specs:
                sh = sharding.cache_shardings(cfg, specs["cache"], mesh)
                _check_divisible(specs["cache"], sh, mesh)


def test_split_kv_cache_sharding_when_heads_indivisible():
    """command-r: 8 kv heads < model=16 -> the cache shards its seq dim over
    model (split-KV decode) instead of replicating 21 GB/chip."""
    cfg = configs.get_config("command-r-35b")
    model = registry.build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 32768))
    mesh = _meshes()[0]
    sh = sharding.cache_shardings(cfg, cache, mesh)
    assert sh["k"].spec[2] == "model", sh["k"].spec
    assert sh["k"].spec[1] == "data", sh["k"].spec


def test_param_bytes_per_chip_fit_serving():
    """Serving layout: every arch's bf16 weights fit 16 GB/chip on the
    single-pod mesh (the KV cache is accounted separately per cell)."""
    mesh = _meshes()[0]
    for arch in configs.ARCH_NAMES:
        cfg = configs.get_config(arch)
        params = registry.param_specs(cfg)
        sh = sharding.param_shardings(cfg, params, mesh, serve_2d=True)
        per_chip = 0
        for leaf, s in zip(jax.tree.leaves(params),
                           jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))):
            n_shards = 1
            for axes in s.spec:
                if axes is None:
                    continue
                for ax in (axes if isinstance(axes, tuple) else (axes,)):
                    n_shards *= mesh.shape[ax]
            per_chip += math.prod(leaf.shape) * 2 / n_shards  # bf16
        assert per_chip < 16e9, (arch, per_chip / 1e9)
