"""Compile every Pallas kernel of the served path for a TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these tests run on a CPU-only host: they catch what interpret mode cannot
(block shapes Mosaic refuses, scalars in VMEM, more fast memory than a
kernel may use) at the market sizes the allocator serves.  Nothing runs;
each test asserts that the compiled program holds the kernel
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bisect_alloc import bisect_alloc
from repro.kernels.dual_demand import dual_demand
from repro.kernels.market_clear import market_clear, mbdf_demand


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip cannot be read back from the
    # persistent cache without one, so keep it out of the cache.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,k", [(16, 32), (8192, 128)])
def test_market_clear_compiles(one_chip, n, k):
    text = _compiled_text(lambda a, t, b, lam: market_clear(a, t, b, lam),
                          one_chip, (n, k), (n, k), (), ())
    assert "tpu_custom_call" in text


def test_mbdf_demand_compiles_five_bids(one_chip):
    text = _compiled_text(lambda a, t, p: mbdf_demand(a, t, p, 0.5),
                          one_chip, (256, 32), (256, 32), (256, 5))
    assert "tpu_custom_call" in text


def test_dual_demand_compiles(one_chip):
    text = _compiled_text(lambda a, t, lam: dual_demand(a, t, lam),
                          one_chip, (8192, 128), (8192, 128), ())
    assert "tpu_custom_call" in text


def test_bisect_alloc_compiles(one_chip):
    text = _compiled_text(lambda a, t, b: bisect_alloc(a, t, b),
                          one_chip, (8192, 128), (8192, 128), (8192,))
    assert "tpu_custom_call" in text
