"""Per-kernel correctness: interpret-mode Pallas vs the pure-jnp oracle in
ref.py, swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import intra, network
from repro.kernels import ref
from repro.kernels.bisect_alloc import bisect_alloc
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import mlstm_chunk

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 2, 256, 64),
    (1, 8, 1, 512, 128),   # MQA
    (2, 2, 2, 128, 256),   # MHA, gemma head_dim
    (1, 4, 4, 384, 64),    # non-pow2 seq (3 blocks of 128)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("window", [32, 128, 1024])
def test_flash_attention_sliding_window(window):
    b, hq, hkv, s, d = 1, 4, 1, 512, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=128, block_k=128, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_flash_attention_non_causal():
    b, hq, hkv, s, d = 2, 2, 2, 256, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d,valid", [
    (2, 8, 2, 512, 64, 512),
    (2, 8, 2, 512, 64, 317),   # partial cache
    (1, 4, 1, 2048, 128, 1500),
    (4, 4, 4, 256, 256, 100),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, hq, hkv, s, d, valid, dtype):
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = decode_attention(q, k, v, jnp.int32(valid), block_k=256, interpret=True)
    expect = ref.decode_attention_ref(q, k, v, jnp.int32(valid))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# bisect_alloc (the paper's kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k", [(5, 18), (16, 25), (64, 40), (3, 130)])
def test_bisect_alloc_matches_core_solver(n, k):
    svc, _ = network.sample_services(jax.random.key(4), n, k_max=k)
    b = jax.random.uniform(jax.random.key(5), (n,), minval=0.2, maxval=4.0)
    t_star, b_alloc = bisect_alloc(svc.alpha, svc.t_comp, b, interpret=True)
    t_ref, b_ref = ref.bisect_alloc_ref(svc.alpha, svc.t_comp, b)
    np.testing.assert_allclose(np.asarray(t_star), np.asarray(t_ref), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(b_alloc), np.asarray(b_ref), rtol=1e-3, atol=1e-5)


def test_bisect_alloc_budget_and_equalization():
    svc, _ = network.sample_services(jax.random.key(6), 12, k_max=30)
    b = jnp.full((12,), 1.5)
    t_star, b_alloc = bisect_alloc(svc.alpha, svc.t_comp, b, interpret=True)
    np.testing.assert_allclose(np.asarray(b_alloc.sum(-1)), 1.5, rtol=1e-5)
    finish = svc.t_comp + svc.alpha / jnp.maximum(b_alloc, 1e-30)
    finish = jnp.where(svc.mask, finish, t_star[:, None])
    np.testing.assert_allclose(
        np.asarray(finish), np.asarray(t_star)[:, None] * np.ones_like(finish),
        rtol=1e-3,
    )


@pytest.mark.parametrize("kernel", ["bisect_alloc", "mbdf_demand",
                                    "dual_demand", "market_clear"])
@pytest.mark.parametrize("n,k", [(10, 48), (8, 128), (3, 130), (129, 7)])
def test_padded_shape_is_what_each_wrapper_launches(kernel, n, k):
    """``tiling.padded_shape`` is the (rows, lanes) every wrapper hands
    ``pallas_call``; ``policy.launch_shape`` reports it for the fleet
    engine's work counts."""
    from jaxpr_shapes import pallas_input_shapes

    from repro.kernels import dual_demand, market_clear
    from repro.kernels.tiling import padded_shape

    a = jnp.ones((n, k), jnp.float32)
    calls = {
        "bisect_alloc": (lambda: bisect_alloc(a, a, jnp.ones((n,)),
                                              interpret=True),
                         padded_shape(n, k)),
        "mbdf_demand": (lambda: market_clear.mbdf_demand(
            a, a, jnp.ones((n, 5)), 0.5, interpret=True),
            padded_shape(n, k, market_clear.TILE_N_MBDF)),
        "dual_demand": (lambda: dual_demand.dual_demand(a, a, 1.0,
                                                        interpret=True),
                        padded_shape(n, k, dual_demand.TILE_N)),
        "market_clear": (lambda: market_clear.market_clear(
            a, a, 10.0, 0.0, interpret=True),
            padded_shape(n, k, market_clear.TILE_N)),
    }
    fn, want = calls[kernel]
    assert pallas_input_shapes(fn) == [want]


# ---------------------------------------------------------------------------
# mlstm_chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,dh,chunk", [
    (2, 2, 256, 64, 128),
    (1, 4, 512, 128, 128),
    (2, 1, 256, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_chunk_matches_parallel_oracle(b, h, s, dh, chunk, dtype):
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (b, h, s, dh), dtype)
    k = jax.random.normal(ks[1], (b, h, s, dh), dtype) / jnp.sqrt(dh).astype(dtype)
    v = jax.random.normal(ks[2], (b, h, s, dh), dtype)
    ig = (jax.random.normal(ks[3], (b, h, s)) * 0.5).astype(dtype)
    fg = (jax.random.normal(ks[4], (b, h, s)) * 0.5 + 2.0).astype(dtype)
    out = mlstm_chunk(q, k, v, ig, fg, chunk=chunk, interpret=True)
    expect = ref.mlstm_chunk_ref(q, k, v, ig, fg)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol
    )
