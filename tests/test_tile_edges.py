"""Tile-edge padding parity: every allocation kernel must be exact on shapes
where N is NOT a multiple of its row tile and K is NOT a multiple of the
128-lane pad, with ragged masks and fully-inactive service slots riding in
the padded region, and masked slots carrying a t^C above every valid
client's.  Also the unified ``ops._resolve_backend`` dispatch rule,
including the ``REPRO_FORCE_PALLAS`` CI override.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import disba, network
from repro.core.types import ServiceSet
from repro.kernels import ops, ref
from repro.kernels.bisect_alloc import bisect_alloc
from repro.kernels.dual_demand import dual_demand
from repro.kernels.market_clear import market_clear, mbdf_demand

B = network.B_TOTAL_MHZ

# None of these N are tile multiples (tiles are 8 / 128); K values straddle
# the 128-lane pad boundary: 13 < 128, 130 and 257 just past a multiple.
EDGE_SHAPES = [(5, 13), (9, 130), (13, 100), (21, 257)]


def _edge_set(seed, n, k):
    rng = np.random.default_rng(seed)
    alpha = rng.uniform(0.01, 0.3, size=(n, k)).astype(np.float32)
    t_comp = rng.uniform(0.01, 0.06, size=(n, k)).astype(np.float32)
    mask = np.zeros((n, k), dtype=bool)
    for i in range(n):
        mask[i, : rng.integers(1, k + 1)] = True
    mask[rng.integers(0, n)] = False          # a fully-inactive slot
    alpha = np.where(mask, alpha, 0.0)
    # Masked slots slower than every valid client (a dropped straggler):
    # their 1 - t^C f must not reach the TINY floor and 0/0.
    t_comp = np.where(mask, t_comp, 99.0).astype(np.float32)
    return ServiceSet(alpha=jnp.asarray(alpha), t_comp=jnp.asarray(t_comp),
                      mask=jnp.asarray(mask))


@pytest.mark.parametrize("n,k", EDGE_SHAPES)
def test_dual_demand_tile_edges(n, k):
    svc = _edge_set(0, n, k)
    lam = jnp.float32(0.2)
    b, slope = dual_demand(svc.alpha, svc.t_comp, lam, interpret=True)
    b_r, s_r = ref.dual_demand_ref(svc.alpha, svc.t_comp, lam)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(slope), np.asarray(s_r),
                               rtol=1e-3, atol=1e-4)
    inactive = ~np.asarray(svc.service_active())
    assert np.all(np.asarray(b)[inactive] == 0.0)


@pytest.mark.parametrize("n,k", EDGE_SHAPES)
def test_bisect_alloc_tile_edges(n, k):
    svc = _edge_set(1, n, k)
    b = jax.random.uniform(jax.random.key(2), (n,), minval=0.2, maxval=4.0)
    b = jnp.where(svc.service_active(), b, 0.0)
    t_star, b_alloc = bisect_alloc(svc.alpha, svc.t_comp, b, interpret=True)
    t_r, b_r = ref.bisect_alloc_ref(svc.alpha, svc.t_comp, b)
    active = np.asarray(svc.service_active())
    np.testing.assert_allclose(np.asarray(t_star)[active],
                               np.asarray(t_r)[active], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(b_alloc), np.asarray(b_r),
                               rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("n,k", EDGE_SHAPES)
def test_market_clear_tile_edges(n, k):
    svc = _edge_set(2, n, k)
    lam_prev = disba.solve_lambda_bisect(svc, B).lam * jnp.float32(1.03)
    expect = disba.solve_lambda_newton_warm(svc, B, lam_prev)
    b, f, lam = market_clear(svc.alpha, svc.t_comp, jnp.float32(B), lam_prev,
                             tile_n=8, interpret=True)
    np.testing.assert_allclose(float(lam), float(expect.lam), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(b), np.asarray(expect.b),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f), np.asarray(expect.f),
                               rtol=1e-3, atol=1e-5)
    inactive = ~np.asarray(svc.service_active())
    assert np.all(np.asarray(b)[inactive] == 0.0)
    assert np.all(np.asarray(f)[inactive] == 0.0)


@pytest.mark.parametrize("n,k", EDGE_SHAPES)
def test_mbdf_tile_edges(n, k):
    svc = _edge_set(3, n, k)
    from repro.core import auction, fairness

    bid = auction.uniform_truthful_bids(svc, 3, 0.5)
    expect = fairness.mbdf_grid(svc, bid.prices, 0.5)
    got = mbdf_demand(svc.alpha, svc.t_comp, bid.prices, 0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# The unified dispatch rule.
# ---------------------------------------------------------------------------

def test_resolve_backend_defaults(monkeypatch):
    monkeypatch.delenv(ops.FORCE_PALLAS_ENV, raising=False)
    on_tpu = ops._on_tpu()
    use, interp = ops._resolve_backend(None, False)
    assert use is on_tpu
    assert interp is (not on_tpu)
    # explicit overrides always win
    assert ops._resolve_backend(True, False)[0] is True
    assert ops._resolve_backend(False, False)[0] is False
    # explicit interpret stays on
    assert ops._resolve_backend(True, True)[1] is True


def test_resolve_backend_force_pallas_env(monkeypatch):
    monkeypatch.setenv(ops.FORCE_PALLAS_ENV, "1")
    use, interp = ops._resolve_backend(None, False)
    assert use is True
    assert interp is (not ops._on_tpu())
    # the env var forces only the *auto* path; explicit False still wins
    assert ops._resolve_backend(False, False)[0] is False
    monkeypatch.setenv(ops.FORCE_PALLAS_ENV, "0")
    assert ops._resolve_backend(None, False)[0] is ops._on_tpu()


def test_force_pallas_env_runs_interpret_kernel(monkeypatch):
    """With the override set, the auto path of an op really is the kernel:
    dual_demand's auto result matches the explicit interpret launch."""
    monkeypatch.setenv(ops.FORCE_PALLAS_ENV, "1")
    svc = _edge_set(4, 7, 19)
    lam = jnp.float32(0.25)
    b_auto, s_auto = ops.dual_demand(svc.alpha, svc.t_comp, lam)
    b_kern, s_kern = dual_demand(svc.alpha, svc.t_comp, lam, interpret=True)
    assert np.array_equal(np.asarray(b_auto), np.asarray(b_kern))
    assert np.array_equal(np.asarray(s_auto), np.asarray(s_kern))
