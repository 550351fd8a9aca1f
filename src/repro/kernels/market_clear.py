"""Whole-market clearing as ONE Pallas launch: the complete safeguarded-
Newton dual iteration of ``disba.solve_lambda_newton_warm`` fused end to end.

PR 3's ``dual_demand`` kernel fused one dual *evaluation*: the solver still
launched once per Newton trip (<= 6 warm / ~12 cold), round-tripping the
(N, K) service tensors through HBM between trips.  At the 1024-8192-service
markets the ROADMAP targets those re-loads dominate: each trip re-streams
N*K*8 bytes to recompute a pair of scalars.  This kernel runs the *entire*
solve in one launch -- the (N, K) alpha/t_comp tensors are loaded into VMEM
once (8192 x 128 f32 pairs = 8 MB, inside the ~16 MB/core budget) and an
internal ``fori_loop`` over row tiles performs, per Newton trip:

  1. per-service demand b_n(lam) + closed-form slope db_n/dlam
     (``demand_slope_tile`` -- the same in-VMEM tile function the
     ``dual_demand`` kernel launches, so per-row arithmetic is shared);
  2. the aggregate reduction D(lam) = sum_n b_n, D'(lam) (scalar accumulators
     across tiles);
  3. the dual update with bisection safeguard -- bit-for-bit the reference
     solver's step: bracket fold, Newton step, midpoint fallback.

A final pass re-evaluates demand at the full ``inner_iters`` trip count,
projects onto sum b = B, and solves the Eq. 7 round time per service so the
launch emits the complete ``(b, f, lam)`` clearing result.  HBM traffic is
one load of the service tensors plus the (N,) outputs -- independent of the
trip count -- versus one full reload *per trip* for the launch-per-iteration
path.

Aggregate sums accumulate tile-sequentially, so final lam/b/f match the
reference solver exact-to-dtype (PR 3's convention; see
tests/test_market_clear.py), not bitwise; the bitwise fallback is
``ops.market_clear(use_pallas=False)`` -> ``ref.market_clear_ref`` which
delegates to the reference solver itself.

``mbdf_demand`` moves the auction's joint (N, M) ``fairness.mbdf_grid``
bisection onto the same tiling conventions: grid (n_tiles,), each launch
step solving all M price columns of a (TILE_N, M) block against its
(TILE_N, K) service tile, so services stream from HBM once, not M times.

Padding conventions match ``bisect_alloc``/``dual_demand``: padded client
slots carry alpha = 0, K pads to the 128-lane multiple, N to the tile.
Inactive rows (sum alpha = 0) demand nothing at any price and emit
b = f = 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dual_demand import (
    F_CEIL, NEG_INF, TINY, demand_slope_tile,
)

TILE_N = 128      # row tile of the megakernel's internal loop
TILE_N_MBDF = 8   # row tile of the (N, M) mbdf grid kernel


def _freq_tile(alpha, tcomp, b, iters: int):
    """Eq. 7 round time -> frequency for one (TN, K) tile at bandwidth b.

    Mirrors ``intra.solve_round_time``'s arithmetic exactly (bisection on
    u = t - max_k t^C with the hoisted gap masking) so the megakernel's final
    f matches the reference solver's ``intra.freq`` to dtype.
    """
    valid = alpha > 0.0
    asum = jnp.sum(alpha, axis=1, keepdims=True)                 # (TN, 1)
    tcmax = jnp.max(jnp.where(valid, tcomp, NEG_INF), axis=1, keepdims=True)
    u_hi = asum / jnp.maximum(b, TINY)
    gap = jnp.where(valid, tcmax - tcomp, 1.0)                   # (TN, K)

    def body(_, carry):
        lo, hi = carry
        u = 0.5 * (lo + hi)
        val = jnp.sum(alpha / (u + gap), axis=1, keepdims=True) - b
        go_right = val > 0.0
        return jnp.where(go_right, u, lo), jnp.where(go_right, hi, u)

    lo, hi = jax.lax.fori_loop(0, iters, body, (jnp.zeros_like(u_hi), u_hi))
    t_star = tcmax + 0.5 * (lo + hi)
    return jnp.where(b > 0.0, 1.0 / t_star, 0.0)


def _market_clear_kernel(alpha_ref, tcomp_ref, btot_ref, lamprev_ref,
                         b_ref, f_ref, lam_ref, *,
                         iters: int, inner_iters: int,
                         newton_inner_iters: int, tile_n: int, n_tiles: int):
    b_total = btot_ref[0, 0]
    lam_prev = lamprev_ref[0, 0]

    def rows(j):
        return pl.ds(j * tile_n, tile_n)

    # --- bracket top: lam_hi0 = max_n p_max (exact: max is associative) ----
    def pmax_tile(j, acc):
        asum = jnp.sum(alpha_ref[rows(j), :], axis=1)
        p = jnp.where(asum > 0.0, 1.0 / jnp.maximum(asum, TINY), 0.0)
        return jnp.maximum(acc, jnp.max(p))

    lam_hi0 = jax.lax.fori_loop(0, n_tiles, pmax_tile, jnp.float32(0.0))

    # --- warm seed (identical to solve_lambda_newton_warm) -----------------
    warm_ok = jnp.logical_and(lam_prev > 0.0, lam_prev < lam_hi0)
    lam0 = jnp.where(warm_ok, lam_prev, 0.5 * lam_hi0)

    # --- the fixed-trip safeguarded-Newton loop, entirely in VMEM ----------
    def newton(_, state):
        lam, lo, hi = state

        def dtile(j, acc):
            d_acc, s_acc = acc
            b_t, s_t = demand_slope_tile(
                alpha_ref[rows(j), :], tcomp_ref[rows(j), :], lam,
                newton_inner_iters)
            return d_acc + jnp.sum(b_t), s_acc + jnp.sum(s_t)

        d, slope = jax.lax.fori_loop(
            0, n_tiles, dtile, (jnp.float32(0.0), jnp.float32(0.0)))
        resid = d - b_total
        lo = jnp.where(resid > 0, lam, lo)   # demand too high -> raise price
        hi = jnp.where(resid > 0, hi, lam)
        step = resid / jnp.where(jnp.abs(slope) > TINY, slope, -TINY)
        lam_newton = lam - step
        # Non-strict bounds, matching the reference: a converged iterate
        # reproduces itself instead of bouncing to the midpoint.
        in_bracket = jnp.logical_and(lam_newton >= lo, lam_newton <= hi)
        lam_next = jnp.where(in_bracket, lam_newton, 0.5 * (lo + hi))
        return lam_next, lo, hi

    lam, _, _ = jax.lax.fori_loop(
        0, iters, newton, (lam0, jnp.float32(0.0), lam_hi0))

    # --- final demand at the full inner trip count + aggregate -------------
    def demand_tile(j, total):
        b_t, _ = demand_slope_tile(
            alpha_ref[rows(j), :], tcomp_ref[rows(j), :], lam, inner_iters)
        b_ref[rows(j), :] = b_t
        return total + jnp.sum(b_t)

    total = jax.lax.fori_loop(0, n_tiles, demand_tile, jnp.float32(0.0))

    # --- project onto sum b = B, then Eq. 7 round time -> f ----------------
    scale = b_total / jnp.maximum(total, TINY)

    def finish_tile(j, carry):
        b_t = b_ref[rows(j), :] * scale
        b_ref[rows(j), :] = b_t
        f_ref[rows(j), :] = _freq_tile(
            alpha_ref[rows(j), :], tcomp_ref[rows(j), :], b_t, inner_iters)
        return carry

    jax.lax.fori_loop(0, n_tiles, finish_tile, jnp.float32(0.0))
    lam_ref[0, 0] = lam


@functools.partial(jax.jit, static_argnames=("iters", "inner_iters",
                                             "newton_inner_iters", "tile_n",
                                             "interpret"))
def market_clear(
    alpha: jax.Array,     # (N, K) f32, 0 at padded client slots
    t_comp: jax.Array,    # (N, K) f32
    b_total: jax.Array,   # () f32 bandwidth budget B
    lam_prev: jax.Array,  # () f32 previous dual price (<= 0: cold seed)
    *,
    iters: int = 6,
    inner_iters: int = 48,
    newton_inner_iters: int = 24,
    tile_n: int = TILE_N,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused launch of the whole market clear.  Returns (b (N,), f (N,),
    lam ())."""
    n, k = alpha.shape
    k_pad = (k + 127) // 128 * 128
    n_pad = (n + tile_n - 1) // tile_n * tile_n
    if (n_pad, k_pad) != (n, k):
        alpha = jnp.pad(alpha, ((0, n_pad - n), (0, k_pad - k)))
        t_comp = jnp.pad(t_comp, ((0, n_pad - n), (0, k_pad - k)))
    n_tiles = n_pad // tile_n

    kernel = functools.partial(
        _market_clear_kernel, iters=iters, inner_iters=inner_iters,
        newton_inner_iters=newton_inner_iters, tile_n=tile_n, n_tiles=n_tiles)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    # Mosaic cannot store scalars to VMEM: B, the warm seed and lam travel
    # through (1, 1) SMEM refs.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    b, f, lam = pl.pallas_call(
        kernel,
        in_specs=[vmem, vmem, smem, smem],
        out_specs=[vmem, vmem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(alpha.astype(jnp.float32), t_comp.astype(jnp.float32),
      jnp.asarray(b_total, jnp.float32).reshape(1, 1),
      jnp.asarray(lam_prev, jnp.float32).reshape(1, 1))
    return b[:n, 0], f[:n, 0], lam[0, 0]


# ---------------------------------------------------------------------------
# Auction (N, M) joint mbdf bisection on the same tiling conventions.
# ---------------------------------------------------------------------------

def _mbdf_kernel(alpha_ref, tcomp_ref, price_ref, b_ref, *,
                 alpha_fair: float, iters: int):
    alpha = alpha_ref[...]                       # (TN, K)
    valid = alpha > 0.0
    # Padded slots read t^C = 0 (see ``demand_slope_tile``).
    tcomp = jnp.where(valid, tcomp_ref[...], 0.0)
    prices = price_ref[...]                      # (TN, M)
    m = prices.shape[1]
    cols = [prices[:, j:j + 1] for j in range(m)]

    asum = jnp.sum(alpha, axis=1, keepdims=True)
    tcmax = jnp.max(jnp.where(valid, tcomp, NEG_INF), axis=1, keepdims=True)
    active = asum > 0.0
    f_hi = jnp.where(active, F_CEIL / jnp.maximum(tcmax, TINY), 0.0)

    def q_at(f):
        one_m = jnp.maximum(1.0 - tcomp * f, TINY)
        s = jnp.sum(alpha / (one_m * one_m), axis=1, keepdims=True)
        # q(f) = g'(b) at f: [(1-a) + a/(1+f)] * f*'(b)  (Eq. 21 derivative)
        return ((1.0 - alpha_fair) + alpha_fair / (1.0 + f)) \
            * (1.0 / jnp.maximum(s, TINY))

    def body(_, carry):
        # All M price columns bisect together: one (lo, hi) pair per column.
        out = []
        for price, (lo, hi) in zip(cols, carry):
            f = 0.5 * (lo + hi)
            go_right = (q_at(f) - price) > 0.0   # q decreasing in f
            out.append((jnp.where(go_right, f, lo), jnp.where(go_right, hi, f)))
        return tuple(out)

    lo0 = jnp.zeros_like(f_hi)
    brackets = jax.lax.fori_loop(0, iters, body,
                                 tuple((lo0, f_hi) for _ in range(m)))

    p_max = jnp.where(active, 1.0 / jnp.maximum(asum, TINY), 0.0)
    for j, (price, (lo, hi)) in enumerate(zip(cols, brackets)):
        f = jnp.where(price >= p_max, 0.0, 0.5 * (lo + hi))
        one_m = jnp.maximum(1.0 - tcomp * f, TINY)
        b_ref[:, j:j + 1] = jnp.sum(alpha * f / one_m, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("alpha_fair", "iters", "tile_n",
                                             "interpret"))
def mbdf_demand(
    alpha: jax.Array,    # (N, K) f32, 0 at padded client slots
    t_comp: jax.Array,   # (N, K) f32
    prices: jax.Array,   # (N, M) f32 ascending price grid
    alpha_fair: float,
    *,
    iters: int = 48,
    tile_n: int = TILE_N_MBDF,
    interpret: bool = False,
) -> jax.Array:
    """Modified bandwidth demand d_n(p_m) at the whole (N, M) grid -> (N, M).

    Grid (n_tiles,): each step takes one (TILE_N, K) service tile with its
    whole (TILE_N, M) price block, so the tile streams from HBM once and all
    M joint bisections run on it in VMEM.  The price and output blocks span
    the full M axis, which is what Mosaic requires of a last block dimension
    that is not a multiple of 128.
    """
    n, k = alpha.shape
    m = prices.shape[1]
    k_pad = (k + 127) // 128 * 128
    n_pad = (n + tile_n - 1) // tile_n * tile_n
    if (n_pad, k_pad) != (n, k):
        alpha = jnp.pad(alpha, ((0, n_pad - n), (0, k_pad - k)))
        t_comp = jnp.pad(t_comp, ((0, n_pad - n), (0, k_pad - k)))
        prices = jnp.pad(prices, ((0, n_pad - n), (0, 0)), constant_values=1.0)

    out = pl.pallas_call(
        functools.partial(_mbdf_kernel, alpha_fair=alpha_fair, iters=iters),
        grid=(n_pad // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, m), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, m), jnp.float32),
        interpret=interpret,
    )(alpha.astype(jnp.float32), t_comp.astype(jnp.float32),
      prices.astype(jnp.float32))
    return out[:n, :]
