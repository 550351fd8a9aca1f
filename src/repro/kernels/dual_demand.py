"""Fused dual-demand evaluation as a Pallas TPU kernel -- one launch per
DISBA dual iteration.

Market clearing (cooperative DISBA, paper §IV) repeatedly evaluates the
aggregate demand D(lam) = sum_n b*_n(lam): each evaluation solves the Eq. 14
stationarity condition

    (1 + f) * sum_k alpha_k / (1 - t^C_k f)^2 = 1 / lam

for every service's frequency f, then maps f -> bandwidth via Eq. 7.  The
reference path materializes ~48 masked (N, K) array sweeps per evaluation; at
one evaluation per dual iteration of every period of every vmapped episode
this dominates the long-term simulation's allocation cost.

This kernel is the fused fast path: a grid step's block of whole rows, all
K client slots of each, runs the whole fixed-trip price->frequency
bisection in VMEM/VREGs and emits BOTH the per-service demand b_n(lam) and
its closed-form slope db_n/dlam (Lemma 1 /
Eqns. 9-10 via psi(f) = f'/(1+f)) in a single launch, so a safeguarded-Newton
dual iteration (``disba.solve_lambda_newton_warm``) is one kernel call
instead of ~48 jnp sweeps.  Zero HBM traffic beyond the initial tile load --
compute-bound on the VPU like its sibling ``bisect_alloc``.

Tiling/padding conventions match ``bisect_alloc``: padded client slots carry
alpha = 0 (zero contribution to every sum), K is padded to the 128-lane
multiple, N to the tile, whose rows ``row_blocks`` fits to K: the
block's ``VMEM_PLANES`` planes of K lanes fit the VMEM budget.  Rows with
sum(alpha) = 0 (inactive fixed-capacity slots) and opted-out providers
(lam >= p_max = 1/sum(alpha)) emit b = slope = 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

NEG_INF = -1e30
TINY = 1e-30
F_CEIL = 1.0 - 1e-6  # stay strictly inside the 1 - tC*f > 0 region (Eq. 14)
# (block, K) float32 planes a grid step holds in VMEM: alpha and t^C in,
# double-buffered (4), and the temporaries of the bisection and the slope
# sums (5).  The TPU v5e compiler takes 8.3 planes at 1792 lanes.
VMEM_PLANES = 9


def row_blocks(rows: int, k: int) -> tiling.Launch:
    """The launch ``dual_demand`` makes over ``rows`` rows of ``k`` client
    slots (a vmapped call's rows are the whole batch's): its padded
    rows and lanes, grid step and VMEM bytes a step."""
    return tiling.row_blocks(rows, k, VMEM_PLANES)


def demand_slope_tile(alpha, tcomp, lam, iters: int):
    """Per-row (demand, slope) for one (TN, K) tile at price(s) ``lam``.

    The in-VMEM home of the fused Eq. 14 price->frequency bisection plus the
    Lemma 1 / Eqns. 9-10 closed-form slope.  ``lam`` may be a (TN, 1) column
    (one price per row, the ``dual_demand`` launch shape) or a scalar (the
    ``market_clear`` megakernel broadcasts the current dual iterate over
    every tile).  Shared by both kernels so the per-row arithmetic is
    bitwise-identical between the per-evaluation and whole-solve launches.
    """
    valid = alpha > 0.0
    # Padded slots read t^C = 0, so their 1 - t^C f stays 1: a slot slower
    # than every valid client would otherwise hit the TINY floor, whose
    # square underflows in f32 and turns its 0 numerator into 0/0 = NaN.
    tcomp = jnp.where(valid, tcomp, 0.0)

    asum = jnp.sum(alpha, axis=1, keepdims=True)                 # (TN, 1)
    tcmax = jnp.max(jnp.where(valid, tcomp, NEG_INF), axis=1, keepdims=True)
    active = asum > 0.0
    # f_max = 1 / max_k t^C; inactive rows get a degenerate [0, 0] bracket.
    f_hi = jnp.where(active, F_CEIL / jnp.maximum(tcmax, TINY), 0.0)
    target = 1.0 / jnp.maximum(lam, TINY)

    def body(_, carry):
        lo, hi = carry
        f = 0.5 * (lo + hi)
        one_m = jnp.maximum(1.0 - tcomp * f, TINY)
        lhs = (1.0 + f) * jnp.sum(alpha / (one_m * one_m), axis=1,
                                  keepdims=True)
        go_right = (target - lhs) > 0.0          # LHS increasing in f
        return jnp.where(go_right, f, lo), jnp.where(go_right, hi, f)

    lo, hi = jax.lax.fori_loop(0, iters, body, (jnp.zeros_like(f_hi), f_hi))
    f = 0.5 * (lo + hi)

    # Providers opt out (demand 0) at/above p_max = f*'(0) = 1/sum(alpha).
    p_max = jnp.where(active, 1.0 / jnp.maximum(asum, TINY), 0.0)
    f = jnp.where(lam >= p_max, 0.0, f)

    one_m = jnp.maximum(1.0 - tcomp * f, TINY)
    s2 = jnp.sum(alpha / (one_m * one_m), axis=1, keepdims=True)
    s3 = jnp.sum(alpha * tcomp / (one_m * one_m * one_m), axis=1,
                 keepdims=True)
    b = jnp.sum(alpha * f / one_m, axis=1, keepdims=True)        # Eq. 7 in f

    # Closed-form slope: db/dlam = b'(f) / psi'(f) with b' = 1/f*' (Eq. 8),
    # psi(f) = f*'/(1+f) (Eq. 13), f*'/f*'' from Eqns. 9-10 and the chain
    # rule d(f*')/df = f*''/f*'.
    fp = 1.0 / jnp.maximum(s2, TINY)
    fpp = -2.0 * s3 / jnp.maximum(s2, TINY) ** 3
    psi_p = (fpp * (1.0 + f) / fp - fp) / (1.0 + f) ** 2
    slope = jnp.where(f > 0.0, (1.0 / fp) / psi_p, 0.0)
    return b, slope


def _dual_demand_kernel(alpha_ref, tcomp_ref, lam_ref, b_ref, slope_ref, *,
                        iters: int):
    b, slope = demand_slope_tile(alpha_ref[...], tcomp_ref[...], lam_ref[...],
                                 iters)
    b_ref[...] = b
    slope_ref[...] = slope


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def dual_demand(
    alpha: jax.Array,    # (N, K) f32, 0 at padded client slots
    t_comp: jax.Array,   # (N, K) f32
    lam: jax.Array,      # scalar or (N,) f32 dual price
    *,
    iters: int = 48,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (b (N,), db/dlam (N,)) -- per-service demand and slope.
    Under vmap, one launch over the rows of the whole batch
    (``tiling.fold_rows``)."""
    lam = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), alpha.shape[:1])
    launch = functools.partial(_launch, iters=iters, interpret=interpret)
    return tiling.fold_rows(launch)(alpha, t_comp, lam)


def _launch(alpha, t_comp, lam, *, iters: int, interpret: bool):
    n, k = alpha.shape
    blocks = row_blocks(n, k)
    n_pad, k_pad, tile_n, _ = blocks
    if (n_pad, k_pad) != (n, k):
        alpha = jnp.pad(alpha, ((0, n_pad - n), (0, k_pad - k)))
        t_comp = jnp.pad(t_comp, ((0, n_pad - n), (0, k_pad - k)))
        lam = jnp.pad(lam, (0, n_pad - n), constant_values=1.0)

    grid = (n_pad // tile_n,)
    b, slope = pl.pallas_call(
        functools.partial(_dual_demand_kernel, iters=iters),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        compiler_params=blocks.compiler_params,
        interpret=interpret,
    )(alpha.astype(jnp.float32), t_comp.astype(jnp.float32),
      lam.astype(jnp.float32)[:, None])
    return b[:n, 0], slope[:n, 0]
