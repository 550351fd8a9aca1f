"""Batched intra-service bandwidth allocation as a Pallas TPU kernel -- the
paper's computational hot-spot at fleet scale.

One launch solves Eq. 7 (sum_k alpha_k/(t - t^C_k) = b_n) for a whole tile of
services via fixed-trip bisection and emits both the optimal round time t*_n
and the per-client water-filling split b_{n,k}.  At production scale the
operator re-solves this for every active service each period (and inside
every DISBA dual iteration), so N reaches 1e5-1e6 service-solves per second
fleet-wide.  A grid step keeps a block of whole rows, all K client slots of
each, in VMEM through all 48 bisection trips, with zero HBM traffic beyond
the initial load -- the kernel is compute-bound on the VPU by design
(roofline analysis in EXPERIMENTS.md §Perf).  The block's rows follow K:
``row_blocks`` gives the most that fit its ``VMEM_PLANES`` planes of K
lanes in the VMEM budget (``tiling.row_tile``), so a wider client axis
takes more, smaller steps.

Padding convention: padded client slots carry alpha = 0 (they contribute 0 to
every sum and -inf to the t^C max).  K is padded to a lane multiple (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

NEG_INF = -1e30
TINY = 1e-30
# (block, K) float32 planes a grid step holds in VMEM: alpha and t^C in and
# the split out, each double-buffered (6), and the trip's temporaries (3).
# The TPU v5e compiler takes 8.2-8.5 planes at 1792 and 4096 lanes.
VMEM_PLANES = 9


def row_blocks(rows: int, k: int) -> tiling.Launch:
    """The launch ``bisect_alloc`` makes over ``rows`` rows of ``k`` client
    slots (a vmapped call's rows are the whole batch's): its padded
    rows and lanes, grid step and VMEM bytes a step."""
    return tiling.row_blocks(rows, k, VMEM_PLANES)


def _bisect_kernel(alpha_ref, tcomp_ref, b_ref, tstar_ref, balloc_ref, *, iters: int):
    alpha = alpha_ref[...]                       # (TN, K)
    tcomp = tcomp_ref[...]                       # (TN, K)
    b = b_ref[...]                               # (TN, 1)
    valid = alpha > 0.0

    tcmax = jnp.max(jnp.where(valid, tcomp, NEG_INF), axis=1, keepdims=True)  # (TN,1)
    asum = jnp.sum(alpha, axis=1, keepdims=True)
    safe_b = jnp.maximum(b, TINY)
    gap = jnp.where(valid, tcmax - tcomp, 0.0)   # >= 0; padded -> 0 but alpha=0

    u_hi = asum / safe_b
    u_lo = jnp.zeros_like(u_hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        denom = mid + gap
        h = jnp.sum(
            jnp.where(valid, alpha / jnp.maximum(denom, TINY), 0.0),
            axis=1, keepdims=True,
        ) - b
        go_right = h > 0.0
        return jnp.where(go_right, mid, lo), jnp.where(go_right, hi, mid)

    u_lo, u_hi = jax.lax.fori_loop(0, iters, body, (u_lo, u_hi))
    u = 0.5 * (u_lo + u_hi)
    t_star = tcmax + u

    raw = jnp.where(valid, alpha / jnp.maximum(u + gap, TINY), 0.0)
    total = jnp.maximum(jnp.sum(raw, axis=1, keepdims=True), TINY)
    balloc_ref[...] = raw * (b / total)
    tstar_ref[...] = jnp.where(b > 0.0, t_star, jnp.full_like(t_star, 1.0 / TINY))


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def bisect_alloc(
    alpha: jax.Array,    # (N, K) f32, 0 at padded client slots
    t_comp: jax.Array,   # (N, K) f32
    b: jax.Array,        # (N,) f32 per-service bandwidth budget
    *,
    iters: int = 48,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (t_star (N,), b_alloc (N, K)).  Under vmap, one launch over
    the rows of the whole batch (``tiling.fold_rows``)."""
    launch = functools.partial(_launch, iters=iters, interpret=interpret)
    return tiling.fold_rows(launch)(alpha, t_comp, b)


def _launch(alpha, t_comp, b, *, iters: int, interpret: bool):
    n, k = alpha.shape
    blocks = row_blocks(n, k)
    n_pad, k_pad, tile_n, _ = blocks
    if (n_pad, k_pad) != (n, k):
        alpha = jnp.pad(alpha, ((0, n_pad - n), (0, k_pad - k)))
        t_comp = jnp.pad(t_comp, ((0, n_pad - n), (0, k_pad - k)))
        b = jnp.pad(b, (0, n_pad - n), constant_values=1.0)

    grid = (n_pad // tile_n,)
    t_star, b_alloc = pl.pallas_call(
        functools.partial(_bisect_kernel, iters=iters),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        ],
        compiler_params=blocks.compiler_params,
        interpret=interpret,
    )(alpha.astype(jnp.float32), t_comp.astype(jnp.float32),
      b.astype(jnp.float32)[:, None])
    return t_star[:n, 0], b_alloc[:n, :k]
