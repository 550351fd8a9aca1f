"""Fairness-adjusted utilities and exact market clearing (paper §V.B).

The fairness-adjusted benefit of provider n is

    g_n(b) = (1 - alpha_fair) * f*_n(b) + alpha_fair * log(1 + f*_n(b))

(Eq. 21).  Its derivative defines the modified marginal valuation function
(mMVF)  q_n(b) = g'_n(b)  and its inverse the modified bandwidth demand
function (mBDF)  d_n(p) = (g'_n)^{-1}(p).  The modified market clearing price
(mMCP) zeta solves  sum_n d_n(zeta) = B  and the induced allocation maximizes
sum_n g_n(b_n) (Prop. 3).  alpha_fair = 0 recovers total-frequency
maximization (Prop. 2's MCP); alpha_fair = 1 recovers proportional fairness,
i.e. the cooperative DISBA solution.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import intra
from repro.core.types import BISECT_ITERS, ServiceSet

_TINY = 1e-30


def g_value(f: jax.Array, alpha_fair: float) -> jax.Array:
    """g_n expressed at frequency f (Eq. 21's benefit part)."""
    return (1.0 - alpha_fair) * f + alpha_fair * jnp.log1p(f)


def g_prime_at_f(svc: ServiceSet, f: jax.Array, alpha_fair: float) -> jax.Array:
    """q_n(b) = g'_n(b) at frequency f: [(1-a) + a/(1+f)] * f*'(b)."""
    w = (1.0 - alpha_fair) + alpha_fair / (1.0 + f)
    return w * intra.freq_prime_at_f(svc, f)


def fairness_cost(f: jax.Array, alpha_fair: float) -> jax.Array:
    """The ex-post fairness-adjusted charge alpha * (f - log(1+f)) (§V.B.2)."""
    return alpha_fair * (f - jnp.log1p(f))


def mbdf(
    svc: ServiceSet,
    price: jax.Array,
    alpha_fair: float,
    iters: int = BISECT_ITERS,
) -> jax.Array:
    """Modified bandwidth demand d_n(p) = (g'_n)^{-1}(p), batched over services.

    g'_n(b) is decreasing in b (concavity), so we bisect on f in
    [0, f_max): find f with q(f) = p, then map to b via Eq. 7.
    Demand is 0 for p >= q(0) = g'_n(0) = f*'(0) = 1/sum(alpha) (the weight
    [(1-a) + a/(1+f)] equals 1 at f=0, for any a).
    price: scalar or (N,).
    """
    price = jnp.broadcast_to(jnp.asarray(price, dtype=svc.alpha.dtype), (svc.n_services,))
    f_hi = intra.f_max(svc) * (1.0 - 1e-6)

    def h(f):  # q is decreasing in f; root of q(f) - p fits _bisect's convention
        return g_prime_at_f(svc, f, alpha_fair) - price

    f_star = intra._bisect(h, jnp.zeros_like(f_hi), f_hi, iters)
    f_star = jnp.where(price >= intra.p_max(svc), 0.0, f_star)
    return intra.bandwidth_from_freq(svc, f_star)


MBDF_BACKENDS = ("reference", "pallas")


def mbdf_grid(
    svc: ServiceSet,
    prices: jax.Array,
    alpha_fair: float,
    iters: int = BISECT_ITERS,
    backend: str = "reference",
) -> jax.Array:
    """Modified bandwidth demand at a whole (N, M) price grid in ONE joint
    bisection: the grid is flattened to an (N*M)-row replicated ServiceSet
    and handed to the scalar-price ``mbdf`` itself -- a single ``fori_loop``
    over the joint bracket instead of a vmap of M per-column solves, with
    the mMVF arithmetic keeping exactly one home.  Per element the ops are
    identical to the vmapped path, so the result matches it bitwise.

    ``backend="pallas"`` dispatches to the ``kernels/market_clear``
    (N, M)-grid kernel on the market tiling conventions instead: each
    (tile, K) row block streams from HBM once for all M price columns
    (no N*M row replication is ever materialized).  Exact-to-dtype against
    the reference (tests/test_market_clear.py).
    """
    prices = jnp.asarray(prices, dtype=svc.alpha.dtype)          # (N, M)
    if backend == "pallas":
        from repro.kernels import ops

        return ops.mbdf_demand(svc.alpha, svc.t_comp, prices, alpha_fair,
                               use_pallas=True, iters=iters)
    if backend != "reference":
        raise ValueError(f"unknown mbdf backend {backend!r}; "
                         f"expected one of {MBDF_BACKENDS}")
    n, m = prices.shape
    rep = ServiceSet(
        alpha=jnp.repeat(svc.alpha, m, axis=0),
        t_comp=jnp.repeat(svc.t_comp, m, axis=0),
        mask=jnp.repeat(svc.mask, m, axis=0),
    )
    return mbdf(rep, prices.reshape(-1), alpha_fair, iters).reshape(n, m)


class ClearingResult(NamedTuple):
    b: jax.Array      # (N,) allocation
    f: jax.Array      # (N,) resulting frequencies
    price: jax.Array  # () clearing price


@functools.partial(jax.jit, static_argnames=("alpha_fair", "iters", "inner_iters"))
def exact_mmcp(
    svc: ServiceSet,
    total_bandwidth: float,
    alpha_fair: float,
    iters: int = BISECT_ITERS,
    inner_iters: int = BISECT_ITERS,
) -> ClearingResult:
    """Full-information modified market clearing (Prop. 3): bisect the price
    until aggregate modified demand equals B.  The reference the multi-bid
    auction is an M-bid approximation of."""
    b_total = jnp.asarray(total_bandwidth, dtype=jnp.float32)
    p_hi = jnp.max(intra.p_max(svc))

    def h(p):
        return jnp.sum(mbdf(svc, p, alpha_fair, inner_iters)) - b_total

    price = intra._bisect(h, jnp.zeros_like(p_hi), p_hi, iters)
    b = mbdf(svc, price, alpha_fair, inner_iters)
    b = b * (b_total / jnp.maximum(jnp.sum(b), _TINY))
    return ClearingResult(b=b, f=intra.freq(svc, b, inner_iters), price=price)


def provider_utility(
    svc: ServiceSet, b: jax.Array, price: jax.Array, alpha_fair: float
) -> jax.Array:
    """u_n = f*(b) - p*b - alpha*(f*(b) - log(1+f*(b)))  (Eq. 21 with both charges)."""
    f = intra.freq(svc, b)
    return f - price * b - fairness_cost(f, alpha_fair)
