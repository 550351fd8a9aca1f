"""Plain reference of the selfish providers' multi-bid auction
(arXiv:2101.03627 §V), with truthful bids on the uniform price grid of
§V.E.

Service n values bandwidth by g_n(b) = (1 - a) f_n(b) + a log(1 + f_n(b))
(Eq. 21).  At the grid prices p_nm = m / (M + 1) * p_max_n, m = 1..M, with
p_max_n = 1 / sum_k alpha_nk, it bids the bandwidth at which its marginal
valuation g_n'(b) equals the price: in terms of f,

    [(1 - a) + a / (1 + f)] / sum_k alpha_nk / (1 - t^C_nk f)^2 = p.

The operator clears the book (Eqns. 22-26): the clearing price zeta is the
highest bid price at which the demand at or above it exceeds B; each
service gets its demand strictly above zeta, and the services that bid
exactly zeta share what is left of B in proportion to their steps there.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.cell import bisect, demand_given_frequency, frequency


def bids(alpha, t_comp, mask, n_bids: int, alpha_fair: float):
    """(prices, demands), each (N, M)."""
    dtype = alpha.dtype
    one, zero = jnp.asarray(1, dtype), jnp.asarray(0, dtype)
    a = jnp.asarray(alpha_fair, dtype)
    load = jnp.sum(alpha, axis=-1)
    active = load > 0
    p_max = jnp.where(active, one / jnp.where(active, load, one), zero)
    m = jnp.arange(1, n_bids + 1).astype(dtype)
    prices = m[None, :] * p_max[:, None] / jnp.asarray(n_bids + 1, dtype)
    slowest = jnp.max(jnp.where(mask, t_comp, zero), axis=-1)
    top = jnp.where(active, one / jnp.where(slowest > 0, slowest, one), zero)

    def column(p):
        def excess(f):      # marginal valuation - p: falls with f
            s = jnp.sum(alpha / (one - t_comp * f[:, None]) ** 2, axis=-1)
            return ((one - a) + a / (one + f)) / s - p

        f = bisect(excess, jnp.zeros_like(top), top)
        f = jnp.where(p < p_max, f, zero)
        return demand_given_frequency(alpha, t_comp, f)

    demands = jnp.stack([column(prices[:, j]) for j in range(n_bids)],
                        axis=1)
    return prices, demands


def clear(prices, demands, b_total):
    """Eq. 26 allocation of the book: (N,) bandwidth."""
    dtype = prices.dtype
    zero = jnp.asarray(0, dtype)
    b_total = jnp.asarray(b_total, dtype)
    nxt = jnp.concatenate([demands[:, 1:], jnp.zeros_like(demands[:, :1])],
                          axis=1)
    step = demands - nxt                 # demand added as the price falls
    flat_p, flat_s = prices.reshape(-1), step.reshape(-1)
    order = jnp.argsort(-flat_p)
    p_desc, s_desc = flat_p[order], flat_s[order]
    cum = jnp.cumsum(s_desc)
    # Demand at a price counts every bid at that price: only the last of a
    # run of equal prices is a candidate.
    last = jnp.concatenate([p_desc[:-1] > p_desc[1:], jnp.array([True])])
    over = (cum > b_total) & last & (p_desc > 0)
    zeta = jnp.where(jnp.any(over), p_desc[jnp.argmax(over)], zero)
    above = jnp.sum(jnp.where(prices > zeta, step, zero), axis=1)
    at = jnp.sum(jnp.where(prices == zeta, step, zero), axis=1)
    at = jnp.where(zeta > 0, at, zero)
    left = jnp.maximum(b_total - jnp.sum(above), zero)
    total_at = jnp.sum(at)
    share = jnp.where(total_at > 0, at / jnp.where(total_at > 0, total_at, 1)
                      * left, zero)
    return above + share


def allocate(alpha, t_comp, mask, b_total, n_bids: int, alpha_fair: float):
    """(b, f), each (N,), of one period."""
    prices, demands = bids(alpha, t_comp, mask, n_bids, alpha_fair)
    b = clear(prices, demands, b_total)
    return b, frequency(alpha, t_comp, mask, b)
