"""Plain reference of the cooperative allocation (arXiv:2101.03627 §IV):
the bandwidth split that maximizes sum_n log(1 + f_n(b_n)) with
sum_n b_n = B, found at the market-clearing dual price.

At a price lam, service n runs at the f that solves the stationarity
condition (Eq. 14)

    (1 + f) * sum_k alpha_nk / (1 - t^C_nk f)^2 = 1 / lam,

and asks for b_n = sum_k alpha_nk f / (1 - t^C_nk f); it asks for nothing
when lam >= 1 / sum_k alpha_nk.  Total demand falls with lam, so the price
is found by bisection until demand meets B.  Every solve here is a plain
bisection run to the dtype's resolution, from a cold bracket: no state
is carried from one period to the next.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.cell import bisect, demand_given_frequency, frequency


def _demand(alpha, t_comp, mask, lam):
    dtype = alpha.dtype
    one, zero = jnp.asarray(1, dtype), jnp.asarray(0, dtype)
    load = jnp.sum(alpha, axis=-1)
    wants = lam * load < one
    slowest = jnp.max(jnp.where(mask, t_comp, zero), axis=-1)
    top = jnp.where(wants, one / jnp.where(slowest > 0, slowest, one), zero)
    target = one / lam

    def excess(f):      # target - LHS(f): falls with f
        lhs = (one + f) * jnp.sum(
            alpha / (one - t_comp * f[:, None]) ** 2, axis=-1)
        return target - lhs

    f = jnp.where(wants, bisect(excess, jnp.zeros_like(top), top), zero)
    return demand_given_frequency(alpha, t_comp, f)


def allocate(alpha, t_comp, mask, b_total, n_bids=None, alpha_fair=None):
    """(b, f), each (N,), of one period; rows without clients get 0."""
    dtype = alpha.dtype
    b_total = jnp.asarray(b_total, dtype)
    load = jnp.sum(alpha, axis=-1)
    active = load > 0
    top = jnp.max(jnp.where(active, 1 / jnp.where(active, load, 1), 0))

    def excess(lam):    # demand - B: falls with the price
        return jnp.sum(_demand(alpha, t_comp, mask, lam)) - b_total

    lam = bisect(excess, jnp.zeros_like(top), top)
    b = _demand(alpha, t_comp, mask, lam)
    total = jnp.sum(b)
    b = jnp.where(total > 0, b * (b_total / jnp.where(total > 0, total, 1)),
                  b)
    return b, frequency(alpha, t_comp, mask, b)
