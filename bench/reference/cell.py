"""Plain reference of one period of a wireless cell (arXiv:2101.03627 §III,
§VI.A), written from the paper and independent of the program under test.

The data are drawn from the same key stream the program draws them from,
so the reference and the program see the same services; everything after
the draws is computed here, in the dtype the caller asks for.

Each service n has clients k with a transmission load
alpha_nk = s/r_dl + s/r_ul [MHz s] and a compute time t^C_nk [s].  Given a
bandwidth b_n the best round time t solves sum_k alpha_nk / (t - t^C_nk) =
b_n (Eq. 7); the service's FL frequency is f_n = 1 / t.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

TRIPS = 40      # bisection halvings: far below float32 resolution


def sample(key, counts, net: dict, k_max: int, dtype):
    """(alpha, t_comp, mask), each (N, K), of one period's services.

    ``counts`` (N,) are the enrolled clients of each slot.  The draws are
    the paper's §VI.A statistics; they are taken in float32, as the
    program takes them, and the arithmetic after them is in ``dtype``."""
    n = counts.shape[0]
    keys = jax.random.split(key, 8)
    eps_service = jax.random.normal(keys[1], (n, 1)).astype(dtype)
    eps_client = jax.random.normal(keys[2], (n, k_max)).astype(dtype)
    size = jax.random.uniform(keys[3], (n, 1), minval=net["model_mbit_lo"],
                              maxval=net["model_mbit_hi"]).astype(dtype)
    p_ul = jax.random.uniform(keys[4], (n, k_max), minval=net["p_ul_lo"],
                              maxval=net["p_ul_hi"]).astype(dtype)
    p_dl = jax.random.uniform(keys[5], (n, 1), minval=net["p_dl_lo"],
                              maxval=net["p_dl_hi"]).astype(dtype)
    t_local = jax.random.uniform(keys[6], (n, k_max), minval=net["t_local_lo"],
                                 maxval=net["t_local_hi"]).astype(dtype)

    def c(x):
        return jnp.asarray(x, dtype)

    loss_db = (c(net["mean_pathloss_db"])
               + c(net["var_pathloss_db"] ** 0.5) * eps_service
               + c(net["var_pathloss_client_db"] ** 0.5) * eps_client)
    gain = jnp.power(c(10.0), -loss_db / c(10.0))
    noise = c(net["noise_w"])
    r_dl = jnp.log2(c(1.0) + p_dl * gain / noise)
    r_ul = jnp.log2(c(1.0) + p_ul * gain / noise)
    mask = jnp.arange(k_max)[None, :] < counts[:, None]
    alpha = jnp.where(mask, size / r_dl + size / r_ul, c(0.0))
    t_comp = jnp.where(mask, t_local + c(net["t_global"]), c(0.0))
    return alpha, t_comp, mask


def bisect(fn, lo, hi, trips: int = TRIPS):
    """Root of a function that falls from >= 0 at lo to <= 0 at hi."""

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) / 2
        up = fn(mid) > 0
        return jnp.where(up, mid, lo), jnp.where(up, hi, mid)

    lo, hi = jax.lax.fori_loop(0, trips, body, (lo, hi))
    return (lo + hi) / 2


def frequency(alpha, t_comp, mask, b):
    """Eq. 7: f_n = 1 / t_n with sum_k alpha_nk / (t_n - t^C_nk) = b_n;
    0 for a service given no bandwidth."""
    dtype = alpha.dtype
    zero = jnp.asarray(0, dtype)
    slowest = jnp.max(jnp.where(mask, t_comp, zero), axis=-1)
    load = jnp.sum(alpha, axis=-1)
    given = b > 0
    safe_b = jnp.where(given, b, jnp.asarray(1, dtype))
    gap = jnp.where(mask, slowest[:, None] - t_comp, jnp.asarray(1, dtype))

    def excess(u):        # falls with u = t - slowest
        return jnp.sum(alpha / (u[:, None] + gap), axis=-1) - safe_b

    u = bisect(excess, jnp.zeros_like(load), load / safe_b)
    return jnp.where(given, 1 / (slowest + u), zero)


def demand_given_frequency(alpha, t_comp, f):
    """Eq. 7 solved for b: the bandwidth at which the service runs at f."""
    one = jnp.asarray(1, alpha.dtype)
    return jnp.sum(alpha * f[:, None] / (one - t_comp * f[:, None]), axis=-1)
