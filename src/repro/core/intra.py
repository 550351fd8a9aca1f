"""Intra-service bandwidth allocation (paper §III.A / §IV.A, Eqns. 1-10, 14).

Given a service's bandwidth budget b_n, the optimal per-client split equalizes
completion times (Eq. 6); the optimal round time t*_n is the unique root of

    h(t) = sum_k alpha_{n,k} / (t - t^C_{n,k}) - b_n = 0        (Eq. 7)

on (max_k t^C_{n,k}, inf).  All solvers here are fixed-trip bisections written
array-wise over a batched ServiceSet, so one call solves every service at once;
they are jit/vmap/shard_map-friendly and free of data-dependent shapes.

Also provided: the frequency function f*_n(b) = 1/t*_n and its first/second
derivatives (Lemma 1), the price->frequency inverse of the per-provider
Lagrangian stationarity condition (Eq. 14), and the frequency->bandwidth map
(Eq. 7 rewritten in f).  These are the primitives DISBA and the auction build on.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.types import BISECT_ITERS, ServiceSet

_TINY = 1e-30


def _bisect(fn, lo, hi, iters: int = BISECT_ITERS):
    """Batched bisection for a decreasing-in-root sign convention.

    Finds x with fn(x) = 0 where fn is monotone *decreasing* (fn(lo) >= 0 >=
    fn(hi)).  lo/hi/fn-output share an arbitrary batch shape.  Fixed trip count
    -> constant-time, fully vectorized.
    """

    def body(_, state):
        lo_, hi_ = state
        mid = 0.5 * (lo_ + hi_)
        val = fn(mid)
        go_right = val > 0.0
        return jnp.where(go_right, mid, lo_), jnp.where(go_right, hi_, mid)

    lo_f, hi_f = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo_f + hi_f)


# ---------------------------------------------------------------------------
# t*(b) / f*(b): the intra-service optimum.
# ---------------------------------------------------------------------------

def solve_round_time(svc: ServiceSet, b: jax.Array, iters: int = BISECT_ITERS) -> jax.Array:
    """Optimal round length t*_n(b_n) for every service.  b: (N,) MHz -> (N,) s.

    Solves Eq. 7 by bisection on u = t - max_k t^C, bracketed by
    (0, sum_k alpha / b]: at u->0+ the slowest client's term diverges (+inf);
    at u_hi = sum(alpha)/b, sum_k alpha/(u + tCmax - tC_k) <= sum(alpha)/u_hi = b.
    Services with b<=0 get t* = +inf.
    """
    t_cmax = svc.t_comp_max()                       # (N,)
    a_sum = svc.alpha_sum()                         # (N,)
    safe_b = jnp.maximum(b, _TINY)
    u_hi = a_sum / safe_b

    # Loop-invariant masking, hoisted out of the bisection body: alpha is
    # exactly 0 at masked slots (0 / positive = 0 contributes nothing), and
    # the masked gap is set to 1 (any positive value) so the denominator
    # never needs a per-trip ``where``.  Each of the ``iters`` trips is then
    # a single fused multiply-sum over the (N, K) tile.
    alpha_m = jnp.where(svc.mask, svc.alpha, 0.0)
    # Gap of each client's pole below the slowest client's pole (>= 0).
    gap = jnp.where(svc.mask, t_cmax[:, None] - svc.t_comp, 1.0)  # (N, K)

    def h(u):  # u: (N,)
        return jnp.sum(alpha_m / (u[:, None] + gap), axis=-1) - b

    u_star = _bisect(h, jnp.zeros_like(u_hi), u_hi, iters)
    t_star = t_cmax + u_star
    return jnp.where(b > 0.0, t_star, jnp.inf)


def client_allocation(svc: ServiceSet, b: jax.Array, iters: int = BISECT_ITERS) -> jax.Array:
    """Optimal per-client split b_{n,k} = alpha_{n,k} / (t* - t^C_{n,k}).  (N,K)."""
    t_star = solve_round_time(svc, b, iters)
    denom = jnp.maximum(t_star[:, None] - svc.t_comp, _TINY)
    raw = svc.alpha / denom
    raw = jnp.where(svc.mask, raw, 0.0)
    # Renormalize the residual bisection error so the budget holds exactly.
    total = jnp.maximum(jnp.sum(raw, axis=-1, keepdims=True), _TINY)
    return raw * (b[:, None] / total)


def freq(svc: ServiceSet, b: jax.Array, iters: int = BISECT_ITERS) -> jax.Array:
    """Optimal FL frequency f*_n(b_n) = 1 / t*_n(b_n).  (N,)."""
    t_star = solve_round_time(svc, b, iters)
    return jnp.where(jnp.isfinite(t_star), 1.0 / t_star, 0.0)


# ---------------------------------------------------------------------------
# Derivatives of f*(b) (Lemma 1) -- closed-form given f.
# ---------------------------------------------------------------------------

def _masked_sum(svc: ServiceSet, x) -> jax.Array:
    return jnp.sum(jnp.where(svc.mask, x, 0.0), axis=-1)


def freq_prime_at_f(svc: ServiceSet, f: jax.Array) -> jax.Array:
    """f*'(b) expressed at frequency f (Eq. 9): ( sum_k alpha/(1 - tC f)^2 )^-1."""
    one_m = 1.0 - svc.t_comp * f[:, None]
    s = _masked_sum(svc, svc.alpha / jnp.maximum(one_m, _TINY) ** 2)
    return 1.0 / jnp.maximum(s, _TINY)


def freq_second_at_f(svc: ServiceSet, f: jax.Array) -> jax.Array:
    """f*''(b) at frequency f (Eq. 10)."""
    one_m = jnp.maximum(1.0 - svc.t_comp * f[:, None], _TINY)
    s2 = _masked_sum(svc, svc.alpha / one_m**2)
    s3 = _masked_sum(svc, svc.alpha * svc.t_comp / one_m**3)
    return -2.0 * s3 / jnp.maximum(s2, _TINY) ** 3


def bandwidth_from_freq(svc: ServiceSet, f: jax.Array) -> jax.Array:
    """Invert Eq. 7: b(f) = sum_k alpha_k * f / (1 - t^C_k f).  f in [0, 1/max tC)."""
    one_m = jnp.maximum(1.0 - svc.t_comp * f[:, None], _TINY)
    return _masked_sum(svc, svc.alpha * f[:, None] / one_m)


def f_max(svc: ServiceSet) -> jax.Array:
    """Supremum frequency 1 / max_k t^C_{n,k} (approached as b -> inf)."""
    return 1.0 / jnp.maximum(svc.t_comp_max(), _TINY)


def p_max(svc: ServiceSet) -> jax.Array:
    """f*'(0) = 1/sum_k alpha (Eq. 32): the price above which demand is zero.

    Inactive slots of a fixed-capacity set (alpha_sum = 0) get p_max = 0, so
    they opt out of every market (demand 0 at any price) instead of blowing
    up the dual bracket max_n p_max with a 1/0.
    """
    a_sum = svc.alpha_sum()
    return jnp.where(a_sum > 0.0, 1.0 / jnp.maximum(a_sum, _TINY), 0.0)


# ---------------------------------------------------------------------------
# Price -> (frequency, bandwidth): the DISBA inner problem (Eq. 12-14).
# ---------------------------------------------------------------------------

_F_CEIL = 1.0 - 1e-6  # stay strictly inside the 1 - tC*f > 0 region


def freq_from_price(svc: ServiceSet, lam: jax.Array, iters: int = BISECT_ITERS) -> jax.Array:
    """Solve the stationarity condition (Eq. 14) for f given the dual price lam:

        (1 + f) * sum_k alpha_k / (1 - t^C_k f)^2 = 1 / lam.

    The LHS is increasing on [0, 1/max tC); LHS(0) = sum(alpha) = 1/p_max.
    For lam >= p_max the provider demands nothing (f = 0, b = 0).
    lam may be scalar or (N,).
    """
    lam = jnp.broadcast_to(jnp.asarray(lam, dtype=svc.alpha.dtype), (svc.n_services,))
    f_hi = f_max(svc) * _F_CEIL
    target = 1.0 / jnp.maximum(lam, _TINY)

    # Hoisted loop-invariant masking: alpha_m is exactly 0 at masked slots, so
    # they contribute 0 to the sum without a per-trip ``where``.  t_comp is
    # zeroed there too, so a masked slot's 1 - t^C f stays 1: a slot slower
    # than every valid client would otherwise hit the TINY floor, whose square
    # underflows in f32 and turns its 0 numerator into 0/0 = NaN.
    alpha_m = jnp.where(svc.mask, svc.alpha, 0.0)
    t_comp_m = jnp.where(svc.mask, svc.t_comp, 0.0)

    def h(f):  # decreasing convention: target - LHS(f)
        one_m = jnp.maximum(1.0 - t_comp_m * f[:, None], _TINY)
        lhs = (1.0 + f) * jnp.sum(alpha_m / one_m**2, axis=-1)
        return target - lhs

    f_star = _bisect(h, jnp.zeros_like(f_hi), f_hi, iters)
    opt_out = lam >= p_max(svc)
    return jnp.where(opt_out, 0.0, f_star)


def demand(svc: ServiceSet, lam: jax.Array, iters: int = BISECT_ITERS) -> jax.Array:
    """b*_n(lam) = argmax_b [ log(1 + f*(b)) - lam*b ]  (Eq. 12), per service."""
    f_star = freq_from_price(svc, lam, iters)
    return bandwidth_from_freq(svc, f_star)


def price_at_freq(svc: ServiceSet, f: jax.Array) -> jax.Array:
    """lam(f) = f*'(b)/(1+f*) evaluated at frequency f (inverse of Eq. 13)."""
    return freq_prime_at_f(svc, f) / (1.0 + f)


# Convenience jitted entry points ------------------------------------------------

solve_round_time_jit = jax.jit(solve_round_time, static_argnames=("iters",))
freq_jit = jax.jit(freq, static_argnames=("iters",))
demand_jit = jax.jit(demand, static_argnames=("iters",))
client_allocation_jit = jax.jit(client_allocation, static_argnames=("iters",))
