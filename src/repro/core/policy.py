"""Unified AllocationPolicy interface over every inter-service allocator.

The paper evaluates five bandwidth-allocation regimes -- cooperative DISBA
(§IV), the fairness-adjusted selfish auction (§V), and the EC / ES / PP
benchmarks (§VI.D) -- and its long-term simulation re-runs the chosen one
every period.  Related work (e.g. arXiv:2011.12469) frames all of them as
instances of one periodic allocation step; this module is that frame:

    policy(svc: ServiceSet, b_total) -> (b, f)        # both (N,)

Every policy is a *pure jittable function* of a (possibly fixed-capacity,
mask-padded) ServiceSet.  Whole-service inactivity is expressed through the
client mask (see ``types.mask_inactive``): an all-masked row receives
b = f = 0 from every policy, so arrivals/departures in the multi-period
simulator are mask flips, not shape changes, and the whole episode compiles
once.

Policies are registered under string keys (``register`` /
``get_policy`` / ``available``), replacing the old if/elif dispatch in
``fl/simulator.py`` and ``launch/train.py``.

The intra-service sub-problem (Eq. 7: optimal round time + per-client
water-filling) is selectable via ``intra_backend``:

  * ``"reference"``  -- the pure-jnp fixed-trip bisection in ``core/intra``;
  * ``"pallas"``     -- the Pallas TPU kernel ``kernels/bisect_alloc`` (runs
                        in interpret mode off-TPU), the deployment path for
                        fleet-scale solves (EXPERIMENTS.md §Perf);
  * ``"megakernel"`` -- same intra-service kernel path, but ``coop``'s
                        *inter*-service dual solve additionally runs as ONE
                        fused ``kernels/market_clear`` launch (the whole
                        safeguarded-Newton iteration in VMEM) instead of one
                        ``dual_demand`` launch per trip -- the 1024-8192
                        service regime (EXPERIMENTS.md §Market scaling).

All backends solve the same equations with the same trip counts; parity is
asserted in tests/test_policy_simulator.py and tests/test_market_clear.py.
"""
from __future__ import annotations

import inspect
from typing import Any, Callable, NamedTuple, Protocol

import jax
import jax.numpy as jnp

from repro.core import auction, baselines, disba, intra
from repro.core.types import BISECT_ITERS, ServiceSet

INTRA_BACKENDS = ("reference", "pallas", "megakernel")

FreqFn = Callable[[ServiceSet, jax.Array], jax.Array]


class AllocationPolicy(Protocol):
    """A pure inter-service allocation step: (ServiceSet, B) -> (b, f)."""

    def __call__(
        self, svc: ServiceSet, b_total: jax.Array | float
    ) -> tuple[jax.Array, jax.Array]:
        ...


class StatefulPolicy(NamedTuple):
    """A policy with an optional fixed-shape carry threaded between periods.

    ``init_state(n) -> state`` builds the carry for an n-slot fixed-capacity
    set (an arbitrary pytree of arrays -- or ``()`` for stateless policies);
    ``step(svc, B, state) -> (b, f, state')`` is the per-period allocation.
    The carry's tree structure and array shapes are fixed at init, so the
    multi-period simulator threads it through its ``lax.scan`` carry and the
    period step still traces exactly once.

    Warm-started policies (``warm_start=True``) carry solver state -- e.g.
    ``coop`` carries the previous period's dual price, seeding a safeguarded
    Newton clear that replaces the 48-trip cold bisection.  Policies without
    a warm variant get the trivial wrapper (empty carry), so every
    (policy, warm_start) combination is valid.

    Batching contract: ``init_state`` must be a *pure, key-free* function of
    the slot count -- no RNG, no data-dependent shapes.  The sweep engines
    (``run_batch``'s vmap, ``run_fleet``'s shard_map of chunked vmaps) trace
    it once per episode batch, broadcasting the constant init across the
    seed axis and each device shard; a stateful init would need a key
    threaded per episode and would break the bitwise equivalence between
    sharded/chunked and flat sweeps.
    """

    init_state: Callable[[int], Any]
    step: Callable[..., tuple[jax.Array, jax.Array, Any]]


# ---------------------------------------------------------------------------
# Intra-service backend selection (reference jnp vs Pallas kernel).
# ---------------------------------------------------------------------------

def _pallas_solve(svc: ServiceSet, b: jax.Array, iters: int):
    """(t*, per-client split) via the kernel -- compiled on TPU, interpret
    elsewhere (the ``ops.intra_allocate`` dispatch convention)."""
    from repro.kernels import ops

    return ops.intra_allocate(svc.alpha, svc.t_comp, b, use_pallas=True,
                              iters=iters)


def _intra_impl(intra_backend: str) -> str:
    """Collapse the backend name to the intra-service implementation.

    ``"megakernel"`` changes only the *inter*-service dual solve (one fused
    ``market_clear`` launch); its intra-service sub-problems (round time /
    client split) ride the same ``bisect_alloc`` kernel as ``"pallas"``.
    """
    return "pallas" if intra_backend == "megakernel" else intra_backend


def freq_fn(intra_backend: str = "reference", iters: int = BISECT_ITERS) -> FreqFn:
    """f*(b) with the chosen intra-service solver backend."""
    intra_backend = _intra_impl(intra_backend)
    if intra_backend == "reference":
        return lambda svc, b: intra.freq(svc, b, iters)
    if intra_backend == "pallas":

        def _freq(svc: ServiceSet, b: jax.Array) -> jax.Array:
            t_star, _ = _pallas_solve(svc, b, iters)
            # kernel reports t* ~ 1/TINY for b <= 0 rows; map those to f = 0
            return jnp.where(
                jnp.logical_and(b > 0.0, t_star < 1e20),
                1.0 / jnp.maximum(t_star, 1e-30), 0.0,
            )

        return _freq
    raise ValueError(f"unknown intra backend {intra_backend!r}; "
                     f"expected one of {INTRA_BACKENDS}")


def client_split_fn(
    intra_backend: str = "reference", iters: int = BISECT_ITERS
) -> Callable[[ServiceSet, jax.Array], jax.Array]:
    """Per-client water-filling split b_{n,k} with the chosen backend."""
    intra_backend = _intra_impl(intra_backend)
    if intra_backend == "reference":
        return lambda svc, b: intra.client_allocation(svc, b, iters)
    if intra_backend == "pallas":
        return lambda svc, b: _pallas_solve(svc, b, iters)[1]
    raise ValueError(f"unknown intra backend {intra_backend!r}; "
                     f"expected one of {INTRA_BACKENDS}")


def round_time_fn(
    intra_backend: str = "reference", iters: int = BISECT_ITERS
) -> Callable[[ServiceSet, jax.Array], jax.Array]:
    """Optimal round time t*_n(b_n) with the chosen backend ((N,) seconds;
    +inf for b <= 0 rows).  The co-simulation derives per-round straggler
    deadlines from this -- same solver family as the allocation itself, so
    the deadline is consistent with the allocated latencies."""
    intra_backend = _intra_impl(intra_backend)
    if intra_backend == "reference":
        return lambda svc, b: intra.solve_round_time(svc, b, iters)
    if intra_backend == "pallas":

        def _t(svc: ServiceSet, b: jax.Array) -> jax.Array:
            t_star, _ = _pallas_solve(svc, b, iters)
            # kernel reports t* ~ 1/TINY for b <= 0 rows; map those to +inf
            return jnp.where(
                jnp.logical_and(b > 0.0, t_star < 1e20), t_star, jnp.inf)

        return _t
    raise ValueError(f"unknown intra backend {intra_backend!r}; "
                     f"expected one of {INTRA_BACKENDS}")


def launch_shape(name: str, *, intra_backend: str = "reference",
                 warm_start: bool = False, n: int, k: int, batch: int = 1
                 ) -> tuple[int, int] | None:
    """(rows, lanes) of one launch of policy ``name``'s per-period solves,
    over ``batch`` (n, k) service sets vmapped together: the sets
    themselves where no kernel runs, else the padding of the Pallas
    wrappers the policy calls.  ``bisect_alloc``, ``dual_demand`` and
    ``mbdf_demand`` fold the batch into their rows (``tiling.fold_rows``)
    and pad the folded rows once, each in its own row blocks
    (``tiling.row_tile``); where the policy's kernels take different
    blocks, as they do once k spans several lane groups, this is the
    launch of the one whose grid step holds the most VMEM bytes.  The
    megakernel is not folded, so each set pads to its own tile of 128.
    None for cold ``coop`` on ``pallas``, whose dual bisection runs on the
    reference at (n, k) and whose f*(b) on ``bisect_alloc``'s padding, so
    its solves share no one shape."""
    launch = _solve_launch(name, intra_backend, warm_start, n, k, batch)
    return launch and launch[:2]


def launch_tile(name: str, *, intra_backend: str = "reference",
                warm_start: bool = False, n: int, k: int, batch: int = 1
                ) -> int | None:
    """Rows each grid step of that launch takes; None where no kernel runs
    or the solves share no one shape."""
    launch = _solve_launch(name, intra_backend, warm_start, n, k, batch)
    return launch and launch[2]


def launch_block_bytes(name: str, *, intra_backend: str = "reference",
                       warm_start: bool = False, n: int, k: int,
                       batch: int = 1) -> int | None:
    """VMEM bytes one grid step of that launch holds, by the account its
    row tile was fitted to (``tiling.block_bytes``); None where no folded
    kernel runs (the megakernel holds its whole input at once)."""
    launch = _solve_launch(name, intra_backend, warm_start, n, k, batch)
    return launch and launch[3]


def _solve_launch(name, intra_backend, warm_start, n, k, batch):
    """(rows, lanes, row tile, block bytes) behind ``launch_shape``,
    ``launch_tile`` and ``launch_block_bytes`` (tests/test_obs.py checks
    each combination against the traced step)."""
    from repro.kernels import bisect_alloc, dual_demand, market_clear, tiling

    if intra_backend == "reference" or name == "ec":
        return batch * n, k, None, None
    if name == "coop" and intra_backend == "megakernel":
        # One grid step a set: it loops over its own tiles inside.
        rows, lanes = tiling.padded_shape(n, k, market_clear.TILE_N)
        return batch * rows, lanes, rows, None
    if name == "coop" and not warm_start:
        return None
    # Each kernel the policy launches: its bids or dual demand, then every
    # policy's f*(b) on bisect_alloc.
    kernels = {"selfish": (market_clear.mbdf_row_blocks,),
               "coop": (dual_demand.row_blocks,)}.get(name, ())
    return max((blocks(batch * n, k)
                for blocks in kernels + (bisect_alloc.row_blocks,)),
               key=lambda launch: launch.block_bytes)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[..., AllocationPolicy]] = {}


def register(name: str):
    """Register a policy factory under ``name``.

    A factory takes keyword options (n_bids, alpha_fair, intra_backend, ...)
    and returns the pure allocation function.  Factories are free to ignore
    options they don't use.
    """

    def deco(factory: Callable[..., AllocationPolicy]):
        _REGISTRY[name] = factory
        return factory

    return deco


def available() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_policy(
    name: str,
    *,
    n_bids: int = 5,
    alpha_fair: float = 0.5,
    intra_backend: str = "reference",
    iters: int = BISECT_ITERS,
    **unknown,
) -> AllocationPolicy:
    """Build the named policy, wrapped so inactive slots get b = f = 0.

    Unknown keyword options raise a ValueError: factories ignore options
    they don't use, so a typo (``alpha_fiar=...``) would otherwise be
    silently swallowed and the default used instead.
    """
    if name not in _REGISTRY:
        raise ValueError(f"unknown policy {name!r}; available: {available()}")
    if unknown:
        raise ValueError(
            f"unknown option(s) {sorted(unknown)} for policy {name!r}; "
            f"known options: {list(KNOWN_OPTIONS)}")
    raw = _REGISTRY[name](
        n_bids=n_bids, alpha_fair=alpha_fair,
        intra_backend=intra_backend, iters=iters,
    )

    def wrapped(svc: ServiceSet, b_total):
        b, f = raw(svc, b_total)
        active = svc.service_active()
        # EC's min-rate round time is -inf on an empty row -> clamp, then mask.
        b = jnp.where(active, b, 0.0)
        f = jnp.where(active, jnp.maximum(f, 0.0), 0.0)
        return b, f

    return wrapped


# Derived from the signature so the unknown-option error can never list a
# stale set of known options.
KNOWN_OPTIONS = tuple(sorted(
    p.name for p in inspect.signature(get_policy).parameters.values()
    if p.kind == inspect.Parameter.KEYWORD_ONLY))


def allocate(name: str, svc: ServiceSet, b_total, **options):
    """One-shot convenience: ``get_policy(name, **options)(svc, b_total)``."""
    return get_policy(name, **options)(svc, b_total)


# ---------------------------------------------------------------------------
# Stateful (warm-startable) policies.
# ---------------------------------------------------------------------------

_STATEFUL_REGISTRY: dict[str, Callable[..., StatefulPolicy]] = {}


def register_stateful(name: str):
    """Register the warm-started (carry-threading) variant of a policy.

    The factory takes the same keyword options as the stateless one and
    returns a ``StatefulPolicy``.  Only policies that can exploit temporal
    coherence register here; every other name falls back to the trivial
    empty-carry wrapper in ``get_stateful_policy``.
    """

    def deco(factory: Callable[..., StatefulPolicy]):
        _STATEFUL_REGISTRY[name] = factory
        return factory

    return deco


def get_stateful_policy(
    name: str,
    *,
    warm_start: bool = False,
    n_bids: int = 5,
    alpha_fair: float = 0.5,
    intra_backend: str = "reference",
    iters: int = BISECT_ITERS,
    **unknown,
) -> StatefulPolicy:
    """Build the named policy in carry-threading form.

    ``warm_start=False`` (or a policy without a registered warm variant)
    wraps the stateless policy with an empty carry, so the step function is
    *identical* to ``get_policy``'s -- the default simulator path stays
    bitwise-unchanged.  ``warm_start=True`` selects the registered stateful
    variant where one exists (``coop``: previous-period dual price seeding a
    safeguarded-Newton market clear).
    """
    if name not in _REGISTRY:
        raise ValueError(f"unknown policy {name!r}; available: {available()}")
    if unknown:
        raise ValueError(
            f"unknown option(s) {sorted(unknown)} for policy {name!r}; "
            f"known options: {list(STATEFUL_KNOWN_OPTIONS)}")
    if warm_start and name in _STATEFUL_REGISTRY:
        raw = _STATEFUL_REGISTRY[name](
            n_bids=n_bids, alpha_fair=alpha_fair,
            intra_backend=intra_backend, iters=iters,
        )

        def step(svc: ServiceSet, b_total, state):
            b, f, state = raw.step(svc, b_total, state)
            active = svc.service_active()
            b = jnp.where(active, b, 0.0)
            f = jnp.where(active, jnp.maximum(f, 0.0), 0.0)
            return b, f, state

        return StatefulPolicy(init_state=raw.init_state, step=step)

    fn = get_policy(name, n_bids=n_bids, alpha_fair=alpha_fair,
                    intra_backend=intra_backend, iters=iters)

    def stateless_step(svc: ServiceSet, b_total, state):
        b, f = fn(svc, b_total)
        return b, f, state

    return StatefulPolicy(init_state=lambda n: (), step=stateless_step)


STATEFUL_KNOWN_OPTIONS = tuple(sorted(
    p.name for p in inspect.signature(get_stateful_policy).parameters.values()
    if p.kind == inspect.Parameter.KEYWORD_ONLY))


# ---------------------------------------------------------------------------
# The five paper policies.
# ---------------------------------------------------------------------------

@register("coop")
def _coop(*, intra_backend: str = "reference", iters: int = BISECT_ITERS, **_):
    """Cooperative DISBA via direct market clearing (same optimum as Alg. 1)."""
    _freq = freq_fn(intra_backend, iters)

    def fn(svc: ServiceSet, b_total):
        if intra_backend == "megakernel":
            # Cold fused clear: one launch runs 12 safeguarded-Newton trips
            # (matches solve_lambda_newton's cold configuration, which
            # reaches the bisect optimum to solver tolerance).
            res = disba.solve_lambda_newton_warm(
                svc, b_total, disba.WARM_COLD, iters=12, inner_iters=iters,
                newton_inner_iters=iters, backend="megakernel")
            return res.b, res.f
        res = disba.solve_lambda_bisect(svc, b_total, inner_iters=iters)
        # the dual solve is backend-independent; only the final f*(b)
        # evaluation goes through the selected intra backend
        f = res.f if intra_backend == "reference" else _freq(svc, res.b)
        return res.b, f

    return fn


class WarmDualState(NamedTuple):
    """Carry of the warm-started coop policy: the previous period's dual
    price plus a running count of cold-bisection rescues
    (``DisbaResult.fallback`` events -- non-finite inputs/seed/outputs).
    Fixed-shape, so it threads through ``lax.scan`` and checkpoints like the
    old scalar carry did."""

    lam: jax.Array        # () float32 dual price (WARM_COLD = no seed)
    fallbacks: jax.Array  # () int32 cumulative solver fallbacks


def fallback_count(pol_state) -> int:
    """Cumulative solver-fallback count carried in a policy state (0 for
    policies without one) -- the control plane mirrors this into its
    ``solver_fallbacks`` metric."""
    if isinstance(pol_state, WarmDualState):
        return int(pol_state.fallbacks)
    return 0


@register_stateful("coop")
def _coop_warm(*, intra_backend: str = "reference", iters: int = BISECT_ITERS,
               **_):
    """Warm-started cooperative DISBA: the previous period's dual price rides
    in the scan carry and seeds a safeguarded-Newton market clear
    (``disba.solve_lambda_newton_warm``), cutting the ~48 cold bisection
    trips to <= ``disba.WARM_ITERS`` fused demand evaluations.  With the
    ``pallas`` backend each dual iteration is one ``dual_demand`` kernel
    launch; with ``megakernel`` the WHOLE warm clear -- every trip plus the
    final demand/frequency evaluation -- is one ``market_clear`` launch."""
    _freq = freq_fn(intra_backend, iters)
    backend = (intra_backend if intra_backend in ("pallas", "megakernel")
               else "reference")

    def init_state(n: int):
        return WarmDualState(lam=jnp.float32(disba.WARM_COLD),
                             fallbacks=jnp.int32(0))

    def step(svc: ServiceSet, b_total, state):
        res = disba.solve_lambda_newton_warm(
            svc, b_total, state.lam, inner_iters=iters, backend=backend)
        # megakernel emits f from the same launch; reference's res.f is
        # already the reference evaluation.
        f = (res.f if intra_backend in ("reference", "megakernel")
             else _freq(svc, res.b))
        # Only carry the price out of periods that actually cleared a market;
        # an all-inactive period would otherwise poison the seed with 0.
        lam_next = jnp.where(jnp.any(svc.service_active()), res.lam, state.lam)
        state_next = WarmDualState(
            lam=lam_next,
            fallbacks=state.fallbacks
            + jnp.asarray(res.fallback, jnp.int32))
        return res.b, f, state_next

    return StatefulPolicy(init_state=init_state, step=step)


@register("selfish")
def _selfish(*, n_bids: int = 5, alpha_fair: float = 0.5,
             intra_backend: str = "reference", iters: int = BISECT_ITERS, **_):
    """Fairness-adjusted multi-bid auction with truthful uniform bids (§V.E)."""
    _freq = freq_fn(intra_backend, iters)
    # A kernel backend runs the bids' (N, M) mBDF grid on the mbdf kernel too.
    bid_backend = _intra_impl(intra_backend)

    def fn(svc: ServiceSet, b_total):
        bid = auction.uniform_truthful_bids(svc, n_bids, alpha_fair,
                                            iters=iters, backend=bid_backend)
        b, _ = auction.allocate(bid, b_total)
        return b, _freq(svc, b)

    return fn


@register("ec")
def _ec(**_):
    """Equal-Client benchmark: uniform per-client bandwidth, no intra solve."""

    def fn(svc: ServiceSet, b_total):
        return baselines.equal_client(svc, b_total)

    return fn


@register("es")
def _es(*, intra_backend: str = "reference", iters: int = BISECT_ITERS, **_):
    """Equal-Service benchmark: B / N_active each, optimal intra split."""
    _freq = freq_fn(intra_backend, iters)

    def fn(svc: ServiceSet, b_total):
        b, f = baselines.equal_service(svc, b_total)
        if intra_backend != "reference":
            f = _freq(svc, b)
        return b, f

    return fn


@register("pp")
def _pp(*, intra_backend: str = "reference", iters: int = BISECT_ITERS, **_):
    """Proportional benchmark: B * K_n / sum K, optimal intra split."""
    _freq = freq_fn(intra_backend, iters)

    def fn(svc: ServiceSet, b_total):
        b, f = baselines.proportional(svc, b_total)
        if intra_backend != "reference":
            f = _freq(svc, b)
        return b, f

    return fn
