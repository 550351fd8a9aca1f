"""Multi-period wireless-network simulator (paper §VI.D long-term setting).

Services arrive via a Poisson(p_arrive) process, live for a fixed number of
FL rounds (2000 in the paper), and exit on completion.  Each period the
active set is (re-)allocated bandwidth by the selected policy -- this periodic
re-solve is the paper's elasticity mechanism: arrivals/departures change the
allocation without disturbing the surviving services' state.

Engines
-------

``run_scan`` -- the production engine.  The episode state lives in a
*fixed-capacity* ServiceSet (capacity = ``n_services_total``); a service that
has not arrived yet or has already finished is an all-masked row
(``types.mask_inactive``), so arrivals/departures are mask flips, never shape
changes.  The entire multi-period loop is one ``jax.lax.scan`` whose body --
sample channels, flip activity masks, run the ``AllocationPolicy`` -- is
traced exactly once per (policy, shape) combination, no matter how many
periods or episodes run (see ``trace_count``).  ``run_batch`` vmaps the same
compiled episode over a batch of seeds for scenario sweeps: one compiled call
evaluates many network conditions.

``run_fleet`` -- the device-sharded, memory-bounded sweep engine for
Monte-Carlo fleets of 10k+ episodes per call.  The fleet's seed axis is
sharded over a one-axis device mesh (``launch.mesh.make_fleet_mesh`` /
``compat.flat_mesh``) with ``compat.shard_map_unchecked``; inside each
device the local batch is processed in fixed-size chunks by an outer
``lax.map`` whose body is the vmapped compiled episode, so the episode
working set is O(chunk), not O(fleet) -- at fleet sizes where one flat vmap
thrashes the cache (a (4096, N, K) solver working set is tens of MB per
array), the chunked sweep keeps every bisection trip L2-resident.  Episode
input buffers are donated at the jit boundary and the period-step carry is
reused in place by XLA; beyond the O(chunk) working set only the requested
outputs are allocated, so a ``collect_history=False`` sweep never
materializes any (S, T) array.  Every episode stays bitwise identical to its
own ``run_scan`` regardless of sharding/chunking, and the period step still
traces exactly once (``trace_count()``).  Fleet setup is O(1) dispatches:
arrivals and client counts for all seeds come from one compiled, vmapped
device-side draw (``_static_draws_batch``).

``run`` -- the legacy per-period Python loop, kept as the checkpointable
reference engine (plain-dict state survives crashes; exercised by
tests/test_fl_runtime.py).  It consumes the *same* per-period step math as
the scan engine, so the two produce identical durations on the same seed
(asserted in tests/test_policy_simulator.py).

``fl.cotrain`` builds the training-in-the-loop engines
(``run_cotrain_scan`` / ``_batch`` / ``_fleet``) on the same period step:
``_period_step`` returns the period's allocation record as ``extras``
(dead-code-eliminated by every duration-only engine), and the co-trained
episode consumes it to pace real FedAvg rounds -- with durations bitwise
identical to the engines here (tests/test_cotrain.py).

Policies: coop (DISBA), selfish (multi-bid auction), ec / es / pp benchmarks
-- all resolved through the string-keyed ``core.policy`` registry, including
the selectable intra-service backend (reference bisection or the Pallas
``bisect_alloc`` kernel).

Scenarios
---------

The stochastic environment is selected per axis through the
``repro.scenarios`` registries (see EXPERIMENTS.md "Scenario catalogue"):
``channel_process`` (i.i.d. redraw, Gauss-Markov shadowing, correlated
Rayleigh block fading), ``arrival_process`` (Poisson, periodic, batched,
bursty MMPP), and ``churn_process`` (none, Bernoulli, Gilbert client
dropout).  Channel and churn processes are stateful ``(key, state, svc) ->
(state, svc')`` transforms whose state rides in the scan carry, so every
scenario combination still compiles the period step exactly once.  Arrival
processes are device-side per-episode draws (see ``_draws``), batched over
the fleet's seed axis.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat, scenarios
from repro.core import network, policy as policy_mod
from repro.core.types import (ServiceSet, mask_clients, mask_inactive,
                              scale_uplink)
from repro.launch import mesh as mesh_lib

POLICIES = ("coop", "selfish", "ec", "es", "pp")

# Default per-device chunk of run_fleet: small enough that the period step's
# (chunk, N, K) solver working set stays cache-resident through the
# bisection/Newton trips, large enough to amortize the chunk loop.
FLEET_CHUNK = 64

# Incremented each time the per-period allocation step is *traced* (not run).
# The scan engine's acceptance bar is exactly one trace per episode shape --
# mask flips must never retrigger compilation.
_TRACE_COUNTS = {"allocation_step": 0}


def trace_count() -> int:
    return _TRACE_COUNTS["allocation_step"]


def reset_trace_count() -> None:
    _TRACE_COUNTS["allocation_step"] = 0


@dataclasses.dataclass
class SimConfig:
    policy: str = "coop"
    n_services_total: int = 10
    rounds_required: int = 2000
    p_arrive: float = 5.0              # mean arrival interval in periods
    mean_clients: float = 25.0
    var_clients: float = 15.0
    mean_channel_db: float = 85.0
    var_channel_db: float = 15.0
    n_bids: int = 5
    alpha_fair: float = 0.5
    max_periods: int = 4000
    seed: int = 0
    intra_backend: str = "reference"   # "reference" | "pallas" | "megakernel"
    k_max: int | None = None           # client-capacity pad; None -> derived
    # Warm-start the allocation across periods: policy solver state (e.g.
    # coop's dual price) rides in the scan carry and seeds the next period's
    # solve.  Off by default -- the cold path is pinned by the goldens.
    warm_start: bool = False
    # When False the scan emits no per-period stacked history -- only scalar
    # aggregates accumulated in the carry -- cutting HBM traffic and host
    # transfer for large run_batch sweeps.
    collect_history: bool = True
    # When True (requires collect_history) the history additionally stacks
    # the per-period allocation record itself -- b, f, active, rounds -- so a
    # replay exposes the full served-allocation stream.  This is the
    # reference side of the control plane's differential check
    # (fl.control_plane / tests/test_control_plane.py).
    collect_alloc: bool = False
    # Scenario processes: registry keys or scenarios.spec(name, **params).
    channel_process: str | scenarios.ScenarioSpec = "iid"
    arrival_process: str | scenarios.ScenarioSpec = "poisson"
    churn_process: str | scenarios.ScenarioSpec = "none"


def _default_net(cfg: SimConfig) -> network.NetworkConfig:
    return network.NetworkConfig(
        mean_clients=cfg.mean_clients, var_clients=cfg.var_clients,
        mean_pathloss_db=cfg.mean_channel_db, var_pathloss_db=cfg.var_channel_db,
    )


def _k_cap(cfg: SimConfig) -> int:
    """Seed-independent client-capacity pad: mean + 5 sigma (counts are
    clipped into it, so no silent truncation).  Deriving the pad from the
    config rather than the drawn counts keeps every engine -- run, run_scan,
    and any batch composition in run_batch -- on the same shapes, hence the
    same RNG draws and bitwise-identical per-seed results."""
    if cfg.k_max is not None:
        return cfg.k_max
    return int(np.ceil(cfg.mean_clients + 5.0 * np.sqrt(max(cfg.var_clients, 0.0))))


# Salt folded into the episode key to derive the episode-static draw stream
# (arrival periods + client counts).  Follows the scenarios.base salt
# convention: above every period number, distinct from the scenario-state
# salts, so the static draws never collide with per-period sampling.
_DRAW_SALT = (1 << 30) + 3

# Version tag of the episode-static draw stream, written into legacy-engine
# checkpoints: resuming re-derives arrivals/counts from cfg.seed, so a
# snapshot from a different stream (e.g. the pre-fleet host-NumPy draws)
# must be refused, not silently continued with different arrivals.
DRAW_STREAM = "device/v1"

_DRAW_STATICS = ("arrival", "n_total", "p_arrive", "mean_clients",
                 "var_clients", "k_min", "k_cap")


@functools.partial(jax.jit, static_argnames=_DRAW_STATICS)
def _draws(keys, *, arrival, n_total, p_arrive, mean_clients, var_clients,
           k_min, k_cap):
    """Episode-static randomness for a whole fleet in ONE compiled dispatch.

    Arrival periods come from the registered device-side ``arrival_process``
    sampler (default: cumulative exponential gaps, the paper's Poisson
    process); client counts are a clipped normal, fixed at arrival.  Both are
    drawn per episode key and vmapped over the fleet's seed axis, so setup
    cost is O(1) dispatches for any fleet size -- and because each row
    depends only on its own key, the batched draw is bitwise identical to
    per-seed draws (asserted in tests/test_fleet.py).
    """
    draw = scenarios.get_arrival(arrival)
    std = np.sqrt(max(var_clients, 1e-9))

    def one(key):
        k_arr, k_cnt = jax.random.split(jax.random.fold_in(key, _DRAW_SALT))
        arrivals = draw(k_arr, n_total, p_arrive).astype(jnp.int32)
        counts = jnp.clip(
            jnp.round(mean_clients
                      + std * jax.random.normal(k_cnt, (n_total,), jnp.float32)),
            k_min, k_cap).astype(jnp.int32)
        return arrivals, counts

    return jax.vmap(one)(keys)


def _episode_keys(seeds) -> jax.Array:
    """Per-episode PRNG keys -- the same stream run_scan/run_batch always fed
    the compiled episode; the static draws branch off it via ``_DRAW_SALT``."""
    return jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32) + 7)


def _draw_statics(cfg: SimConfig, net: network.NetworkConfig) -> dict:
    return dict(arrival=scenarios.as_spec(cfg.arrival_process, "poisson"),
                n_total=cfg.n_services_total, p_arrive=cfg.p_arrive,
                mean_clients=cfg.mean_clients, var_clients=cfg.var_clients,
                k_min=net.k_min, k_cap=_k_cap(cfg))


def _static_draws_batch(
    cfg: SimConfig, net: network.NetworkConfig, seeds,
) -> tuple[np.ndarray, np.ndarray]:
    """Batched episode-static draws: (S, N) arrivals + client counts."""
    arrivals, counts = _draws(_episode_keys(seeds), **_draw_statics(cfg, net))
    return np.asarray(arrivals), np.asarray(counts)


def _static_draws(cfg: SimConfig, net: network.NetworkConfig) -> tuple[np.ndarray, np.ndarray]:
    """Single-episode view of ``_static_draws_batch`` (the looped reference:
    calling this per seed is bitwise identical to one batched call)."""
    arrivals, counts = _static_draws_batch(cfg, net, [cfg.seed])
    return arrivals[0].astype(np.int64), counts[0].astype(np.int64)


# ---------------------------------------------------------------------------
# The shared per-period step (one trace serves every period of every episode).
# ---------------------------------------------------------------------------

def _period_step(rounds_done, duration, chan_state, churn_state, pol_state,
                 period, arrivals, counts, key, extra_avail=None,
                 ul_comp=None, *,
                 policy_fn, chan_step, churn_step, chan_rebuilds: bool, net,
                 n_total: int, k_max: int, rounds_required: int):
    """One period: evolve channels and churn, flip activity masks, allocate.

    All shapes are fixed at (n_total, k_max); activity and churn are pure
    masking, and the scenario processes *and* the policy solver (``pol_state``,
    e.g. the warm-start dual price) carry fixed-shape state, so the scan
    engine traces this exactly once per (episode shape, scenario) combo.

    Besides the carry and scalar ``stats`` it returns ``extras`` -- the
    period's full allocation record (the churn-masked ServiceSet, per-service
    bandwidth/frequency, activity mask, and the round counts *before* the
    rounds_required clamp).  ``extras`` is assembled purely from values the
    step already computed, so consuming it (the ``fl.cotrain`` co-simulation)
    or discarding it (every duration-only engine; dead-code-eliminated under
    jit) cannot move a single RNG draw or allocation result.

    ``extra_avail`` is an optional externally-supplied (n_total, k_max) bool
    availability mask applied on top of the churn process (the control
    plane's heartbeat-timeout drops).  ``None`` -- what every offline engine
    passes -- leaves the traced graph unchanged; an all-True mask is a
    bitwise no-op (masking an already-masked set is the identity), which is
    exactly what makes the live daemon's healthy-path stream replayable by
    ``run_scan``.

    ``ul_comp`` is an optional (n_total,) per-service uplink-compression
    multiplier (each service's ``fl.compression.compression_ratio``) applied
    to the dynamic s^UT column via ``types.scale_uplink`` *before* the
    policy runs -- so the allocator prices the compressed upload, round
    frequency rises, and the bandwidth split shifts.  This is the
    compression→allocation feedback edge of the co-simulation
    (``fl.cotrain``).  Like ``extra_avail``, the ``None`` default leaves the
    traced graph untouched, which is what keeps every duration engine and
    the committed goldens bitwise-pinned.
    """
    _TRACE_COUNTS["allocation_step"] += 1
    key_p = jax.random.fold_in(key, period)
    if chan_rebuilds:
        # The channel process reconstructs the ServiceSet itself (on this
        # same key, so non-channel draws match the i.i.d. path); hand it a
        # shape/mask-only shell instead of tracing a discarded base sample.
        mask = jnp.arange(k_max)[None, :] < counts[:, None]
        svc_full = ServiceSet(alpha=jnp.zeros(mask.shape, jnp.float32),
                              t_comp=jnp.zeros(mask.shape, jnp.float32),
                              mask=mask)
    else:
        svc_full, _ = network.sample_services(
            key_p, n_total, net, k_max=k_max, client_counts=counts,
        )
    chan_state, svc_full = chan_step(key_p, chan_state, svc_full)
    churn_state, svc_full = churn_step(key_p, churn_state, svc_full)
    if extra_avail is not None:
        svc_full = mask_clients(svc_full, extra_avail)
    if ul_comp is not None:
        svc_full = scale_uplink(svc_full, ul_comp)
    active = jnp.logical_and(arrivals <= period, rounds_done < rounds_required)
    svc = mask_inactive(svc_full, active)
    b, f, pol_state = policy_fn(svc, net.total_bandwidth_mhz, pol_state)
    # Integrity guard: a non-finite frequency (poisoned channel state under
    # fault injection) must not corrupt the integer rounds_done carry --
    # floor(NaN).astype(int32) is undefined.  Bitwise no-op on finite f.
    f_rounds = jnp.where(jnp.isfinite(f), f, 0.0)
    rounds = jnp.maximum(
        jnp.floor(f_rounds * jnp.float32(net.period_s)), 0.0
    ).astype(jnp.int32)
    rounds_done = jnp.minimum(
        rounds_done + jnp.where(active, rounds, 0), rounds_required
    )
    duration = duration + active.astype(jnp.int32)
    stats = {
        "freq_sum": jnp.sum(f),
        "objective": jnp.sum(jnp.log1p(f)),
        "n_active": jnp.sum(active.astype(jnp.int32)),
        "n_clients": jnp.sum(svc.mask.astype(jnp.int32)),
        "all_done": jnp.all(rounds_done >= rounds_required),
    }
    extras = {"svc": svc, "b": b, "f": f, "active": active, "rounds": rounds}
    return (rounds_done, duration, chan_state, churn_state, pol_state, stats,
            extras)


_EPISODE_STATICS = ("policy", "net", "n_total", "k_max", "rounds_required",
                    "max_periods", "n_bids", "alpha_fair", "intra_backend",
                    "warm_start", "collect_history", "collect_alloc",
                    "channel", "churn")

_AGG_KEYS = ("freq_sum", "objective", "n_active", "n_clients")


def _episode_impl(arrivals, counts, key, avail=None, *, policy, net, n_total,
                  k_max, rounds_required, max_periods, n_bids, alpha_fair,
                  intra_backend, warm_start, collect_history, collect_alloc,
                  channel, churn):
    pol = policy_mod.get_stateful_policy(
        policy, warm_start=warm_start, n_bids=n_bids, alpha_fair=alpha_fair,
        intra_backend=intra_backend,
    )
    chan_proc = scenarios.get_channel(channel, net)
    churn_proc = scenarios.get_churn(churn, net)

    def step(carry, xs):
        # ``avail`` (a recorded per-period availability stream, e.g. the
        # control plane's heartbeat masks) rides the scan xs next to the
        # period index; None -- every offline engine -- leaves the traced
        # graph exactly as before.
        period, extra_avail = xs if avail is not None else (xs, None)
        rounds_done, duration, chan_state, churn_state, pol_state, agg = carry
        (rounds_done, duration, chan_state, churn_state, pol_state,
         stats, extras) = _period_step(
            rounds_done, duration, chan_state, churn_state, pol_state, period,
            arrivals, counts, key, extra_avail,
            policy_fn=pol.step, chan_step=chan_proc.step,
            churn_step=churn_proc.step, chan_rebuilds=chan_proc.rebuilds,
            net=net, n_total=n_total, k_max=k_max,
            rounds_required=rounds_required,
        )
        carry = (rounds_done, duration, chan_state, churn_state, pol_state)
        if collect_history:
            if collect_alloc:
                stats = dict(stats, b=extras["b"], f=extras["f"],
                             active=extras["active"], rounds=extras["rounds"])
            return carry + ((),), stats
        # Aggregate-only mode: fold the per-period stats into the carry over
        # the first ``periods`` periods (up to and including the one where
        # every service finishes -- the same window _summarize slices).
        live = jnp.logical_not(agg["done"])
        agg = {
            "done": jnp.logical_or(agg["done"], stats["all_done"]),
            "periods": agg["periods"] + live.astype(jnp.int32),
            **{k: agg[k] + jnp.where(live, stats[k], 0).astype(agg[k].dtype)
               for k in _AGG_KEYS},
        }
        return carry + (agg,), None

    agg0 = () if collect_history else {
        "done": jnp.bool_(False), "periods": jnp.int32(0),
        "freq_sum": jnp.float32(0), "objective": jnp.float32(0),
        "n_active": jnp.int32(0), "n_clients": jnp.int32(0),
    }
    init = (jnp.zeros((n_total,), jnp.int32), jnp.zeros((n_total,), jnp.int32),
            chan_proc.init(key, n_total, k_max),
            churn_proc.init(key, n_total, k_max),
            pol.init_state(n_total), agg0)
    periods = jnp.arange(max_periods, dtype=jnp.int32)
    xs = periods if avail is None else (periods, avail)
    (rounds_done, duration, _, _, _, agg), hist = jax.lax.scan(step, init, xs)
    return rounds_done, duration, (hist if collect_history else agg)


_episode = functools.partial(jax.jit, static_argnames=_EPISODE_STATICS)(_episode_impl)


@functools.partial(jax.jit, static_argnames=_EPISODE_STATICS)
def _episode_batch(arrivals, counts, keys, *, policy, net, n_total, k_max,
                   rounds_required, max_periods, n_bids, alpha_fair,
                   intra_backend, warm_start, collect_history, collect_alloc,
                   channel, churn):
    """vmap of the episode over a leading seeds axis -- one compiled call
    evaluates a whole scenario sweep."""

    def one(a, c, k):
        return _episode_impl(
            a, c, k, policy=policy, net=net, n_total=n_total, k_max=k_max,
            rounds_required=rounds_required, max_periods=max_periods,
            n_bids=n_bids, alpha_fair=alpha_fair, intra_backend=intra_backend,
            warm_start=warm_start, collect_history=collect_history,
            collect_alloc=collect_alloc, channel=channel, churn=churn,
        )

    return jax.vmap(one)(arrivals, counts, keys)


def _summarize(cfg: SimConfig, rounds_done, duration, hist) -> dict:
    duration = np.asarray(duration)
    if not cfg.collect_history:
        agg = hist
        return {
            "avg_duration": float(np.mean(duration)),
            "std_duration": float(np.std(duration)),
            "durations": [int(d) for d in duration],
            "periods": int(agg["periods"]),
            "history": None,
            "totals": {k: float(agg[k]) for k in _AGG_KEYS},
            "finished": bool(
                np.all(np.asarray(rounds_done) >= cfg.rounds_required)),
        }
    done = np.asarray(hist["all_done"])
    periods = int(np.argmax(done)) + 1 if done.any() else cfg.max_periods
    return {
        "avg_duration": float(np.mean(duration)),
        "std_duration": float(np.std(duration)),
        "durations": [int(d) for d in duration],
        "periods": periods,
        # Every stacked series except the completion flag (with
        # collect_alloc that includes the b/f/active/rounds stream itself).
        "history": {k: np.asarray(v)[:periods] for k, v in hist.items()
                    if k != "all_done"},
        "finished": bool(np.all(np.asarray(rounds_done) >= cfg.rounds_required)),
    }


def _episode_statics(cfg: SimConfig, net: network.NetworkConfig,
                     k_max: int) -> dict:
    if cfg.collect_alloc and not cfg.collect_history:
        raise ValueError(
            "collect_alloc stacks the per-period allocation stream into the "
            "history, so it requires collect_history=True")
    return dict(
        policy=cfg.policy, net=net, n_total=cfg.n_services_total, k_max=k_max,
        rounds_required=cfg.rounds_required, max_periods=cfg.max_periods,
        n_bids=cfg.n_bids, alpha_fair=cfg.alpha_fair,
        intra_backend=cfg.intra_backend, warm_start=cfg.warm_start,
        collect_history=cfg.collect_history, collect_alloc=cfg.collect_alloc,
        channel=scenarios.as_spec(cfg.channel_process, "iid"),
        churn=scenarios.as_spec(cfg.churn_process, "none"),
    )


def run_scan(cfg: SimConfig, net: network.NetworkConfig | None = None, *,
             arrivals=None, counts=None, avail=None) -> dict:
    """Simulate one episode as a single compiled ``lax.scan``.

    Returns the same summary keys as ``run`` (avg_duration, durations,
    periods, finished) with the per-period history as stacked arrays.

    ``arrivals``/``counts`` optionally replace the episode-static draws with
    an explicit (n_services_total,) admission trace -- per-slot arrival
    period and enrolled-client count.  This is how the control plane's
    differential check replays a *live* admission stream through the offline
    reference engine: everything else (channel/churn draws, policy state)
    still comes from ``cfg.seed``'s episode key, so a daemon run on the same
    seed must match bitwise (tests/test_control_plane.py).

    ``avail`` optionally adds a recorded per-period client-availability
    stream, a ``(max_periods, n_services_total, k_max)`` bool tensor applied
    on top of the churn process each period (``_period_step``'s
    ``extra_avail`` hook).  The control plane records its heartbeat-timeout
    masks and feeds them back here, so even a heartbeat-masked live episode
    replays bitwise.  All-True planes are a bitwise no-op.
    """
    net = net or _default_net(cfg)
    if (arrivals is None) != (counts is None):
        raise ValueError("pass arrivals and counts together (or neither)")
    if arrivals is None:
        arrivals, counts = _static_draws(cfg, net)
    k_max = _k_cap(cfg)
    if avail is not None:
        avail = jnp.asarray(avail, bool)
        want = (cfg.max_periods, cfg.n_services_total, k_max)
        if avail.shape != want:
            raise ValueError(
                f"avail must have shape (max_periods, n_services_total, "
                f"k_max) = {want}, got {avail.shape}")
    rounds_done, duration, hist = _episode(
        jnp.asarray(arrivals, jnp.int32), jnp.asarray(counts, jnp.int32),
        jax.random.key(cfg.seed + 7), avail,
        **_episode_statics(cfg, net, k_max),
    )
    return _summarize(cfg, rounds_done, duration, hist)


def _summarize_batch(cfg: SimConfig, seeds, rounds_done, duration, hist) -> dict:
    """Per-seed stacked summary shared by ``run_batch`` and ``run_fleet``."""
    duration = np.asarray(duration)
    finished = np.all(np.asarray(rounds_done) >= cfg.rounds_required, axis=1)
    out = {
        "seeds": list(seeds),
        "avg_duration": duration.mean(axis=1),
        "std_duration": duration.std(axis=1),
        "durations": duration,
        "finished": finished,
    }
    if cfg.collect_history:
        out["history"] = {k: np.asarray(v) for k, v in hist.items()}
    else:
        # hist is the per-seed aggregate carry: scalar reductions only, no
        # (S, T) stacked arrays ever leave the device.
        out["history"] = None
        out["periods"] = np.asarray(hist["periods"])
        out["totals"] = {k: np.asarray(hist[k]) for k in _AGG_KEYS}
    return out


def run_batch(cfg: SimConfig, seeds, net: network.NetworkConfig | None = None) -> dict:
    """Scenario sweep: the compiled episode vmapped over ``seeds``.

    Every engine pads clients to the same config-derived ``k_max``
    (``_k_cap``), so the sweep is a single compiled call AND each episode is
    bitwise identical to its own ``run_scan``/``run`` regardless of which
    other seeds share the batch.  Returns per-seed summaries stacked:
    avg_duration (S,), durations (S, N), ...
    """
    net = net or _default_net(cfg)
    seeds = list(seeds)
    if not seeds:
        raise ValueError("run_batch needs at least one seed")
    keys = _episode_keys(seeds)
    arrivals, counts = _draws(keys, **_draw_statics(cfg, net))
    rounds_done, duration, hist = _episode_batch(
        arrivals, counts, keys, **_episode_statics(cfg, net, _k_cap(cfg)),
    )
    return _summarize_batch(cfg, seeds, rounds_done, duration, hist)


# ---------------------------------------------------------------------------
# Fleet engine: device-sharded, memory-bounded episode sweeps.
# ---------------------------------------------------------------------------

def _fleet_shape(n_seeds: int, n_dev: int, chunk_size: int | None) -> tuple[int, int, int]:
    """(chunk, n_chunks, padded fleet size): seeds are padded up to
    n_dev * n_chunks * chunk so every device runs the same chunk grid (the
    pad rows are dropped before summarizing)."""
    per_dev = -(-n_seeds // n_dev)
    chunk = max(1, min(chunk_size or FLEET_CHUNK, per_dev))
    n_chunks = -(-per_dev // chunk)
    return chunk, n_chunks, n_dev * n_chunks * chunk


def sharded_chunked_fn(mesh, axis: str, n_chunks: int, chunk: int, episode):
    """Build the compiled fleet sweep for an arbitrary per-episode function:
    shard_map over the seed axis of an outer ``lax.map`` over chunks of the
    vmapped episode.  ``episode(arrivals, counts, key_data) -> pytree`` takes
    one seed's inputs (keys as raw uint32 key data, which shard like any
    other array).

    Shared by the duration engine's ``run_fleet`` and the co-training
    engine's ``fl.cotrain.run_cotrain_fleet``; callers lru_cache the result
    per (mesh, chunk grid, episode statics) so the period step still traces
    exactly once per combination no matter how many fleet calls run.  Input
    buffers (arrivals, counts) are donated -- together with XLA's in-place
    reuse of the scan carry this keeps peak memory at O(chunk) episode state
    plus the requested outputs.
    """

    def device_fn(arrivals, counts, key_data):
        def chunk_fn(args):
            return jax.vmap(episode)(*args)

        def to_chunks(x):
            return x.reshape((n_chunks, chunk) + x.shape[1:])

        out = jax.lax.map(
            chunk_fn, (to_chunks(arrivals), to_chunks(counts),
                       to_chunks(key_data)))
        return jax.tree_util.tree_map(
            lambda x: x.reshape((n_chunks * chunk,) + x.shape[2:]), out)

    spec = P(axis)
    fn = compat.shard_map_unchecked(
        device_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    # Keys are excluded from donation: no uint32 output ever reuses them, so
    # donating would only emit a "not usable" warning per call.
    return jax.jit(fn, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _fleet_fn(mesh, axis: str, n_chunks: int, chunk: int, statics_items):
    """Compiled duration-engine fleet sweep (see ``sharded_chunked_fn``);
    the lru_cache plays the role of jit's cache for the mesh/chunk-grid +
    episode statics."""
    statics = dict(statics_items)

    def episode(arrivals, counts, key_data):
        return _episode_impl(arrivals, counts,
                             jax.random.wrap_key_data(key_data), **statics)

    return sharded_chunked_fn(mesh, axis, n_chunks, chunk, episode)


def fleet_geometry(seeds, mesh, chunk_size: int | None):
    """Normalize a fleet request: validate the mesh (one axis), derive the
    chunk grid, and pad the seed list with repeats of its last element so
    every device runs the same grid.  Returns
    ``(mesh, axis, n_dev, chunk, n_chunks, padded_seeds)``; callers slice
    the pad rows off on device before summarizing."""
    seeds = [int(s) for s in seeds]
    if not seeds:
        raise ValueError("fleet sweeps need at least one seed")
    if mesh is None:
        mesh = mesh_lib.make_fleet_mesh()
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"fleet sweeps shard over a one-axis mesh, got axes "
            f"{mesh.axis_names}")
    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    chunk, n_chunks, padded_to = _fleet_shape(len(seeds), n_dev, chunk_size)
    padded = seeds + [seeds[-1]] * (padded_to - len(seeds))
    return mesh, axis, n_dev, chunk, n_chunks, padded


def run_fleet(cfg: SimConfig, seeds, net: network.NetworkConfig | None = None,
              *, mesh=None, chunk_size: int | None = None) -> dict:
    """Device-sharded, memory-bounded Monte-Carlo sweep over ``seeds``.

    The fleet's seed axis is split across a one-axis device mesh (default:
    ``launch.mesh.make_fleet_mesh()`` over every visible device), and each
    device walks its local batch in chunks of ``chunk_size`` episodes
    (default ``FLEET_CHUNK``) via an outer ``lax.map``, so the episode
    *working set* (solver intermediates, scan carry) is O(chunk) regardless
    of fleet size -- 10k+ episodes per call.  What remains O(fleet) is only
    the requested output: with ``collect_history=True`` that includes the
    (S, T) history arrays themselves; ``collect_history=False`` sweeps
    return per-seed scalars only and never materialize any (S, T) array.

    Invariants (tests/test_fleet.py): per-seed outputs are bitwise identical
    to ``run_batch``/``run_scan`` under every mesh size, chunk size, and
    fleet-size remainder, and the per-period allocation step traces exactly
    once.  Returns the ``run_batch`` summary dict plus a ``"fleet"`` record
    of the sweep geometry.
    """
    net = net or _default_net(cfg)
    seeds = [int(s) for s in seeds]
    mesh, axis, n_dev, chunk, n_chunks, padded = fleet_geometry(
        seeds, mesh, chunk_size)
    n_seeds = len(seeds)
    # Padded with repeats of the last seed: identical shapes on every device;
    # the pad episodes' outputs are sliced off (on device) before transfer.
    keys = _episode_keys(padded)
    arrivals, counts = _draws(keys, **_draw_statics(cfg, net))
    statics = _episode_statics(cfg, net, _k_cap(cfg))
    fn = _fleet_fn(mesh, axis, n_chunks, chunk, tuple(statics.items()))
    sharded = fn(arrivals, counts, jax.random.key_data(keys))
    # The devices that actually hold the sweep's output shards.
    device_ids = sorted(d.id for d in sharded[1].sharding.device_set)
    rounds_done, duration, hist = jax.tree_util.tree_map(
        lambda x: x[:n_seeds], sharded)
    out = _summarize_batch(cfg, seeds, rounds_done, duration, hist)
    out["fleet"] = {"n_devices": n_dev, "mesh_axis": axis, "chunk": chunk,
                    "n_chunks": n_chunks, "padded_to": len(padded),
                    "device_ids": device_ids}
    return out


# ---------------------------------------------------------------------------
# Legacy checkpointable engine (reference semantics for the scan engine).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _legacy_step_jit(policy, n_bids, alpha_fair, intra_backend, warm_start,
                     net, n_total, k_max, rounds_required, channel, churn):
    """Jitted period step + scenario processes, cached across ``run`` calls
    (per static shape / scenario spec) so per-seed sweeps / resumes reuse one
    compilation."""
    pol = policy_mod.get_stateful_policy(
        policy, warm_start=warm_start, n_bids=n_bids, alpha_fair=alpha_fair,
        intra_backend=intra_backend,
    )
    chan_proc = scenarios.get_channel(channel, net)
    churn_proc = scenarios.get_churn(churn, net)
    bound = functools.partial(
        _period_step, policy_fn=pol.step, chan_step=chan_proc.step,
        churn_step=churn_proc.step, chan_rebuilds=chan_proc.rebuilds, net=net,
        n_total=n_total, k_max=k_max, rounds_required=rounds_required,
    )

    def _drop_extras(*args):
        # The legacy loop only consumes the carry + stats; dropping the
        # allocation extras inside the jit boundary lets XLA dead-code
        # eliminate them instead of transferring a ServiceSet every period.
        *out, _ = bound(*args)
        return tuple(out)

    return jax.jit(_drop_extras), chan_proc, churn_proc, pol


def _scenario_state_to_json(state) -> list:
    """Flatten a scenario-state pytree to JSON-serializable nested lists."""
    return [np.asarray(leaf).tolist() for leaf in jax.tree_util.tree_leaves(state)]


def _scenario_state_from_json(template, data: list):
    """Rebuild scenario state from ``_scenario_state_to_json`` output, using
    a freshly-initialized ``template`` for tree structure, dtypes, shapes."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    if len(data) != len(leaves):
        raise ValueError(
            f"checkpointed scenario state has {len(data)} leaves, the "
            f"configured processes expect {len(leaves)} -- was the checkpoint "
            f"written under a different scenario?")
    restored = [
        jnp.asarray(np.asarray(d).reshape(np.asarray(leaf).shape),
                    dtype=leaf.dtype)
        for d, leaf in zip(data, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, restored)


def run(cfg: SimConfig, net: network.NetworkConfig | None = None,
        state: dict | None = None, checkpoint_path: str | None = None) -> dict:
    """Per-period Python loop until every service finishes.

    Runs the same fixed-capacity period step as ``run_scan`` (so durations
    match the compiled engine exactly on the same seed) but keeps plain-dict
    state: ``state`` resumes a previous partial run and ``checkpoint_path``
    writes a JSON snapshot each period, so long runs restart after a crash.
    """
    net = net or _default_net(cfg)
    arrivals, counts = _static_draws(cfg, net)
    k_max = _k_cap(cfg)

    if state is None:
        state = {
            "period": 0,
            "rounds_done": [0] * cfg.n_services_total,
            "duration": [0] * cfg.n_services_total,
            "history": [],
            "draw_stream": DRAW_STREAM,
        }
    elif state["period"] > 0 and state.get("draw_stream") != DRAW_STREAM:
        # Arrivals/counts are re-derived from cfg.seed on resume, so a
        # snapshot written under a different episode-static draw stream
        # (e.g. the pre-fleet host-NumPy stream) would silently continue
        # with different arrival periods than the ones that produced its
        # rounds_done/duration.  Refuse instead.
        raise ValueError(
            f"resume state was written under draw stream "
            f"{state.get('draw_stream')!r}, this engine draws "
            f"{DRAW_STREAM!r} -- the checkpoint's arrivals cannot be "
            f"reconstructed; restart the episode")

    period = state["period"]
    rounds_done = list(state["rounds_done"])
    duration = list(state["duration"])
    history = list(state["history"])

    step_jit, chan_proc, churn_proc, pol = _legacy_step_jit(
        cfg.policy, cfg.n_bids, cfg.alpha_fair, cfg.intra_backend,
        cfg.warm_start, net,
        cfg.n_services_total, k_max, cfg.rounds_required,
        scenarios.as_spec(cfg.channel_process, "iid"),
        scenarios.as_spec(cfg.churn_process, "none"),
    )
    key = jax.random.key(cfg.seed + 7)
    arrivals_j = jnp.asarray(arrivals, jnp.int32)
    counts_j = jnp.asarray(counts, jnp.int32)

    # Scenario state: same init draws as the scan engine (episode key), then
    # restored from the snapshot when resuming mid-episode.
    def _restore_scenario_state(name: str, template):
        if name in state:
            return _scenario_state_from_json(template, state[name])
        if period > 0 and jax.tree_util.tree_leaves(template):
            raise ValueError(
                f"resume state has no {name!r} but the configured scenario/"
                f"policy processes are stateful -- was the snapshot written "
                f"under a different configuration?")
        return template

    chan_state = _restore_scenario_state(
        "chan_state", chan_proc.init(key, cfg.n_services_total, k_max))
    churn_state = _restore_scenario_state(
        "churn_state", churn_proc.init(key, cfg.n_services_total, k_max))
    pol_state = _restore_scenario_state(
        "pol_state", pol.init_state(cfg.n_services_total))

    def _snapshot() -> dict:
        return {"period": period, "rounds_done": rounds_done,
                "duration": duration, "history": history,
                "draw_stream": DRAW_STREAM,
                "chan_state": _scenario_state_to_json(chan_state),
                "churn_state": _scenario_state_to_json(churn_state),
                "pol_state": _scenario_state_to_json(pol_state)}

    # With stateful scenario processes (or warm-started policy state) the
    # step must run every period -- even with no active service -- so the
    # state trajectory matches the scan engine's period-per-step carry
    # exactly.  Stateless processes (the defaults) keep the cheap skip of
    # inactive periods.
    stateless = not jax.tree_util.tree_leaves(
        (chan_state, churn_state, pol_state))

    while period < cfg.max_periods:
        if all(r >= cfg.rounds_required for r in rounds_done):
            break
        active = [
            i for i in range(cfg.n_services_total)
            if arrivals[i] <= period and rounds_done[i] < cfg.rounds_required
        ]
        if active or not stateless:
            rd, du, chan_state, churn_state, pol_state, stats = step_jit(
                jnp.asarray(rounds_done, jnp.int32),
                jnp.asarray(duration, jnp.int32),
                chan_state, churn_state, pol_state,
                jnp.int32(period), arrivals_j, counts_j, key,
            )
            rounds_done = [int(r) for r in np.asarray(rd)]
            duration = [int(d) for d in np.asarray(du)]
            if active:
                history.append({
                    "period": period,
                    "active": active,
                    "freq_sum": float(stats["freq_sum"]),
                    "objective": float(stats["objective"]),
                    "n_clients": int(stats["n_clients"]),
                })
        period += 1
        if checkpoint_path is not None:
            snap = _snapshot()
            tmp = checkpoint_path + ".tmp"
            with open(tmp, "w") as fp:
                json.dump(snap, fp)
            os.replace(tmp, checkpoint_path)

    out = {
        "avg_duration": float(np.mean(duration)),
        "std_duration": float(np.std(duration)),
        "durations": duration,
        "periods": period,
        "history": history,
        "finished": all(r >= cfg.rounds_required for r in rounds_done),
        "state": _snapshot(),
    }
    if not cfg.collect_history:
        # Same summary shape as run_scan's aggregate mode.  The snapshot
        # keeps the full per-period list (resumes need it); only the
        # returned summary collapses to totals.  Skipped inactive periods
        # contribute exactly zero to every total, matching the scan carry.
        out["history"] = None
        out["totals"] = {
            "freq_sum": float(sum(h["freq_sum"] for h in history)),
            "objective": float(sum(h["objective"] for h in history)),
            "n_active": float(sum(len(h["active"]) for h in history)),
            "n_clients": float(sum(h["n_clients"] for h in history)),
        }
    return out
