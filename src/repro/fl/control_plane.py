"""Live allocation control plane: online service admission over the
warm-started market-clearing step.

The offline engines (``fl.simulator``) evaluate a *recorded* episode; this
module is the serving side of the same period step: FL services arrive and
depart while the network provider keeps clearing the market (paper §III/§V),
so the allocator runs as a long-lived daemon that

* **admits / retires services online** into free slots of one fixed-capacity
  mask-padded ServiceSet -- an admission is a mask flip plus two array
  writes, never a shape change, so the compiled step traces once for the
  daemon's whole lifetime;
* **holds warm policy state** (``StatefulPolicy`` carry, e.g. coop's dual
  price) across requests, so steady-state decisions reuse the <= 6-trip
  safeguarded-Newton clear instead of the 48-trip cold bisection;
* **drives per-client churn from heartbeats**: a client whose last heartbeat
  is older than ``heartbeat_timeout_periods`` is dropped from the next
  period's clear (CFLMEC-style liveness, mapped onto the
  ``scenarios.churn`` mask conventions via ``types.mask_clients``);
* **checkpoints and auto-resumes**: the full serving state is a fixed-shape
  pytree written through ``CheckpointManager``'s COMMIT protocol;
  ``run_resumable`` drives scripted serving through
  ``distributed.fault.resumable_loop`` so a crashed daemon replays nothing
  and loses at most ``save_every - 1`` periods.

Differential contract (tests/test_control_plane.py): a daemon that never
serves a stale decision produces an allocation stream **bitwise equal** to
``simulator.run_scan(collect_alloc=True)`` fed the same admission trace
(explicit ``arrivals``/``counts``) on the same seed.  Three facts make that
hold: the per-period math IS ``simulator._period_step`` (one shared
implementation), the all-healthy heartbeat mask is a bitwise no-op
(re-masking an already-masked set is the identity), and inactive slots are
invisible to every mask-aware solver -- so the placeholder client counts of
not-yet-admitted slots cannot perturb a single bit of the active rows.

The asyncio front end (request queue, solver-timeout degradation, the
``stale_decisions`` metric) lives in ``repro.launch.allocd``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import scenarios
from repro.checkpoint import CheckpointManager
from repro.core import network, policy as policy_mod
from repro.distributed import fault
from repro.fl import simulator

# Arrival sentinel for a slot no service has been admitted into: period
# numbers stay far below int32 max, so ``arrivals <= period`` is False
# forever.  The replay feeds run_scan the very same sentinel.
NEVER = np.int32(np.iinfo(np.int32).max)


@dataclasses.dataclass(frozen=True)
class ControlPlaneConfig:
    """Static configuration of one allocation daemon.

    Mirrors the ``SimConfig`` fields that select the compiled period step --
    ``replay_sim_config`` maps one onto the other for the differential
    check.  ``capacity`` is the fixed slot count (admissions beyond it are
    rejected, never silently queued into a retrace); ``k_max`` the per-slot
    client pad."""

    capacity: int = 16
    k_max: int = 32
    policy: str = "coop"
    warm_start: bool = True
    rounds_required: int = 2000
    seed: int = 0
    n_bids: int = 5
    alpha_fair: float = 0.5
    intra_backend: str = "reference"
    channel_process: str | scenarios.ScenarioSpec = "iid"
    churn_process: str | scenarios.ScenarioSpec = "none"
    # A client whose last heartbeat is more than this many periods old is
    # dropped from the next clear.  None disables liveness tracking (every
    # enrolled client stays up) -- the replayable configuration.
    heartbeat_timeout_periods: int | None = None


class Decision(NamedTuple):
    """One served per-period allocation over the fixed-capacity slots."""

    period: int
    b: np.ndarray          # (capacity,) MHz
    f: np.ndarray          # (capacity,) rounds/s
    active: np.ndarray     # (capacity,) bool
    stale: bool            # True: previous clear rescaled, not a fresh solve
    # True: the O(1) equal-share emergency policy (stale-streak overflow or
    # a non-finite solver output), flagged distinctly from plain staleness.
    degraded: bool = False


@functools.lru_cache(maxsize=None)
def _serve_step_jit(policy, n_bids, alpha_fair, intra_backend, warm_start,
                    net, n_total, k_max, rounds_required, channel, churn):
    """The daemon's compiled period step: ``simulator._period_step`` bound to
    the same statics the offline engines use, keeping the allocation record
    (b/f/active/rounds) and dropping only the per-period ServiceSet.  Cached
    per configuration so restarts and tests reuse one compilation."""
    pol = policy_mod.get_stateful_policy(
        policy, warm_start=warm_start, n_bids=n_bids, alpha_fair=alpha_fair,
        intra_backend=intra_backend,
    )
    chan_proc = scenarios.get_channel(channel, net)
    churn_proc = scenarios.get_churn(churn, net)
    bound = functools.partial(
        simulator._period_step, policy_fn=pol.step, chan_step=chan_proc.step,
        churn_step=churn_proc.step, chan_rebuilds=chan_proc.rebuilds, net=net,
        n_total=n_total, k_max=k_max, rounds_required=rounds_required,
    )

    def step(rounds_done, duration, chan_state, churn_state, pol_state,
             period, arrivals, counts, key, hb_avail):
        (rounds_done, duration, chan_state, churn_state, pol_state, stats,
         extras) = bound(rounds_done, duration, chan_state, churn_state,
                         pol_state, period, arrivals, counts, key, hb_avail)
        return (rounds_done, duration, chan_state, churn_state, pol_state,
                stats, extras["b"], extras["f"], extras["active"])

    return jax.jit(step), chan_proc, churn_proc, pol


@dataclasses.dataclass
class _SlotRecord:
    service_id: Any
    slot: int
    n_clients: int
    admitted_period: int
    retired_period: int | None = None


class ControlPlane:
    """Synchronous serving core: slot registry + compiled step + state.

    All state transitions happen in ``tick`` (one period each); the asyncio
    daemon in ``launch.allocd`` layers batched request draining, heartbeat
    wall-clock mapping, and solver-timeout degradation on top.
    """

    def __init__(self, cfg: ControlPlaneConfig,
                 net: network.NetworkConfig | None = None):
        if cfg.capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.cfg = cfg
        self.net = net or network.NetworkConfig()
        self._step, chan_proc, churn_proc, pol = _serve_step_jit(
            cfg.policy, cfg.n_bids, cfg.alpha_fair, cfg.intra_backend,
            cfg.warm_start, self.net, cfg.capacity, cfg.k_max,
            cfg.rounds_required,
            scenarios.as_spec(cfg.channel_process, "iid"),
            scenarios.as_spec(cfg.churn_process, "none"),
        )
        # The episode key run_scan would use on the same seed -- re-derived,
        # never checkpointed (typed keys don't round-trip through npz).
        self._key = jax.random.key(cfg.seed + 7)
        n, k = cfg.capacity, cfg.k_max
        self._arrivals = np.full((n,), NEVER, np.int32)
        self._counts = np.zeros((n,), np.int32)
        self._last_seen = np.zeros((n, k), np.int32)
        self._period = 0
        self._carry = (jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
                       chan_proc.init(self._key, n, k),
                       churn_proc.init(self._key, n, k),
                       pol.init_state(n))
        self._rounds_done = np.zeros((n,), np.int32)
        self._last_alloc: tuple[np.ndarray, np.ndarray] | None = None
        self.services: dict[Any, _SlotRecord] = {}
        self.retired: list[_SlotRecord] = []
        self._free = list(range(n))
        self.replayable = True      # falsified by slot reuse / forced retire
        self.unreplayable_reasons: list[str] = []
        # period -> [[slot, client], ...] heartbeat-timeout drops, recorded
        # so a masked episode still replays bitwise (run_scan's ``avail``).
        self._hb_drops: dict[int, list[list[int]]] = {}
        self.metrics = {
            "decisions": 0, "stale_decisions": 0, "admitted": 0,
            "retired": 0, "rejected": 0, "heartbeat_drops": 0,
            # Robustness counters (PR 8): none of these ever move on a
            # healthy run -- each marks a counted, never-silent degradation.
            "solver_fallbacks": 0, "nonfinite_decisions": 0,
            "degraded_decisions": 0, "carry_repairs": 0,
            "checkpoint_skips": 0, "admit_retries": 0,
        }
        self.decisions: list[Decision] = []

    def _mark_unreplayable(self, reason: str) -> None:
        self.replayable = False
        if reason not in self.unreplayable_reasons:
            self.unreplayable_reasons.append(reason)

    # -- admission / retirement -------------------------------------------

    @property
    def period(self) -> int:
        """Periods cleared so far (the next tick's period index)."""
        return self._period

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def admit(self, service_id, n_clients: int) -> int:
        """Admit a service into the lowest free slot, active from the period
        of the *next* ``tick``.  Raises when full or on a duplicate id --
        the daemon maps that onto an explicit rejection, never a silent
        drop."""
        if service_id in self.services:
            raise ValueError(f"service {service_id!r} already admitted")
        if not 1 <= n_clients <= self.cfg.k_max:
            raise ValueError(
                f"n_clients must be in [1, {self.cfg.k_max}], got {n_clients}")
        if not self._free:
            self.metrics["rejected"] += 1
            raise RuntimeError(
                f"all {self.cfg.capacity} slots occupied; retire a service "
                f"or grow capacity")
        # Prefer a never-used slot: reusing a freed one makes the episode
        # inexpressible as a single run_scan (arrival, count) trace.
        free = np.asarray(self._free)
        virgin = free[self._arrivals[free] == NEVER]
        slot = int(virgin.min() if virgin.size else free.min())
        self._free.remove(slot)
        if self._arrivals[slot] != NEVER:
            self._mark_unreplayable("slot reuse")
        self._arrivals[slot] = self._period
        self._counts[slot] = n_clients
        self._last_seen[slot, :] = self._period
        self.services[service_id] = _SlotRecord(
            service_id, slot, n_clients, self._period)
        self.metrics["admitted"] += 1
        return slot

    def retire(self, service_id) -> None:
        """Forced (client-requested) retirement: the slot goes inactive from
        the next period and returns to the free list.  Completion-based
        departures need no request -- ``tick`` detects them."""
        rec = self.services.pop(service_id, None)
        if rec is None:
            raise KeyError(f"unknown service {service_id!r}")
        self._arrivals[rec.slot] = NEVER
        self._free.append(rec.slot)
        rec.retired_period = self._period
        self.retired.append(rec)
        self.metrics["retired"] += 1
        self._mark_unreplayable("forced retire")
        self._counts[rec.slot] = 0

    # -- heartbeats --------------------------------------------------------

    def heartbeat(self, service_id, client: int | None = None) -> None:
        """Record liveness for one client (or the whole cohort) of a
        service, stamped at the current period."""
        rec = self.services.get(service_id)
        if rec is None:
            raise KeyError(f"unknown service {service_id!r}")
        if client is None:
            self._last_seen[rec.slot, :] = self._period
        else:
            if not 0 <= client < rec.n_clients:
                raise ValueError(
                    f"client {client} out of range for service "
                    f"{service_id!r} ({rec.n_clients} clients)")
            self._last_seen[rec.slot, client] = self._period
        return None

    def _heartbeat_mask(self) -> np.ndarray:
        """(capacity, k_max) availability from heartbeat ages.  All-True when
        liveness tracking is off -- a bitwise no-op inside the step.

        Only drops of *live, enrolled* clients can perturb the clear:
        inactive rows are zeroed whole by the activity rule and columns
        ``k >= counts`` by the base client mask, so everything else is forced
        True.  That keeps the mask's non-identity entries sparse, and they
        are recorded per period in ``_hb_drops`` -- ``replay_reference``
        feeds them back through ``run_scan(avail=...)``, so a
        heartbeat-masked episode still replays bitwise."""
        timeout = self.cfg.heartbeat_timeout_periods
        if timeout is None:
            return np.ones((self.cfg.capacity, self.cfg.k_max), bool)
        avail = (self._period - self._last_seen) <= timeout
        live = np.zeros((self.cfg.capacity, 1), bool)
        for rec in list(self.services.values()):
            live[rec.slot, 0] = True
        enrolled = np.arange(self.cfg.k_max)[None, :] < self._counts[:, None]
        eff = np.where(live & enrolled, avail, True)
        dropped = int(np.sum(~eff))
        self.metrics["heartbeat_drops"] += dropped
        if dropped:
            slots, clients = np.nonzero(~eff)
            self._hb_drops[self._period] = [
                [int(s), int(c)] for s, c in zip(slots, clients)]
        return eff

    # -- the period step ---------------------------------------------------

    def tick(self) -> Decision:
        """Run one period: heartbeat-derived churn, the compiled clear,
        completion-based retirement, trace bookkeeping.

        Hardened (chaos-tested): a non-finite solver output is never served
        -- the period degrades to the O(1) equal-share decision, counted in
        ``nonfinite_decisions``; any non-finite values left in the carry are
        healed afterwards (``carry_repairs``) so one poisoned period cannot
        cascade; warm-solver cold-bisection rescues are mirrored from the
        policy carry into ``solver_fallbacks``.  Each of those also falsifies
        ``replayable`` -- an injected fault is not part of the recorded
        trace, so the offline replay could no longer match.
        """
        period = self._period
        hb = self._heartbeat_mask()
        out = self._step(
            *self._carry, jnp.int32(period),
            jnp.asarray(self._arrivals), jnp.asarray(self._counts),
            self._key, jnp.asarray(hb),
        )
        self._carry = out[:5]
        b, f, active = (np.asarray(out[6]), np.asarray(out[7]),
                        np.asarray(out[8]))
        self._rounds_done = np.asarray(out[0])
        self._period = period + 1
        self._retire_finished()
        fallbacks = policy_mod.fallback_count(self._carry[4])
        if fallbacks > self.metrics["solver_fallbacks"]:
            self.metrics["solver_fallbacks"] = fallbacks
            self._mark_unreplayable("solver fallback (non-finite inputs)")
        if self._repair_carry():
            self._mark_unreplayable("carry repaired after non-finite values")
        if not (np.all(np.isfinite(b)) and np.all(np.isfinite(f))):
            self.metrics["nonfinite_decisions"] += 1
            self.metrics["degraded_decisions"] += 1
            self._mark_unreplayable("non-finite solver output")
            decision = self._equal_share(period, stale=False)
        else:
            decision = Decision(period=period, b=b, f=f, active=active,
                                stale=False)
        self.metrics["decisions"] += 1
        self.decisions.append(decision)
        return decision

    def compiled_step_text(self) -> str:
        """HLO text of the compiled period step that ``tick`` runs.  Its
        shapes are fixed by the config, so any state gives the same program;
        a kernel backend shows up as ``tpu_custom_call`` on a TPU."""
        n, k = self.cfg.capacity, self.cfg.k_max
        return self._step.lower(
            *self._carry, jnp.int32(self._period),
            jnp.asarray(self._arrivals), jnp.asarray(self._counts),
            self._key, jnp.ones((n, k), bool),
        ).compile().as_text()

    def _repair_carry(self) -> int:
        """Replace non-finite float entries anywhere in the serving carry
        with 0 (for the warm dual price, 0 means "cold seed next period").
        Returns the number of entries healed, mirrored into
        ``metrics['carry_repairs']`` -- 0 on every healthy tick."""
        leaves, treedef = jax.tree.flatten(self._carry)
        healed = 0
        out = []
        for leaf in leaves:
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.floating):
                bad = ~np.isfinite(arr)
                n_bad = int(bad.sum())
                if n_bad:
                    healed += n_bad
                    arr = np.where(bad, np.zeros_like(arr), arr)
                    leaf = jnp.asarray(arr)
            out.append(leaf)
        if healed:
            self._carry = jax.tree.unflatten(treedef, out)
            self.metrics["carry_repairs"] += healed
        return healed

    def _retire_finished(self) -> None:
        """Completion-based departure (the simulator's own rule): a service
        whose rounds_done reached rounds_required frees its slot.  The
        arrival/count arrays are left untouched -- the step's activity rule
        already excludes the row, and the replay needs the history."""
        done = self._rounds_done >= self.cfg.rounds_required
        for sid in [s for s, r in self.services.items() if done[r.slot]]:
            rec = self.services.pop(sid)
            rec.retired_period = self._period
            self.retired.append(rec)
            self._free.append(rec.slot)
            self.metrics["retired"] += 1

    def _occupied(self) -> np.ndarray:
        """(capacity,) live-slot mask from the registry.  Snapshots the
        registry values first: the daemon may call this from the event loop
        while a tick commits in an executor thread."""
        occupied = np.zeros((self.cfg.capacity,), bool)
        for rec in list(self.services.values()):
            occupied[rec.slot] = True
        return occupied

    def stale_decision(self) -> Decision:
        """Degraded decision for the current period: the previous clear
        rescaled to the live admission mask (budget-preserving), used by the
        daemon when the solver misses its deadline.  Counted in
        ``metrics['stale_decisions']`` -- never served silently -- and NOT
        appended to ``decisions``: that list is the fresh-solve stream the
        differential replay checks; the daemon records what it served."""
        period = self._period
        occupied = self._occupied()
        if self.decisions:
            prev = self.decisions[-1]
            b = np.where(occupied, prev.b, 0.0)
            total = float(b.sum())
            if total > 0.0:
                b = b * (self.net.total_bandwidth_mhz / total)
            f = np.where(occupied, prev.f, 0.0)
            self.metrics["stale_decisions"] += 1
            return Decision(period=period, b=b.astype(np.float32),
                            f=f.astype(np.float32), active=occupied,
                            stale=True)
        # Nothing cleared yet: equal split over live slots.
        self.metrics["stale_decisions"] += 1
        return self._equal_share(period, stale=True, count=False)

    def _equal_share(self, period: int, *, stale: bool,
                     count: bool = False) -> Decision:
        """The O(1) emergency allocation: B split equally over live slots,
        f = 0 (no solve ran, so no frequency claim is honest)."""
        occupied = self._occupied()
        n_live = max(int(occupied.sum()), 1)
        b = np.where(occupied, self.net.total_bandwidth_mhz / n_live, 0.0)
        f = np.zeros((self.cfg.capacity,), np.float32)
        if count:
            self.metrics["degraded_decisions"] += 1
        return Decision(period=period, b=b.astype(np.float32), f=f,
                        active=occupied, stale=stale, degraded=True)

    def degraded_decision(self) -> Decision:
        """Emergency decision for the current period: equal share over the
        live mask, used by the daemon once a stale streak exceeds its bound
        (the previous clear is too old to keep rescaling).  Counted in
        ``metrics['degraded_decisions']`` and flagged ``degraded`` --
        distinct from plain staleness -- and, like ``stale_decision``, NOT
        appended to the fresh-solve stream."""
        return self._equal_share(self._period, stale=True, count=True)

    def allocation_of(self, service_id) -> dict:
        """Latest served (b, f) for one admitted service."""
        rec = self.services.get(service_id)
        if rec is None:
            raise KeyError(f"unknown service {service_id!r}")
        if not self.decisions:
            raise RuntimeError("no decision served yet")
        last = self.decisions[-1]
        return {"period": last.period, "b_mhz": float(last.b[rec.slot]),
                "f_rounds_per_s": float(last.f[rec.slot]),
                "stale": last.stale}

    # -- differential replay ----------------------------------------------

    def trace(self) -> tuple[np.ndarray, np.ndarray]:
        """The admission trace as run_scan inputs: per-slot (arrivals,
        counts), with ``NEVER`` marking slots no service ever occupied."""
        return self._arrivals.copy(), self._counts.copy()

    def replay_sim_config(self) -> simulator.SimConfig:
        """The SimConfig whose ``run_scan(arrivals=..., counts=...,
        collect_alloc=True)`` replays this daemon's stream bitwise (healthy
        heartbeats, no forced retires -- ``replayable`` guards that)."""
        return simulator.SimConfig(
            policy=self.cfg.policy, n_services_total=self.cfg.capacity,
            rounds_required=self.cfg.rounds_required, seed=self.cfg.seed,
            k_max=self.cfg.k_max, max_periods=max(self._period, 1),
            n_bids=self.cfg.n_bids, alpha_fair=self.cfg.alpha_fair,
            intra_backend=self.cfg.intra_backend,
            warm_start=self.cfg.warm_start,
            channel_process=self.cfg.channel_process,
            churn_process=self.cfg.churn_process,
            collect_history=True, collect_alloc=True,
        )

    def recorded_avail(self) -> np.ndarray | None:
        """The recorded heartbeat-drop stream as run_scan's ``avail`` tensor
        ((period, capacity, k_max) bool), or None when no drop was ever
        recorded (an all-True plane would be a bitwise no-op anyway)."""
        if not self._hb_drops:
            return None
        avail = np.ones((max(self._period, 1), self.cfg.capacity,
                         self.cfg.k_max), bool)
        for p, drops in self._hb_drops.items():
            if p < avail.shape[0]:
                for slot, client in drops:
                    avail[p, slot, client] = False
        return avail

    def replay_reference(self) -> dict:
        """Run the offline reference on this daemon's recorded trace
        (admissions + heartbeat-drop masks)."""
        if not self.replayable:
            raise RuntimeError(
                "trace is not replayable as one run_scan episode (slot "
                "reuse, forced retire, or an injected fault: "
                f"{self.unreplayable_reasons or 'unknown'})")
        arrivals, counts = self.trace()
        return simulator.run_scan(self.replay_sim_config(), self.net,
                                  arrivals=arrivals, counts=counts,
                                  avail=self.recorded_avail())

    # -- checkpointable state ---------------------------------------------

    def state_pytree(self) -> dict:
        """The full serving state as one fixed-shape pytree (COMMIT-protocol
        checkpointable; shapes depend only on the config)."""
        return {
            "period": jnp.int32(self._period),
            "arrivals": jnp.asarray(self._arrivals),
            "counts": jnp.asarray(self._counts),
            "last_seen": jnp.asarray(self._last_seen),
            "carry": self._carry,
        }

    def registry_meta(self) -> dict:
        """JSON side-channel for ``CheckpointManager.save(extra=...)``: the
        service-id -> slot map the pytree cannot carry."""
        return {
            "services": {
                str(s): dataclasses.asdict(r)
                for s, r in self.services.items()
            },
            "metrics": dict(self.metrics),
            "replayable": self.replayable,
            "unreplayable_reasons": list(self.unreplayable_reasons),
            "hb_drops": {str(p): d for p, d in self._hb_drops.items()},
        }

    def snapshot(self, manager: CheckpointManager) -> None:
        """COMMIT-protocol checkpoint of serving state + registry meta."""
        manager.save(self._period, self.state_pytree(),
                     extra=self.registry_meta())

    def restore(self, manager: CheckpointManager) -> bool:
        """Adopt the newest VERIFIABLE checkpoint; False when none survives.
        Corrupted-but-committed steps the manager had to skip are surfaced
        in ``metrics['checkpoint_skips']`` -- a skipped checkpoint costs
        recovery time and is never silent."""
        step, tree, extra = manager.restore_latest(self.state_pytree())
        skipped = len(getattr(manager, "last_skipped", ()))
        if step is None:
            self.metrics["checkpoint_skips"] += skipped
            return False
        self.load_state(tree, extra)
        self.metrics["checkpoint_skips"] += skipped
        return True

    def load_state(self, state: dict, meta: dict | None = None) -> None:
        """Adopt a checkpointed pytree (and optionally the registry meta).

        Without ``meta`` the registry is rebuilt from the arrays alone --
        slot indices become the service ids -- which is exactly what the
        scripted ``run_resumable`` path needs after a crash."""
        self._period = int(state["period"])
        self._arrivals = np.asarray(state["arrivals"], np.int32).copy()
        self._counts = np.asarray(state["counts"], np.int32).copy()
        self._last_seen = np.asarray(state["last_seen"], np.int32).copy()
        self._carry = tuple(state["carry"])
        self._rounds_done = np.asarray(self._carry[0], np.int32)
        self.services.clear()
        self._free = []
        self._hb_drops = {}
        if meta and "services" in meta:
            for rec in meta["services"].values():
                rec = _SlotRecord(**rec)
                self.services[rec.service_id] = rec
            if "metrics" in meta:
                self.metrics.update(meta["metrics"])
            self.replayable = bool(meta.get("replayable", True))
            self.unreplayable_reasons = list(
                meta.get("unreplayable_reasons", []))
            self._hb_drops = {int(p): [[int(s), int(c)] for s, c in drops]
                              for p, drops in meta.get("hb_drops",
                                                       {}).items()}
            occupied = {r.slot for r in self.services.values()}
        else:
            occupied = set()
            live = np.logical_and(self._arrivals != NEVER,
                                  self._rounds_done < self.cfg.rounds_required)
            for slot in np.flatnonzero(live):
                slot = int(slot)
                self.services[slot] = _SlotRecord(
                    service_id=slot, slot=slot,
                    n_clients=int(self._counts[slot]),
                    admitted_period=int(self._arrivals[slot]))
                occupied.add(slot)
            if (self.cfg.heartbeat_timeout_periods is not None
                    and self._period > 0):
                # The array-only restore path has no heartbeat-drop record,
                # so a liveness-tracked episode cannot be replayed soundly.
                self._mark_unreplayable(
                    "restored without a heartbeat-drop record")
        self._free = [s for s in range(self.cfg.capacity)
                      if s not in occupied]


# ---------------------------------------------------------------------------
# Scripted serving through the fault-tolerance layer.
# ---------------------------------------------------------------------------

def run_resumable(
    cfg: ControlPlaneConfig,
    schedule: dict[int, tuple[int, ...]],
    n_periods: int,
    manager: CheckpointManager,
    policy: fault.RestartPolicy | None = None,
    fail_at: int | None = None,
    net: network.NetworkConfig | None = None,
) -> tuple[dict, ControlPlane]:
    """Drive a scripted admission schedule through
    ``fault.resumable_loop``: one resumable step per period, the serving
    state checkpointed via the COMMIT protocol every ``policy.save_every``
    periods.  ``schedule`` maps period -> client counts of the services to
    admit that period (ids are assigned ``p{period}s{i}``).  Deterministic:
    a crashed-and-restarted run reaches a bit-identical final state and
    loses at most ``save_every - 1`` periods of work
    (tests/test_control_plane.py / tests/test_fault.py).

    Returns ``(final state pytree, the replayed ControlPlane)`` -- the
    returned plane has ``load_state``-reconstructed bookkeeping, so its
    ``trace()`` still feeds the differential replay.
    """
    plane = ControlPlane(cfg, net)

    def step(state, t):
        plane.load_state(state)
        for i, n_clients in enumerate(schedule.get(t, ())):
            if plane.free_slots:
                plane.admit(f"p{t}s{i}", n_clients)
        plane.tick()
        return plane.state_pytree()

    final = fault.resumable_loop(step, plane.state_pytree(), n_periods,
                                 manager, policy, fail_at=fail_at)
    plane.load_state(final)
    return final, plane
