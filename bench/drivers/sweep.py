"""The sweep generator: Monte-Carlo episodes of the paper's long-term
setting (arXiv:2101.03627 §VI.D) through ``simulator.run_fleet``, as a
planner runs them, over a mesh of the cell's chips.

Parameters, from the traffic file:

* ``services_per_episode``  services arriving in one episode;
* ``p_arrive``              mean of the exponential gaps between arrivals,
                            in periods (the paper's Poisson process);
* ``episodes_per_chip``     episodes of one ``run_fleet`` call, per chip;
* ``check_episodes``        episodes of the window that ``correct``
                            compares with the reference, drawn from the seed.

Each call takes a fresh block of episode seeds drawn from ``--seed``.  The
window makes whole calls until ``--seconds`` have passed;
``episodes_per_s`` is the episodes of those calls over their time.  A
traced run profiles the window's first call, made of one chunk of episodes
a chip (the fleet engine's unit: the same launches, a quarter of the
operations of a whole call, which a profiler's buffers hold); set-up warms
that shape only in a traced run.
``correct`` replays the drawn episodes with the plain reference: arrivals,
cohorts and every period's services from the episode's key, the policy's
reference allocation each period, and rounds counted from its frequencies.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import Readings

DRAW_SALT = (1 << 30) + 3     # the episode key's branch for arrivals/cohorts


def make(cell, devices):
    return SweepRun(cell, devices)


class SweepRun:
    def __init__(self, cell, devices):
        self.cell, self.cfg, self.tr = cell, cell.config, cell.traffic
        self.devices = devices
        self.per_call = int(self.tr["episodes_per_chip"]) * len(devices)
        self.rng = np.random.default_rng([cell.seed, 3])
        self.calls: list[dict] = []
        self.call_s: list[float] = []

    def _seeds(self, n: int | None = None) -> list[int]:
        return [int(s) for s in self.rng.choice(
            2 ** 31, n or self.per_call, replace=False)]

    def _sim_config(self):
        from repro.fl import simulator

        cfg, net = self.cfg, self.cfg["network"]
        return simulator.SimConfig(
            policy=cfg["policy"], n_services_total=int(
                self.tr["services_per_episode"]),
            rounds_required=int(cfg["rounds_required"]),
            p_arrive=float(self.tr["p_arrive"]),
            mean_clients=float(net["mean_clients"]),
            var_clients=float(net["var_clients"]),
            mean_channel_db=float(net["mean_pathloss_db"]),
            var_channel_db=float(net["var_pathloss_db"]),
            n_bids=int(cfg["n_bids"]), alpha_fair=float(cfg["alpha_fair"]),
            max_periods=int(cfg["max_periods"]),
            intra_backend=cfg["intra_backend"], k_max=int(cfg["k_max"]),
            warm_start=bool(cfg["warm_start"]), collect_history=False)

    def _call(self, seeds):
        from repro.fl import simulator

        return simulator.run_fleet(self.sim, seeds, self.net, mesh=self.mesh)

    def setup(self, tracer) -> None:
        from repro.core import network
        from repro.fl import simulator
        from repro.launch.mesh import make_fleet_mesh

        self.net = network.NetworkConfig(**self.cfg["network"])
        self.sim = self._sim_config()
        self.mesh = make_fleet_mesh(len(self.devices))
        seeds = self._seeds()
        out = self._call(seeds)
        self.chunk = int(out["fleet"]["chunk"])
        self.traced_call = self.chunk * len(self.devices)
        if tracer.enabled:
            self._call(seeds[:self.traced_call])
        self.traces_before = simulator.trace_count()

    def window(self, seconds: float, tracer) -> None:
        from repro.fl import simulator

        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            first = not self.calls
            seeds = self._seeds(self.traced_call if first and tracer.enabled
                                else None)
            if first:
                tracer.start()      # a traced run profiles the first call
            t0 = time.perf_counter()
            with tracer.span("bench.run_fleet"):
                out = self._call(seeds)
            self.call_s.append(time.perf_counter() - t0)
            if first:
                tracer.stop()
            self.calls.append({"seeds": seeds,
                               "durations": np.asarray(out["durations"]),
                               "finished": np.asarray(out["finished"]),
                               "periods": np.asarray(out["periods"])})
        self.retraced = simulator.trace_count() - self.traces_before
        if self.retraced:
            raise RuntimeError(f"the period step was traced {self.retraced} "
                               f"times inside the window")

    def end_to_end(self) -> dict:
        return {"episodes_per_s": self.attempted / sum(self.call_s)}

    def readings(self) -> Readings:
        """Counters of the window, and the shapes of one launch of each
        kernel: the episode step is batched over a chunk of episodes, so
        a launch covers chunk x services rows."""
        periods = np.concatenate([c["periods"] for c in self.calls])
        rows = self.chunk * int(self.tr["services_per_episode"])
        k, m = int(self.cfg["k_max"]), int(self.cfg["n_bids"])
        return Readings(
            counters={"calls": len(self.calls),
                      "useful_period_share": float(np.mean(
                          periods / int(self.cfg["max_periods"])))},
            kernel_calls={"mbdf_demand": dict(n=rows, k=k, m=m, iters=48),
                          "bisect_alloc": dict(n=rows, k=k, iters=48),
                          "dual_demand": dict(n=rows, k=k)})

    @property
    def attempted(self) -> int:
        return sum(len(c["seeds"]) for c in self.calls)

    @property
    def failed(self) -> int:
        """Episodes that did not finish in ``max_periods``."""
        return int(sum(np.sum(~c["finished"]) for c in self.calls))

    def free(self) -> None:
        import jax

        self.mesh = None
        jax.clear_caches()

    def _chosen(self):
        """The window's episodes that ``correct`` compares, from the seed."""
        pick = np.random.default_rng([self.cell.seed, 4])
        flat = [(i, j) for i, c in enumerate(self.calls)
                for j in range(len(c["seeds"]))]
        n_check = min(int(self.tr["check_episodes"]), len(flat))
        return [flat[int(k)] for k in pick.choice(len(flat), n_check,
                                                  replace=False)]

    def check(self, reference) -> dict:
        print(f"[sweep] {len(self.calls)} calls, {self.attempted} episodes, "
              f"{sum(self.call_s):.3f} s in all", flush=True)
        chosen = self._chosen()
        dur = np.stack([self.calls[i]["durations"][j] for i, j in chosen])
        return self._numbers(chosen, dur, reference)

    def control(self, reference, dtype) -> dict:
        """The numbers ``check`` gives when the reference computed in
        ``dtype`` stands in the program's place."""
        chosen = self._chosen()
        seeds = [self.calls[i]["seeds"][j] for i, j in chosen]
        dur, _ = episodes(seeds, self.cfg, self.tr, reference, dtype)
        return self._numbers(chosen, dur, reference)

    def _numbers(self, chosen, dur, reference) -> dict:
        """The share of the drawn episodes' services whose duration differs
        from the reference's.  A service that finishes in another period,
        or not at all, has another duration, so ``finished`` is held too."""
        seeds = [self.calls[i]["seeds"][j] for i, j in chosen]
        dur_ref, _ = episodes(seeds, self.cfg, self.tr, reference)
        return {"duration_mismatch_share": float(np.mean(dur != dur_ref))}


def episodes(seeds, cfg, tr, reference, dtype=None):
    """Reference per-service durations (E, N) and finished flags (E,)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import cell

    dtype = dtype or jnp.float32
    net = cfg["network"]
    n, k_max = int(tr["services_per_episode"]), int(cfg["k_max"])
    required = int(cfg["rounds_required"])
    b_total = float(net["total_bandwidth_mhz"])
    period_s = jnp.float32(net["period_s"])

    def draws(key):
        k_arr, k_cnt = jax.random.split(jax.random.fold_in(key, DRAW_SALT))
        gaps = jax.random.exponential(k_arr, (n,), jnp.float32) * float(
            tr["p_arrive"])
        arrivals = jnp.floor(jnp.cumsum(gaps)).astype(jnp.int32)
        counts = jnp.clip(jnp.round(
            float(net["mean_clients"]) + float(net["var_clients"]) ** 0.5
            * jax.random.normal(k_cnt, (n,), jnp.float32)),
            net["k_min"], k_max).astype(jnp.int32)
        return arrivals, counts

    def episode(key):
        arrivals, counts = draws(key)

        def period(carry, p):
            rounds, duration = carry
            active = (arrivals <= p) & (rounds < required)
            alpha, t_comp, mask = cell.sample(jax.random.fold_in(key, p),
                                              counts, net, k_max, dtype)
            mask = mask & active[:, None]
            zero = jnp.asarray(0, dtype)
            alpha = jnp.where(mask, alpha, zero)
            t_comp = jnp.where(mask, t_comp, zero)
            _, f = reference.allocate(alpha, t_comp, mask, b_total,
                                      n_bids=int(cfg["n_bids"]),
                                      alpha_fair=float(cfg["alpha_fair"]))
            f = f.astype(jnp.float32)
            gained = jnp.maximum(jnp.floor(
                jnp.where(jnp.isfinite(f), f, 0.0) * period_s), 0.0)
            rounds = jnp.minimum(
                rounds + jnp.where(active, gained.astype(jnp.int32), 0),
                required)
            return (rounds, duration + active.astype(jnp.int32)), None

        zeros = jnp.zeros((n,), jnp.int32)
        (rounds, duration), _ = jax.lax.scan(
            period, (zeros, zeros), jnp.arange(int(cfg["max_periods"])))
        return duration, jnp.all(rounds >= required)

    keys = jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32) + 7)
    duration, finished = jax.jit(jax.vmap(episode))(keys)
    return np.asarray(duration), np.asarray(finished)
