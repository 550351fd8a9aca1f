#!/usr/bin/env python3
"""Smoke test of the bandwidth allocator on a TPU, through its served path.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the sharded paths, on four chips

One chip drives the live daemon (``repro.launch.allocd.AllocDaemon`` over
``fl.control_plane.ControlPlane``, built the way ``allocd.main`` builds it)
through three phases:

* paper cell, served: the §VI.A cell (``NetworkConfig`` defaults), warm
  ``coop`` on the reference backend, 256 slots of up to 48 clients filled by
  Poisson admissions and then served for 60 periods.  The served stream must
  replay bitwise through ``plane.replay_reference()``, every decision must
  spend the whole budget, and every degradation counter must stay 0;
* megakernel, served: warm ``coop`` with ``intra_backend="megakernel"`` on an
  8192 x 128 market at least 90% occupied, against a reference-backend
  daemon fed the same admissions;
* auction kernel, served: ``selfish`` with ``intra_backend="pallas"`` (the
  mBDF grid and round-time kernels, 5 bids) against the reference backend.

A kernel phase passes when its served allocation stays within 1e-2 MHz and
within 1e-3 of the largest share of the reference daemon's, and the step it
compiled holds ``tpu_custom_call``: the kernel ran, not its reference.

``--four-chips`` runs only the paths that span chips: ``run_fleet`` of 256
paper-cell episodes over a 4-device mesh against ``run_batch`` on one device
(per-seed durations bitwise equal), and ``disba_sharded(method="newton")`` at
N = 8192, K = 128 against the dense solve.

Decisions/s and tick-time percentiles are printed for information only.  Any
failed phase, or a JAX without a TPU, exits non-zero and prints no result.  A
passing run ends with one JSON line:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MAX_DEV_MHZ = 1e-2   # the allocation benchmark's parity bar
MAX_REL_DEV = 1e-3   # ... and relative to the largest share served
FILL = 0.95          # share of slots the admissions ask for


class PhaseFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailure(msg)


# ---------------------------------------------------------------------------
# Workload and serving.
# ---------------------------------------------------------------------------

def paper_counts(net, k_max: int):
    """Cohort sizes K ~ N(mean, var) of the paper's cell, clipped to a slot."""
    std = math.sqrt(net.var_clients)

    def draw(rng: np.random.Generator) -> int:
        k = int(round(rng.normal(net.mean_clients, std)))
        return int(np.clip(k, net.k_min, k_max))

    return draw


def uniform_counts(k_max: int):
    """Cohort sizes of ``allocd``'s own synthetic workload: U{2..k_max}."""
    return lambda rng: int(rng.integers(2, k_max + 1))


def admission_trace(seed: int, capacity: int, rate: float,
                    counts) -> list[list[tuple[str, int]]]:
    """Poisson(rate) admissions per period until FILL of the slots are
    asked for; never more than fit, so no admission is rejected or retried
    into a reused slot (which would make the trace unreplayable)."""
    rng = np.random.default_rng(seed)
    target = math.ceil(FILL * capacity)
    trace, total = [], 0
    while total < target:
        n = min(int(rng.poisson(rate)), target - total)
        trace.append([(f"svc-{len(trace)}-{i}", counts(rng))
                      for i in range(n)])
        total += n
    return trace


def serve(cfg, trace, n_serve: int, net=None):
    """Drive one ``AllocDaemon`` through ``trace`` and ``n_serve`` more
    periods.  Returns the daemon and the wall seconds of each served period
    (a tick ends with the decision on the host, so the device has finished)."""
    from repro.launch.allocd import Admit, AllocDaemon

    daemon = AllocDaemon(cfg, net)
    tick_s = []

    async def drive():
        for p in range(len(trace) + n_serve):
            for sid, k in (trace[p] if p < len(trace) else ()):
                daemon.submit(Admit(sid, k))
            t0 = time.perf_counter()
            await daemon.step_period()
            tick_s.append(time.perf_counter() - t0)
        await daemon.close()

    asyncio.run(drive())
    return daemon, np.asarray(tick_s)


def report(name: str, tick_s: np.ndarray, n_fill: int) -> None:
    """Tick times of the admission periods (the first compiles the step)
    and of the served periods after them."""
    fill, steady = tick_s[1:n_fill], tick_s[n_fill:]
    print(f"[{name}] first tick {tick_s[0]:.3f} s (compile included); "
          f"{len(fill)} more admission ticks, mean "
          f"{fill.mean() * 1e3 if len(fill) else float('nan'):.3f} ms; "
          f"{len(steady)} served ticks: "
          f"{len(steady) / steady.sum():.1f} decisions/s, "
          f"p50 {np.percentile(steady, 50) * 1e3:.3f} ms, "
          f"p99 {np.percentile(steady, 99) * 1e3:.3f} ms", flush=True)


def check_healthy(name: str, daemon, capacity: int, n_periods: int,
                  min_fill: float) -> None:
    """Every period served fresh, the budget spent, no counter moved."""
    plane = daemon.plane
    m = plane.metrics
    for key in ("solver_fallbacks", "nonfinite_decisions",
                "degraded_decisions", "carry_repairs", "stale_decisions"):
        check(m[key] == 0, f"{name}: {key} = {m[key]}")
    check(not daemon.rejections, f"{name}: rejections {daemon.rejections[:3]}")
    check(len(plane.decisions) == n_periods
          and not any(d.stale or d.degraded for d in daemon.served),
          f"{name}: not every period was a fresh solve")
    occupied = len(plane.services) / capacity
    check(occupied >= min_fill,
          f"{name}: {occupied:.3f} of the slots occupied, want >= {min_fill}")
    budget = plane.net.total_bandwidth_mhz
    for d in plane.decisions:
        check(np.all(np.isfinite(d.b)) and np.all(np.isfinite(d.f))
              and np.all(d.b >= 0.0), f"{name}: bad values at {d.period}")
        if d.active.any():
            check(abs(float(d.b.sum()) - budget) <= 1e-4 * budget,
                  f"{name}: period {d.period} spends {d.b.sum()} of {budget}")
    print(f"[{name}] {occupied:.3f} of {capacity} slots occupied, "
          f"{n_periods} fresh decisions, budget spent, counters 0",
          flush=True)


def compare_served(name: str, kernel, reference) -> None:
    """Kernel daemon's served b against the reference daemon's."""
    b_k = np.stack([d.b for d in kernel.plane.decisions])
    b_r = np.stack([d.b for d in reference.plane.decisions])
    check(b_k.shape == b_r.shape, f"{name}: {b_k.shape} vs {b_r.shape}")
    dev = float(np.max(np.abs(b_k - b_r)))
    rel = dev / float(np.max(b_r))
    print(f"[{name}] max |b - b_ref| = {dev:.3e} MHz "
          f"({rel:.3e} of the largest share)", flush=True)
    check(dev < MAX_DEV_MHZ, f"{name}: deviation {dev} MHz")
    check(rel < MAX_REL_DEV, f"{name}: relative deviation {rel}")
    hlo = kernel.plane.compiled_step_text()
    check("tpu_custom_call" in hlo,
          f"{name}: the compiled step holds no tpu_custom_call")
    print(f"[{name}] compiled step holds tpu_custom_call", flush=True)


# ---------------------------------------------------------------------------
# One-chip phases.
# ---------------------------------------------------------------------------

def paper_cell(capacity=256, k_max=48, n_serve=60, seed=0) -> None:
    from repro.core import network
    from repro.fl.control_plane import ControlPlaneConfig

    net = network.NetworkConfig()
    cfg = ControlPlaneConfig(capacity=capacity, k_max=k_max, policy="coop",
                             warm_start=True, intra_backend="reference",
                             seed=seed)
    trace = admission_trace(seed, capacity, capacity / 8,
                            paper_counts(net, k_max))
    daemon, tick_s = serve(cfg, trace, n_serve, net)
    n = len(trace) + n_serve
    report("paper", tick_s, len(trace))
    check_healthy("paper", daemon, capacity, n, FILL)
    plane = daemon.plane
    check(plane.replayable, f"paper: not replayable "
          f"({plane.unreplayable_reasons})")
    ref = plane.replay_reference()["history"]
    for key in ("b", "f", "active"):
        live = np.stack([getattr(d, key) for d in plane.decisions])
        check(np.array_equal(live, np.asarray(ref[key])[:n]),
              f"paper: served {key} differs from the replay")
    print(f"[paper] replay over {n} periods: bitwise equal", flush=True)


def kernel_cell(name, policy, backend, capacity, k_max, counts, n_serve,
                seed=0, min_fill=0.9) -> None:
    from repro.fl.control_plane import ControlPlaneConfig

    trace = admission_trace(seed, capacity, capacity / 4, counts)
    n = len(trace) + n_serve
    served = {}
    for be in (backend, "reference"):
        cfg = ControlPlaneConfig(capacity=capacity, k_max=k_max,
                                 policy=policy, warm_start=True,
                                 intra_backend=be, seed=seed)
        daemon, tick_s = serve(cfg, trace, n_serve)
        report(f"{name}/{be}", tick_s, len(trace))
        check_healthy(f"{name}/{be}", daemon, capacity, n, min_fill)
        served[be] = daemon
    compare_served(name, served[backend], served["reference"])


def megakernel_cell(capacity=8192, k_max=128, n_serve=8) -> None:
    kernel_cell("megakernel", "coop", "megakernel", capacity, k_max,
                uniform_counts(k_max), n_serve)


def auction_cell(capacity=256, k_max=48, n_serve=20) -> None:
    from repro.core import network

    kernel_cell("auction", "selfish", "pallas", capacity, k_max,
                paper_counts(network.NetworkConfig(), k_max), n_serve)


# ---------------------------------------------------------------------------
# Four-chip phases.
# ---------------------------------------------------------------------------

def fleet_cell(n_devices=4, n_seeds=256, max_periods=400) -> None:
    from repro.fl import simulator
    from repro.launch.mesh import make_fleet_mesh

    cfg = simulator.SimConfig(policy="coop", warm_start=True,
                              max_periods=max_periods, collect_history=False)
    seeds = list(range(n_seeds))
    mesh = make_fleet_mesh(n_devices)
    t0 = time.perf_counter()
    fleet = simulator.run_fleet(cfg, seeds, mesh=mesh)
    t1 = time.perf_counter()
    batch = simulator.run_batch(cfg, seeds)
    t2 = time.perf_counter()
    print(f"[fleet] run_fleet over {n_devices} devices {t1 - t0:.2f} s, "
          f"run_batch on one {t2 - t1:.2f} s (compile included)", flush=True)
    ids = fleet["fleet"]["device_ids"]
    check(len(set(ids)) == n_devices,
          f"fleet: output held by devices {ids}, want {n_devices}")
    check(bool(np.all(fleet["finished"])) and bool(np.all(batch["finished"])),
          f"fleet: not every episode finished in {max_periods} periods")
    check(np.array_equal(fleet["durations"], batch["durations"]),
          "fleet: per-seed durations differ from run_batch")
    print(f"[fleet] {n_seeds} episodes on devices {ids}: per-seed durations "
          f"bitwise equal to run_batch", flush=True)


def sharded_newton_cell(n_devices=4, n=8192, k=128, seed=0) -> None:
    import jax
    import jax.numpy as jnp

    from repro.compat import flat_mesh
    from repro.core import disba, network

    net = network.NetworkConfig()
    rng = np.random.default_rng(seed)
    counts = rng.integers(2, k + 1, size=n).astype(np.int32)
    svc, _ = network.sample_services(jax.random.key(seed), n, net, k_max=k,
                                     client_counts=counts)
    b_total = net.total_bandwidth_mhz
    lam_prev = disba.solve_lambda_bisect(svc, b_total).lam * jnp.float32(1.05)
    dense = disba.solve_lambda_newton_warm(svc, b_total, lam_prev)
    res = disba.disba_sharded(flat_mesh(n_devices), svc, b_total,
                              method="newton", lam_prev=lam_prev,
                              iters=disba.WARM_ITERS)
    ids = sorted(d.id for d in res.b.sharding.device_set)
    check(len(ids) == n_devices,
          f"sharded: b held by devices {ids}, want {n_devices}")
    b, b_ref = np.asarray(res.b), np.asarray(dense.b)
    dev = float(np.max(np.abs(b - b_ref)))
    rel = dev / float(np.max(b_ref))
    lam_rel = abs(float(res.lam) - float(dense.lam)) / float(dense.lam)
    print(f"[sharded] N={n} K={k} on devices {ids}: max |b - b_dense| = "
          f"{dev:.3e} MHz ({rel:.3e} of the largest share), lam rel dev "
          f"{lam_rel:.3e}", flush=True)
    check(np.all(np.isfinite(b)), "sharded: non-finite allocation")
    check(abs(float(b.sum()) - b_total) <= 1e-4 * b_total,
          f"sharded: spends {b.sum()} of {b_total}")
    check(rel < MAX_REL_DEV, f"sharded: relative deviation {rel}")
    check(lam_rel < MAX_REL_DEV, f"sharded: lam relative deviation {lam_rel}")


# ---------------------------------------------------------------------------

def device_info(want: int) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    check(d.platform == "tpu", f"JAX finds no TPU (platform {d.platform!r})")
    check(len(devices) >= want, f"{want} chips needed, {len(devices)} found")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet and sharded Newton "
                         "phases, over four chips")
    args = ap.parse_args(argv)
    try:
        info = device_info(4 if args.four_chips else 1)
        from repro.compile_cache import enable_compile_cache

        print(f"[cache] {enable_compile_cache()}", flush=True)
        phases = ([fleet_cell, sharded_newton_cell] if args.four_chips
                  else [paper_cell, megakernel_cell, auction_cell])
        for phase in phases:
            t0 = time.perf_counter()
            phase()
            print(f"[{phase.__name__}] passed in "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
    except PhaseFailure as exc:
        print(f"FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
