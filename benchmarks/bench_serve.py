"""Control-plane serving benchmark -> repo-root ``BENCH_serve.json``.

``BENCH_allocation.json`` pinned the raw per-period solve and
``BENCH_fleet.json`` the offline sweep throughput; this artifact measures
the *online* serving path (``launch.allocd`` over ``fl.control_plane``):
sustained decisions/sec and p50/p99 per-decision latency of the asyncio
daemon under a Poisson admission workload, at market capacities
N in {16, 64, 256}, with the warm-started dual carry against a cold solve
every period.  Warm vs cold is the serving-side payoff of the <= 6-trip
safeguarded-Newton path: at steady state the daemon re-clears an almost
unchanged market, exactly the regime warm-starting targets.

The artifact also carries the control plane's correctness anchor as a
``parity`` record: a daemon run under completion-based churn whose served
allocation stream must be **bitwise equal** to ``simulator.run_scan`` fed
the same admission trace (see fl/control_plane.py's differential contract),
and a stale-decision drill (an injected solver delay with a tight deadline)
proving the degraded path serves and counts ``stale_decisions`` instead of
stalling.

Usage:
  PYTHONPATH=src python -m benchmarks.bench_serve [--tiny] [--out PATH]

``--tiny`` shrinks capacities/periods for the CI smoke step (same schema,
same validation path).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import time

from repro.compile_cache import enable_compile_cache

SCHEMA = "bench_serve/v1"
DEFAULT_OUT = "BENCH_serve.json"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plan(tiny: bool) -> dict:
    if tiny:
        return {
            "capacities": [4, 8],
            "periods": 10, "warmup": 2,
            "rate_per_slot": 0.1,       # mean admissions/period = rate * N
            "k_max": 8,
            "parity": {"capacity": 8, "periods": 10, "rate": 0.4,
                       "rounds_required": 60, "k_max": 8},
        }
    return {
        "capacities": [16, 64, 256],
        "periods": 40, "warmup": 4,
        "rate_per_slot": 0.1,
        "k_max": 16,
        "parity": {"capacity": 16, "periods": 24, "rate": 0.5,
                   "rounds_required": 100, "k_max": 8},
    }


def _serving_row(capacity: int, warm: bool, plan: dict, seed: int = 0) -> dict:
    """Drive one daemon through a Poisson workload; time each decision."""
    import numpy as np

    from repro.fl.control_plane import ControlPlaneConfig
    from repro.launch import allocd

    cfg = ControlPlaneConfig(
        capacity=capacity, k_max=plan["k_max"], policy="coop",
        warm_start=warm, rounds_required=100_000, seed=seed,
    )
    daemon = allocd.AllocDaemon(cfg)
    workload = allocd.poisson_admissions(
        np.random.default_rng(seed), plan["rate_per_slot"] * capacity,
        plan["periods"], plan["k_max"])

    latencies: list[float] = []

    async def drive() -> None:
        for p in range(plan["periods"]):
            for req in workload.get(p, ()):
                daemon.submit(req)
            t0 = time.perf_counter()
            await daemon.step_period()
            if p >= plan["warmup"]:      # exclude compile periods
                latencies.append(time.perf_counter() - t0)

    asyncio.run(drive())
    lat = np.asarray(latencies)
    m = daemon.plane.metrics
    return {
        "capacity": capacity,
        "warm": warm,
        "periods": plan["periods"],
        "measured_decisions": int(lat.size),
        "decisions_per_sec": float(lat.size / lat.sum()),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "admitted": m["admitted"],
        "rejected": m["rejected"] + len(daemon.rejections),
        "stale_decisions": m["stale_decisions"],
    }


def _parity_record(plan: dict, seed: int = 0) -> dict:
    """Daemon vs run_scan differential on one completion-churn workload."""
    import numpy as np

    from repro.fl.control_plane import ControlPlaneConfig
    from repro.launch import allocd

    p = plan["parity"]
    cfg = ControlPlaneConfig(
        capacity=p["capacity"], k_max=p["k_max"], policy="coop",
        warm_start=True, rounds_required=p["rounds_required"], seed=seed,
    )
    daemon = allocd.AllocDaemon(cfg)
    workload = allocd.poisson_admissions(
        np.random.default_rng(seed), p["rate"], p["periods"], p["k_max"])
    asyncio.run(allocd._run_workload(daemon, workload, p["periods"]))
    assert daemon.plane.replayable, (
        "parity workload overflowed capacity into slot reuse; lower the rate")
    ref = daemon.plane.replay_reference()
    live = {k: np.stack([getattr(d, k) for d in daemon.plane.decisions])
            for k in ("b", "f", "active")}
    n = live["b"].shape[0]
    max_dev = max(
        float(np.max(np.abs(np.asarray(ref["history"][k][:n], np.float64)
                            - np.asarray(live[k], np.float64))))
        for k in ("b", "f"))
    return {
        "capacity": p["capacity"], "periods": n,
        "admitted": daemon.plane.metrics["admitted"],
        "retired": daemon.plane.metrics["retired"],
        "bitwise_equal": bool(
            all(np.array_equal(ref["history"][k][:n], live[k])
                for k in ("b", "f", "active"))),
        "max_dev": max_dev,
    }


def _stale_drill(plan: dict) -> dict:
    """Deadline-miss path: injected solver delay + tight timeout must yield
    counted stale decisions, then a committed fresh one."""
    from repro.fl.control_plane import ControlPlaneConfig
    from repro.launch import allocd

    cfg = ControlPlaneConfig(capacity=4, k_max=plan["k_max"], policy="coop",
                             rounds_required=1000)
    daemon = allocd.AllocDaemon(cfg)        # no deadline while compiling

    async def drive() -> list:
        daemon.submit(allocd.Admit("svc-0", 4))
        await daemon.step_period()          # compile + commit period 0
        daemon.solver_timeout_s = 0.05
        daemon._solver_delay_s = 0.5        # overrun the 50 ms deadline
        await daemon.step_period()          # -> stale
        daemon._solver_delay_s = 0.0
        daemon.solver_timeout_s = None
        await daemon.step_period()          # pending solve commits -> fresh
        await daemon.step_period()          # steady state again
        await daemon.close()
        return daemon.served

    served = asyncio.run(drive())
    return {
        "served": len(served),
        "stale_decisions": daemon.plane.metrics["stale_decisions"],
        "stale_flags": [bool(d.stale) for d in served],
        "fresh_decisions": len(daemon.plane.decisions),
    }


def run(tiny: bool = False) -> dict:
    from benchmarks import common

    plan = _plan(tiny)
    rows = [
        _serving_row(capacity, warm, plan)
        for capacity in plan["capacities"]
        for warm in (True, False)
    ]
    return {
        "schema": SCHEMA,
        "tiny": tiny,
        **common.provenance(),
        "plan": {k: v for k, v in plan.items() if k != "parity"},
        "rows": rows,
        "parity": _parity_record(plan),
        "stale_drill": _stale_drill(plan),
    }


def validate(data: dict) -> None:
    """Schema check used by CI and tests: provenance stamped, both warm
    branches measured at every capacity, the differential replay bitwise
    clean, and the deadline-miss drill counted -- never silent."""
    from benchmarks import common

    assert data["schema"] == SCHEMA
    common.validate_provenance(data)
    seen = {(row["capacity"], row["warm"]) for row in data["rows"]}
    capacities = {c for c, _ in seen}
    assert all((c, w) in seen for c in capacities for w in (True, False)), (
        "every capacity needs a warm AND a cold row")
    for row in data["rows"]:
        assert row["decisions_per_sec"] > 0, row
        assert 0 < row["p50_ms"] <= row["p99_ms"], row
        assert row["stale_decisions"] == 0, (
            "serving rows run without a deadline; stale decisions here mean "
            "the daemon miscounted")
    parity = data["parity"]
    assert parity["bitwise_equal"] is True, parity
    assert parity["max_dev"] == 0.0, parity
    assert parity["admitted"] > 0 and parity["retired"] > 0, (
        "parity run must exercise admissions AND completion-based departures")
    drill = data["stale_drill"]
    assert drill["stale_decisions"] >= 1, drill
    assert drill["stale_flags"].count(True) == drill["stale_decisions"], (
        "every stale decision must be flagged on the served stream")
    assert drill["fresh_decisions"] + drill["stale_decisions"] \
        == drill["served"], drill


def run_rows(tiny: bool = False) -> list[dict]:
    """benchmarks.run adapter: execute, write the artifact, emit CSV rows."""
    from benchmarks import common

    data = run(tiny=tiny)
    validate(data)
    if tiny:
        common.save_artifact("bench_serve_tiny", data)
    else:
        with open(os.path.join(_REPO_ROOT, DEFAULT_OUT), "w") as fp:
            json.dump(data, fp, indent=1, default=float)
            fp.write("\n")
    rows = []
    for row in data["rows"]:
        rows.append(common.row(
            f"serve/{'warm' if row['warm'] else 'cold'}_N{row['capacity']}",
            row["p50_ms"] * 1e3,
            f"dps={row['decisions_per_sec']:.1f} "
            f"p99_ms={row['p99_ms']:.2f}"))
    parity = data["parity"]
    rows.append(common.row(
        "serve/replay_parity", None,
        f"N={parity['capacity']} periods={parity['periods']} "
        f"bitwise={parity['bitwise_equal']} max_dev={parity['max_dev']:.1f}"))
    drill = data["stale_drill"]
    rows.append(common.row(
        "serve/stale_drill", None,
        f"stale={drill['stale_decisions']}/{drill['served']}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (seconds instead of minutes)")
    ap.add_argument("--out", default=os.path.join(_REPO_ROOT, DEFAULT_OUT),
                    help=f"output path (default: {DEFAULT_OUT} at repo root)")
    args = ap.parse_args()
    enable_compile_cache()
    data = run(tiny=args.tiny)
    validate(data)
    with open(args.out, "w") as fp:
        json.dump(data, fp, indent=1, default=float)
        fp.write("\n")
    for row in data["rows"]:
        print(f"N={row['capacity']} {'warm' if row['warm'] else 'cold'}: "
              f"{row['decisions_per_sec']:.1f} decisions/s "
              f"p50={row['p50_ms']:.2f}ms p99={row['p99_ms']:.2f}ms")
    parity = data["parity"]
    print(f"replay parity: bitwise={parity['bitwise_equal']} "
          f"max_dev={parity['max_dev']} "
          f"(admitted={parity['admitted']} retired={parity['retired']})")
    print(f"stale drill: {data['stale_drill']['stale_decisions']} counted")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
