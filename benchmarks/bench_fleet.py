"""Fleet-sweep throughput benchmark -> repo-root ``BENCH_fleet.json``.

PR 3's ``BENCH_allocation.json`` pinned the per-period solve; this artifact
adds the *sweep throughput* axis: episodes/sec and periods/sec of the
device-sharded, chunked ``fl.simulator.run_fleet`` engine against the flat
single-device ``run_batch`` vmap, scaled over forced-host device counts
(1 -> 8) and fleet sizes (64 -> 4096).  Two effects compose:

* **chunking** -- ``run_batch`` at fleet 1024 drags a multi-MB working set
  through every bisection trip of every period; ``run_fleet``'s O(chunk)
  inner batch stays cache-resident (measurable even on ONE device);
* **sharding** -- the seed axis splits across devices, so forced-host CPU
  devices (or real accelerators) add near-linear throughput on top.

On the CPU every row is measured in a fresh worker subprocess so each
device count gets its own ``XLA_FLAGS=--xla_force_host_platform_device_count``
before jax initializes (the flag is locked in at first device query); the
parent never touches JAX there when ``JAX_PLATFORMS=cpu``.  On an
accelerator a child could not reach the devices the parent holds, so every
device count runs in-process over the first n devices
(``launch.mesh.make_fleet_mesh``), up to as many as the host has.  The
largest device count (8 forced-host devices on the CPU) also checks
per-seed *bitwise* parity of ``run_fleet`` against ``run_batch``,
records the max deviation (0.0 by construction), and runs
the headline comparison as an interleaved A/B -- alternating run_batch /
run_fleet calls, median over ``ab_reps`` -- because the DRAM-bound flat
vmap's wall time swings with host memory-bandwidth noise while the
cache-resident fleet's does not; each worker's ru_maxrss lands in the
artifact as the peak-memory proxy.

Usage:
  PYTHONPATH=src python -m benchmarks.bench_fleet [--tiny] [--out PATH]

``--tiny`` shrinks fleets/episodes for the CI smoke step (same schema, same
validation path, seconds instead of minutes).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

SCHEMA = "bench_fleet/v1"
DEFAULT_OUT = "BENCH_fleet.json"
DEVICE_COUNTS = (1, 2, 4, 8)
REFERENCE_DEVICES = 8        # the acceptance point: 8 forced-host devices
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sim_config(tiny: bool) -> dict:
    """Episode config (SimConfig kwargs): aggregate-only coop sweeps -- the
    paper's §VI.D Monte-Carlo workload in miniature."""
    if tiny:
        return dict(policy="coop", n_services_total=4, rounds_required=2000,
                    p_arrive=2.0, mean_clients=8.0, var_clients=4.0,
                    max_periods=8, collect_history=False)
    # 32 service slots x ~70-client pad: at fleet 1024 the flat vmap drags
    # (1024, 32, 70) f32 arrays (~9 MB each, beyond this host's last-level
    # cache) through every bisection trip of every period -- DRAM-bandwidth
    # bound -- while run_fleet's 16-episode chunks (~290 KB per array) stay
    # cache-resident.
    return dict(policy="coop", n_services_total=32, rounds_required=2000,
                p_arrive=2.0, mean_clients=50.0, max_periods=6,
                collect_history=False)


def _plan(tiny: bool) -> dict:
    """What each worker measures (fleet sizes per device count)."""
    if tiny:
        return {
            "batch_fleets": [16, 64],       # 1-device run_batch baseline
            "scaling_fleet": 64,            # device-scaling point
            "fleet_fleets": [16, 64],       # fleet-size sweep at 8 devices
            "parity_fleet": 64,             # acceptance point: A/B + parity
            "device_counts": [1, REFERENCE_DEVICES],
            "reps": 1,
            "ab_reps": 2,
            "chunk_size": None,             # FLEET_CHUNK default
        }
    return {
        "batch_fleets": [64, 256, 1024],
        "scaling_fleet": 256,
        "fleet_fleets": [64, 256, 1024, 4096],
        "parity_fleet": 1024,
        "device_counts": list(DEVICE_COUNTS),
        "reps": 2,
        "ab_reps": 5,
        # Cache-tuned for the full config: 16 episodes x (32, 70) f32 keeps
        # the solver working set under the last-level cache.
        "chunk_size": 16,
    }


# ---------------------------------------------------------------------------
# Worker: runs under a fixed forced-host device count, one subprocess each.
# ---------------------------------------------------------------------------

def _time_call(fn, reps: int, warm: bool = True) -> float:
    """Best-of-reps wall seconds, after one untimed warmup/compile call."""
    if warm:
        fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _measure(devices: int, tiny: bool, reference: bool) -> dict:
    """Rows for one device count, in this process, over the first
    ``devices`` devices; ``reference`` adds the fleet-size sweep, the
    bitwise parity check and the interleaved A/B."""
    import resource

    import numpy as np

    from repro.fl import simulator
    from repro.launch.mesh import make_fleet_mesh

    cfg = simulator.SimConfig(**_sim_config(tiny))
    plan = _plan(tiny)
    reps = plan["reps"]
    chunk_size = plan["chunk_size"]
    periods = cfg.max_periods
    # The exact mesh-construction path run_fleet defaults to.
    mesh = make_fleet_mesh(devices)

    def fleet_row(fleet: int) -> dict:
        seeds = list(range(fleet))
        meta = simulator.run_fleet(cfg, seeds, mesh=mesh,
                                   chunk_size=chunk_size)["fleet"]  # warmup
        secs = _time_call(
            lambda: simulator.run_fleet(cfg, seeds, mesh=mesh,
                                        chunk_size=chunk_size),
            reps, warm=False)
        return {
            "engine": "run_fleet", "devices": devices, "fleet": fleet,
            "chunk": meta["chunk"], "n_chunks": meta["n_chunks"],
            "padded_to": meta["padded_to"],
            "seconds": secs,
            "episodes_per_sec": fleet / secs,
            "periods_per_sec": fleet * periods / secs,
        }

    rows = []
    if devices == 1:
        for fleet in plan["batch_fleets"]:
            seeds = list(range(fleet))
            secs = _time_call(lambda: simulator.run_batch(cfg, seeds), reps)
            rows.append({
                "engine": "run_batch", "devices": 1, "fleet": fleet,
                "seconds": secs,
                "episodes_per_sec": fleet / secs,
                "periods_per_sec": fleet * periods / secs,
            })
    rows.append(fleet_row(plan["scaling_fleet"]))
    parity = ab = None
    if reference:
        rows.extend(fleet_row(f) for f in plan["fleet_fleets"]
                    if f != plan["scaling_fleet"])
        # Bitwise parity at the acceptance point: every per-seed output of
        # the sharded, chunked sweep must equal the flat vmap exactly.
        seeds = list(range(plan["parity_fleet"]))
        fleet_out = simulator.run_fleet(cfg, seeds, mesh=mesh,
                                        chunk_size=chunk_size)
        batch_out = simulator.run_batch(cfg, seeds)
        max_dev = max(
            float(np.max(np.abs(np.asarray(fleet_out[k], np.float64)
                                - np.asarray(batch_out[k], np.float64))))
            for k in ("durations", "periods")
        )
        max_dev = max(max_dev, *(
            float(np.max(np.abs(fleet_out["totals"][k]
                                - batch_out["totals"][k])))
            for k in fleet_out["totals"]))
        parity = {
            "fleet": plan["parity_fleet"], "devices": devices,
            "max_dev": max_dev,
            "durations_equal": bool(
                np.array_equal(fleet_out["durations"],
                               batch_out["durations"])),
        }
        # Interleaved A/B at the acceptance point, medians over ab_reps:
        # the flat vmap is DRAM-bandwidth bound and so hostage to host
        # noise (2x swings between consecutive runs measured), while the
        # cache-resident fleet is stable -- alternating the two engines
        # rep-by-rep exposes both to the same noise windows, and the
        # median filters the outliers a best-of-N would cherry-pick.
        batch_s, fleet_s = [], []
        for _ in range(plan["ab_reps"]):
            batch_s.append(_time_call(
                lambda: simulator.run_batch(cfg, seeds), 1, warm=False))
            fleet_s.append(_time_call(
                lambda: simulator.run_fleet(cfg, seeds, mesh=mesh,
                                            chunk_size=chunk_size), 1,
                warm=False))
        fleet_n = plan["parity_fleet"]
        ab = {
            "fleet": fleet_n,
            "protocol": f"interleaved_median{plan['ab_reps']}",
            "run_batch_eps": fleet_n / float(np.median(batch_s)),
            "run_fleet_eps": fleet_n / float(np.median(fleet_s)),
            "run_batch_seconds": batch_s,
            "run_fleet_seconds": fleet_s,
        }
        ab["speedup"] = ab["run_fleet_eps"] / ab["run_batch_eps"]
    result = {
        "devices": devices,
        "rows": rows,
        "parity": parity,
        "ab": ab,
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        / 1024.0,
    }
    return result


def _worker(devices: int, tiny: bool, out_path: str) -> None:
    # Append to (not clobber) any operator-set XLA_FLAGS, replacing only a
    # pre-existing forced device count with this worker's.
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={devices}"])
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = _measure(devices, tiny, devices == REFERENCE_DEVICES)
    with open(out_path, "w") as fp:
        json.dump(result, fp)


# ---------------------------------------------------------------------------
# Orchestrator: forced-host workers on the CPU, in-process on accelerators.
# ---------------------------------------------------------------------------

def _accelerator_devices() -> int:
    """Devices of the accelerator this process would run on, 0 on the CPU.
    With ``JAX_PLATFORMS=cpu`` and JAX not yet imported it answers without
    importing JAX, so the CPU parent stays off it."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if "jax" not in sys.modules and platforms.split(",")[0] == "cpu":
        return 0
    import jax

    return 0 if jax.default_backend() == "cpu" else jax.device_count()


def _spawn_worker(devices: int, tiny: bool, out_path: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.bench_fleet", "--worker",
           "--devices", str(devices), "--out", out_path]
    if tiny:
        cmd.append("--tiny")
    proc = subprocess.run(cmd, cwd=_REPO_ROOT, env=env, capture_output=True,
                          text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_fleet worker (devices={devices}) failed:\n"
            f"{proc.stderr[-4000:]}")
    with open(out_path) as fp:
        return json.load(fp)


def _results(tiny: bool) -> tuple[list[dict], str]:
    """One result per device count, and the backend that measured them."""
    counts = _plan(tiny)["device_counts"]
    n_acc = _accelerator_devices()
    if n_acc:
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        counts = [n for n in counts if n <= n_acc] or [1]
        import jax

        return ([_measure(n, tiny, n == counts[-1]) for n in counts],
                jax.default_backend())
    with tempfile.TemporaryDirectory() as tmp:
        return ([_spawn_worker(n, tiny, os.path.join(tmp, f"worker_{n}.json"))
                 for n in counts], "cpu")


def run(tiny: bool = False) -> dict:
    from benchmarks import common

    rows, peak_rss, parity, ab = [], {}, None, None
    results, backend = _results(tiny)
    for result in results:
        rows.extend(result["rows"])
        peak_rss[str(result["devices"])] = result["peak_rss_mb"]
        parity = result["parity"] or parity
        ab = result["ab"] or ab

    return {
        "schema": SCHEMA,
        "tiny": tiny,
        **common.provenance(backend),
        "config": _sim_config(tiny),
        "rows": rows,
        "speedup_8dev_vs_run_batch": ab,
        "parity": parity,
        "peak_rss_mb": peak_rss,
    }


def validate(data: dict) -> None:
    """Schema check used by CI and tests: provenance stamped, throughput
    rows parseable, and the sharded sweep bitwise-equal to run_batch."""
    from benchmarks import common

    assert data["schema"] == SCHEMA
    common.validate_provenance(data)
    engines = {row["engine"] for row in data["rows"]}
    assert engines == {"run_batch", "run_fleet"}, engines
    for row in data["rows"]:
        assert row["episodes_per_sec"] > 0 and row["periods_per_sec"] > 0, row
    speed = data["speedup_8dev_vs_run_batch"]
    assert speed["speedup"] and speed["speedup"] > 0
    assert speed["protocol"].startswith("interleaved_median")
    assert len(speed["run_batch_seconds"]) == len(speed["run_fleet_seconds"])
    parity = data["parity"]
    assert parity["durations_equal"] is True
    assert parity["max_dev"] == 0.0, parity
    assert data["peak_rss_mb"], "peak-memory proxy missing"


def run_rows(tiny: bool = False) -> list[dict]:
    """benchmarks.run adapter: execute the study, write the artifact, and
    return ``name,us_per_call,derived`` rows.  Tiny runs land in
    artifacts/bench/; full runs refresh the repo-root trajectory."""
    from benchmarks import common

    data = run(tiny=tiny)
    validate(data)
    if tiny:
        common.save_artifact("bench_fleet_tiny", data)
    else:
        with open(os.path.join(_REPO_ROOT, DEFAULT_OUT), "w") as fp:
            json.dump(data, fp, indent=1, default=float)
            fp.write("\n")
    rows = []
    for row in data["rows"]:
        rows.append(common.row(
            f"fleet/{row['engine']}_dev{row['devices']}_S{row['fleet']}",
            row["seconds"] * 1e6,
            f"eps={row['episodes_per_sec']:.1f} "
            f"pps={row['periods_per_sec']:.0f}"))
    speed = data["speedup_8dev_vs_run_batch"]
    rows.append(common.row(
        "fleet/speedup_8dev_vs_run_batch", None,
        f"fleet={speed['fleet']} speedup={speed['speedup']:.2f}x "
        f"parity_max_dev={data['parity']['max_dev']:.1f}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (seconds instead of minutes)")
    ap.add_argument("--out", default=os.path.join(_REPO_ROOT, DEFAULT_OUT),
                    help=f"output path (default: {DEFAULT_OUT} at repo root)")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=1, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        _worker(args.devices, args.tiny, args.out)
        return
    data = run(tiny=args.tiny)
    validate(data)
    with open(args.out, "w") as fp:
        json.dump(data, fp, indent=1, default=float)
        fp.write("\n")
    for row in data["rows"]:
        print(f"{row['engine']} devices={row['devices']} "
              f"fleet={row['fleet']}: {row['episodes_per_sec']:.1f} eps "
              f"({row['periods_per_sec']:.0f} periods/s)")
    speed = data["speedup_8dev_vs_run_batch"]
    print(f"speedup @fleet={speed['fleet']}: {speed['speedup']:.2f}x "
          f"(parity max_dev={data['parity']['max_dev']})")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
