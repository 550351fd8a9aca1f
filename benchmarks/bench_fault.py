"""Fault-injection benchmark -> repo-root ``BENCH_fault.json``.

``BENCH_serve.json`` pinned the healthy serving path; this artifact pins the
*degraded* one: seeded chaos storms (``repro.chaos``) over the live daemon
at market capacities N in {16, 64, 256}, measuring what the hardened paths
actually cost when heartbeat, solver, checkpoint, and admission faults all
fire together -- decisions lost to restarts, recovery time (consecutive
non-fresh serves per outage), stale/degraded/fallback rates, and the
trajectory digest run twice to prove the storm replays bitwise from its
seed.  A separate checkpoint-restore drill corrupts the newest snapshot
behind an intact COMMIT and verifies the restart falls back to the older
step, counts the skip, and keeps serving finite decisions.

Every counter in the artifact is a degradation the stack refused to take
silently; the invariant harness (budget conservation, finite outputs,
retired slots never allocated, bitwise replay) must hold in every row.

Usage:
  PYTHONPATH=src python -m benchmarks.bench_fault [--tiny] [--out PATH]

``--tiny`` shrinks capacities/periods for the CI smoke step (same schema,
same validation path).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import tempfile

from repro.compile_cache import enable_compile_cache

SCHEMA = "bench_fault/v1"
DEFAULT_OUT = "BENCH_fault.json"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plan(tiny: bool) -> dict:
    if tiny:
        return {"capacities": [4, 8], "periods": 14, "k_max": 8,
                "rounds_required": 250, "seed": 42, "save_every": 3}
    return {"capacities": [16, 64, 256], "periods": 40, "k_max": 16,
            "rounds_required": 400, "seed": 42, "save_every": 5}


def _storm_cfg(capacity: int, plan: dict):
    from repro.fl.control_plane import ControlPlaneConfig

    return ControlPlaneConfig(
        capacity=capacity, k_max=plan["k_max"], policy="coop",
        warm_start=True, rounds_required=plan["rounds_required"],
        channel_process="gauss_markov", heartbeat_timeout_periods=2, seed=0)


def _storm_row(capacity: int, plan: dict) -> dict:
    """One full-catalogue storm at this capacity, run twice from the same
    seed: the second run must land on the identical digest."""
    from repro.chaos.engine import run_storm

    cfg = _storm_cfg(capacity, plan)

    def once(ckpt_dir: str) -> dict:
        return run_storm(cfg, seed=plan["seed"], n_periods=plan["periods"],
                         checkpoint_dir=ckpt_dir,
                         save_every=plan["save_every"], max_stale_streak=4)

    with tempfile.TemporaryDirectory() as d1:
        r1 = once(d1)
    with tempfile.TemporaryDirectory() as d2:
        r2 = once(d2)
    m = r1["metrics"]
    return {
        "capacity": capacity,
        "periods": plan["periods"],
        "seed": plan["seed"],
        "digest": r1["digest"],
        "digest_repeat_equal": bool(r1["digest"] == r2["digest"]),
        "n_events": r1["n_events"],
        "restarts": r1["restarts"],
        "served": r1["served"],
        "decisions_lost": r1["decisions_lost"],
        "recovery": r1["recovery"],
        "stale_rate": r1["served"]["stale"] / plan["periods"],
        "degraded_rate": r1["served"]["degraded"] / plan["periods"],
        "solver_fallbacks": m["solver_fallbacks"],
        "nonfinite_decisions": m["nonfinite_decisions"],
        "carry_repairs": m["carry_repairs"],
        "checkpoint_skips": m["checkpoint_skips"],
        "admit_retries": m["admit_retries"],
        "heartbeat_drops": m["heartbeat_drops"],
        "invariants_ok": bool(all(v["ok"]
                                  for v in r1["invariants"].values())),
        "invariants_failed": [k for k, v in r1["invariants"].items()
                              if not v["ok"]],
    }


def _restore_drill(plan: dict) -> dict:
    """Checkpoint-restore integrity: corrupt the newest snapshot behind its
    intact COMMIT, restart, and verify the daemon falls back to the older
    step, counts the skip, and keeps serving finite decisions."""
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.launch import allocd

    cfg = _storm_cfg(4, plan)

    async def warm_up(daemon, periods):
        daemon.submit(allocd.Admit("a", 3))
        daemon.submit(allocd.Admit("b", 2))
        for _ in range(periods):
            await daemon.step_period()
        await daemon.close()

    async def resume_and_serve(daemon, periods):
        finite = True
        for _ in range(periods):
            d = await daemon.step_period()
            finite &= bool(np.all(np.isfinite(d.b))
                           and np.all(np.isfinite(d.f)))
        await daemon.close()
        return finite

    with tempfile.TemporaryDirectory() as ckpt:
        daemon = allocd.AllocDaemon(cfg, manager=CheckpointManager(ckpt),
                                    save_every=2)
        asyncio.run(warm_up(daemon, 6))
        mgr = daemon.manager
        steps = mgr.all_steps()
        newest = steps[-1]
        shard = os.path.join(mgr._step_dir(newest), "shard_0000.npz")
        with open(shard, "r+b") as f:
            f.truncate(os.path.getsize(shard) // 2)
        daemon2 = allocd.AllocDaemon(cfg, manager=CheckpointManager(ckpt),
                                     save_every=2)
        finite = asyncio.run(resume_and_serve(daemon2, 3))
        return {
            "steps_before": [int(s) for s in steps],
            "corrupted_step": int(newest),
            "resumed": bool(daemon2.resumed),
            "restored_period": int(daemon2.plane.period) - 3,
            "skipped": [int(s) for s, _ in daemon2.manager.last_skipped],
            "checkpoint_skips": int(
                daemon2.plane.metrics["checkpoint_skips"]),
            "served_finite_after_restore": bool(finite),
        }


def run(tiny: bool = False) -> dict:
    from benchmarks import common

    plan = _plan(tiny)
    rows = [_storm_row(capacity, plan) for capacity in plan["capacities"]]
    return {
        "schema": SCHEMA,
        "tiny": tiny,
        **common.provenance(),
        "plan": plan,
        "rows": rows,
        "restore_drill": _restore_drill(plan),
    }


def validate(data: dict) -> None:
    """Schema check used by CI and tests: provenance stamped, every storm
    row deterministic (digest equal across two runs from the same seed) and
    invariant-clean, the served stream fully accounted, and the restore
    drill actually skipping past the corrupted snapshot."""
    from benchmarks import common

    assert data["schema"] == SCHEMA
    common.validate_provenance(data)
    assert data["rows"], "no storm rows"
    for row in data["rows"]:
        assert row["digest_repeat_equal"] is True, (
            f"storm at N={row['capacity']} is not replayable from its seed")
        assert row["invariants_ok"] is True, (
            f"invariants violated at N={row['capacity']}: "
            f"{row['invariants_failed']}")
        s = row["served"]
        assert s["fresh"] + s["stale"] + s["degraded"] == row["periods"], row
        assert row["decisions_lost"] >= 0, row
        assert row["n_events"] > 0, "storm injected nothing"
        assert row["recovery"]["outages"] >= 0
        assert len(row["digest"]) == 64
    drill = data["restore_drill"]
    assert drill["resumed"] is True, drill
    assert drill["corrupted_step"] in drill["skipped"], (
        "corrupted snapshot was not skipped")
    assert drill["checkpoint_skips"] >= 1, (
        "checkpoint skip was absorbed silently")
    assert drill["restored_period"] < drill["corrupted_step"], (
        "restore did not fall back to an older step")
    assert drill["served_finite_after_restore"] is True, drill


def run_rows(tiny: bool = False) -> list[dict]:
    """benchmarks.run adapter: execute, write the artifact, emit CSV rows."""
    from benchmarks import common

    data = run(tiny=tiny)
    validate(data)
    if tiny:
        common.save_artifact("bench_fault_tiny", data)
    else:
        with open(os.path.join(_REPO_ROOT, DEFAULT_OUT), "w") as fp:
            json.dump(data, fp, indent=1, default=float)
            fp.write("\n")
    rows = []
    for row in data["rows"]:
        s = row["served"]
        rows.append(common.row(
            f"fault/storm_N{row['capacity']}", None,
            f"fresh={s['fresh']}/{row['periods']} lost={row['decisions_lost']} "
            f"restarts={row['restarts']} "
            f"recovery_max={row['recovery']['max_periods']}p "
            f"fallbacks={row['solver_fallbacks']} "
            f"repairs={row['carry_repairs']} deterministic="
            f"{row['digest_repeat_equal']}"))
    drill = data["restore_drill"]
    rows.append(common.row(
        "fault/restore_drill", None,
        f"skipped_step={drill['corrupted_step']} "
        f"restored_before={drill['restored_period']} "
        f"finite={drill['served_finite_after_restore']}"))
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
        s = row["served"]
        print(f"N={row['capacity']}: fresh={s['fresh']} stale={s['stale']} "
              f"degraded={s['degraded']} lost={row['decisions_lost']} "
              f"restarts={row['restarts']} "
              f"deterministic={row['digest_repeat_equal']} "
              f"invariants_ok={row['invariants_ok']}")
    drill = data["restore_drill"]
    print(f"restore drill: corrupted step {drill['corrupted_step']} skipped, "
          f"resumed at {drill['restored_period']}, "
          f"finite={drill['served_finite_after_restore']}")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
