"""Shared benchmark utilities: wall-clock timing of jitted callables, the
canonical `name,us_per_call,derived` CSV row format, and the provenance
stamp every BENCH_*.json artifact carries."""
from __future__ import annotations

import datetime
import json
import os
import subprocess
import time


def provenance(backend: str | None = None) -> dict:
    """Stamp for BENCH_*.json artifacts: the commit and date the numbers were
    measured at plus the jax backend that produced them, so the bench
    trajectory is machine-reconstructable from the artifacts alone.

    ``dirty`` records whether the working tree had uncommitted changes at
    measurement time -- a PR's refreshed artifact is necessarily stamped
    with the parent commit plus ``dirty: true`` (the measuring tree IS the
    commit under review); ``dirty: false`` means the stamped commit alone
    reproduces the numbers.

    ``backend`` names the JAX backend that measured the numbers; left None it
    is this process's own (which imports JAX).
    """
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _git(*args):
        return subprocess.run(["git", *args], capture_output=True, text=True,
                              cwd=repo, timeout=10).stdout

    try:
        commit = _git("rev-parse", "HEAD").strip() or "unknown"
        dirty = bool(_git("status", "--porcelain").strip())
    except (OSError, subprocess.SubprocessError):
        commit, dirty = "unknown", False
    if backend is None:
        import jax

        backend = jax.default_backend()
    return {
        "commit": commit,
        "dirty": dirty,
        "date": datetime.datetime.now(datetime.timezone.utc)
                        .strftime("%Y-%m-%dT%H:%M:%SZ"),
        "backend": backend,
    }


def validate_provenance(data: dict) -> None:
    """Assert the artifact carries the stamp fields (schema checkers call
    this so an unstamped artifact fails CI, not a later archaeology dig)."""
    for key in ("schema", "commit", "date", "backend"):
        assert isinstance(data.get(key), str) and data[key], (
            f"bench artifact missing provenance field {key!r}")
    assert isinstance(data.get("dirty"), bool), (
        "bench artifact missing provenance field 'dirty'")
    assert "T" in data["date"], "date must be ISO-8601 UTC"


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median-of-iters wall time in microseconds (blocks on outputs)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def row(name: str, us_per_call: float | None, derived: str) -> dict:
    return {"name": name, "us_per_call": us_per_call, "derived": derived}


def emit(rows: list[dict]) -> None:
    for r in rows:
        us = "" if r["us_per_call"] is None else f"{r['us_per_call']:.1f}"
        print(f"{r['name']},{us},{r['derived']}")


def save_artifact(name: str, data) -> str:
    os.makedirs("artifacts/bench", exist_ok=True)
    path = f"artifacts/bench/{name}.json"
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)
    return path
