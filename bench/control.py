#!/usr/bin/env python3
"""Readings from which the limits of ``correct`` are set, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds <s>

For each of ``--seeds`` it makes a run of the cell, as ``bench/run.py``
does, with a window of ``--seconds``, and prints the numbers ``correct``
compares beside their limits (the sound program's readings, which must come
out correct).  For each of ``--control-seeds`` it does the same but puts
the reference, computed in bfloat16 (the precision below the
configuration's float32), in the program's place: the control's readings,
which must come out not correct.  All seeds run in one process, so the
cell compiles once.  One JSON line per reading on standard output.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(root: Path, workload: str, seeds, control_seeds, seconds: float,
             require_tpu: bool = True, out=sys.stdout) -> list[dict]:
    import jax.numpy as jnp

    from bench import harness, tracing

    rows = []
    for seed, is_control in ([(s, False) for s in seeds]
                             + [(s, True) for s in control_seeds]):
        cell = harness.resolve(root, workload, seed)
        devices = harness.devices_for(cell, require_tpu)
        run, _ = harness.drive(cell, devices, seconds, tracing.NullTracer(),
                               time.perf_counter())
        t0 = time.perf_counter()
        correct, checks = harness.judge(
            cell, run, jnp.bfloat16 if is_control else None)
        row = {"workload": workload, "seed": seed,
               "side": "control" if is_control else "program",
               "correct": correct, "checks": checks,
               "check_s": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
    return rows


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    here = str(ROOT / "bench")
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if p and os.path.abspath(p) != here]

    def ints(s):
        return [int(x) for x in s.split(",") if x]

    rows = readings(ROOT, args.workload, ints(args.seeds),
                    ints(args.control_seeds), args.seconds)
    wrong = [r["seed"] for r in rows
             if r["correct"] != (r["side"] == "program")]
    if wrong:
        print(f"control: seeds {wrong} came out on the wrong side",
              file=sys.stderr, flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
