#!/usr/bin/env python3
"""Run one cell of the allocator's benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout; everything it needs is found by the names given there (see
``bench/harness.py``).  Set-up builds the system from the seed and warms
every shape the window uses; the window then runs for ``--seconds``.  With
``--trace 0`` the result line holds the cell's end-to-end metrics; with
``--trace 1`` a profiled run gives its per-layer metrics, the device's busy
and window seconds, and a breakdown.  After the window the outputs are
compared with the plain reference; the numbers compared are printed beside
their limits, as the last lines on standard error and under ``checks`` in
the result, which is the last line on standard output.

Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.  JAX's compilation cache is kept in ``.jax_cache`` at
the root of the checkout, so only the first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    here = str(ROOT / "bench")
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if p and os.path.abspath(p) != here]
    try:
        from bench import harness

        return harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), T_START)
    except Exception as exc:  # a run that cannot finish prints no result
        traceback.print_exc()
        print(f"bench: {type(exc).__name__}: {exc}", file=sys.stderr,
              flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
