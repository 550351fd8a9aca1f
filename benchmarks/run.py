"""Benchmark entry point: one module per paper table/figure plus the
fleet-scale allocator study and the roofline summary.  Emits
``name,us_per_call,derived`` CSV rows (us empty where the metric is a derived
quantity rather than a timing).

  PYTHONPATH=src python -m benchmarks.run [--only tables,static,...] [--full]

``--only allocation`` without ``--full`` runs the tiny (CI-smoke) sizes,
including the schema-v2 market N-sweep at toy N -- same code path and schema
validation as the full 64..8192-service sweep, seconds instead of minutes.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: tables,static,longterm,scale,"
                         "allocation,fleet,cotrain,serve,fault,robust,"
                         "roofline")
    ap.add_argument("--full", action="store_true",
                    help="paper-sized long-term sims (slow)")
    args = ap.parse_args()
    enable_compile_cache()
    wanted = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0

    def section(name, fn):
        nonlocal failures
        if wanted is not None and name not in wanted:
            return
        try:
            from benchmarks import common
            common.emit(fn())
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name}/FAILED,,{traceback.format_exc().splitlines()[-1]}",
                  flush=True)

    from benchmarks import (allocator_scale, bench_allocation, bench_fault,
                            bench_fleet, bench_robust, bench_serve,
                            paper_figs_cotrain, paper_figs_longterm,
                            paper_figs_static, paper_tables, roofline)

    section("tables", paper_tables.run)
    section("static", paper_figs_static.run)
    section("longterm", lambda: paper_figs_longterm.run(full=args.full))
    section("scale", allocator_scale.run)
    section("allocation", lambda: bench_allocation.run_rows(tiny=not args.full))
    section("fleet", lambda: bench_fleet.run_rows(tiny=not args.full))
    section("cotrain", lambda: paper_figs_cotrain.run_rows(tiny=not args.full))
    section("serve", lambda: bench_serve.run_rows(tiny=not args.full))
    section("fault", lambda: bench_fault.run_rows(tiny=not args.full))
    section("robust", lambda: bench_robust.run_rows(tiny=not args.full))
    section("roofline", roofline.run)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
