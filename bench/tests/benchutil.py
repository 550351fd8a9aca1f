"""Helpers of the benchmark's CPU tests: a copy of the benchmark at a size
a test run can hold, and a run of one of its cells without the chip."""
from __future__ import annotations

import io
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Small enough for seconds on a CPU; the widths of the paper's cell are cut
# only here, never in the benchmark's own files.
TINY_CONFIG = {"k_max": 12, "max_periods": 200, "intra_backend": "reference"}
TINY_TRAFFIC = {"sweep": {"services_per_episode": 4, "episodes_per_chip": 16,
                          "check_episodes": 8}}


def tiny_copy(dst: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dst``, cut to the
    tiny size, with the program's ``src`` linked beside them."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(ROOT / "src")
    for path in (dst / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(TINY_CONFIG)
        path.write_text(json.dumps(cfg))
    for path in (dst / "bench" / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        tr.update(TINY_TRAFFIC[tr["driver"]])
        path.write_text(json.dumps(tr))
    return dst


def run_cell(root: Path, workload: str, seed: int, seconds: float = 0.5,
             trace: bool = False) -> dict:
    """One run of a cell on whatever JAX finds; the parsed result line."""
    from bench import harness

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(root, workload, seed, seconds, trace,
                          time.perf_counter(), require_tpu=False, out=out,
                          err=err)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
