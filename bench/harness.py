"""The benchmark's general machinery, driven by ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Everything that belongs to one of them sits in a file of its own, found by
name:

* ``bench/configs/<config>.json``   the deployment (cell statistics, policy,
                                     solver backend);
* ``bench/traffic/<traffic>.json``  the mix; its ``driver`` key names the
                                     general generator in
                                     ``bench/drivers/<driver>.py``;
* ``bench/reference/<policy>.py``   the plain reference of the policy;
* ``bench/limits/<cell>.json``      the limits of the numbers ``correct``
                                     compares, with the readings they were
                                     set from;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric;
* ``bench/costs/<kernel>.py``       operations and bytes of a kernel call.

A driver module exposes ``make(cell, devices) -> run`` where ``run`` has
``setup(tracer)``, ``window(seconds, tracer)``, ``end_to_end()``,
``readings()``, ``attempted``, ``failed``, ``free()``, ``check(reference)``
and ``control(reference, dtype)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any


class BenchError(Exception):
    """A cell that cannot run here: unknown name, missing file, no chip."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    seed: int
    root: Path


def load_module(path: Path) -> ModuleType:
    """Import a file by path: metric readers carry dots in their names."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_dyn_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(root: Path, workload: str, seed: int = 0) -> Cell:
    """Everything one cell needs, by the names ``BENCHMARK.json`` gives."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {workload!r} names unknown config "
                         f"{w['config']!r}")
    config = read_json(root / configs[w["config"]]["file"])
    bench_dir = root / "bench"
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = read_json(bench_dir / "limits" / f"{workload}.json")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic, limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        seed=int(seed), root=root)


def driver_module(cell: Cell) -> ModuleType:
    return load_module(cell.root / "bench" / "drivers"
                       / f"{cell.traffic['driver']}.py")


def reference_module(cell: Cell) -> ModuleType:
    return load_module(cell.root / "bench" / "reference"
                       / f"{cell.config['policy']}.py")


def metric_reader(cell: Cell, name: str) -> ModuleType:
    return load_module(cell.root / "bench" / "metrics" / f"{name}.py")


def kernel_cost(cell: Cell, kernel: str) -> ModuleType:
    return load_module(cell.root / "bench" / "costs" / f"{kernel}.py")


def peaks_for(root: Path, device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip; an unknown kind is an
    error, never a default."""
    table = read_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def check_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; refuses anything else."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform {platform!r}); the "
                         f"benchmark measures only on the chip")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak memory
    of the fullest chip (read after the window)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if peaks:
        rec["memory_peak_bytes"] = max(peaks)
    return rec


@dataclasses.dataclass
class Readings:
    """What the per-layer readers read: host spans (seconds) and counters
    from the benchmark's own files, the reduced device trace, and the
    kernel calls the window made with their shapes."""

    spans: dict[str, list[float]] = dataclasses.field(default_factory=dict)
    counters: dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Any = None
    kernel_calls: dict[str, dict] = dataclasses.field(default_factory=dict)
    peaks: dict | None = None
    cell: Cell | None = None

    def cost(self, kernel: str):
        """(flops, bytes) of one call of ``kernel`` at its recorded shape."""
        mod = kernel_cost(self.cell, kernel)
        return mod.cost(**self.kernel_calls[kernel])


def read_per_layer(cell: Cell, readings: Readings) -> dict:
    out = {}
    for m in cell.per_layer:
        value = metric_reader(cell, m["name"]).read(readings)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def compare(limits: dict, numbers: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; ``correct`` when every
    number is at or under its limit."""
    checks, ok = {}, True
    for name, value in numbers.items():
        if name not in limits["limits"]:
            raise BenchError(f"no limit for compared number {name!r}")
        limit = float(limits["limits"][name])
        value = float(value)
        good = value <= limit          # NaN compares False: not correct
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    missing = set(limits["limits"]) - set(numbers)
    if missing:
        raise BenchError(f"limits name numbers the check did not give: "
                         f"{sorted(missing)}")
    return ok, checks


def devices_for(cell: Cell, require_tpu: bool = True) -> list:
    if require_tpu:
        return check_devices(cell.chips)
    import jax

    return jax.devices()[:cell.chips]


def drive(cell: Cell, devices, seconds: float, tracer, t_start: float):
    """Set-up and the window: the run, and the set-up's seconds from
    ``t_start``."""
    run = driver_module(cell).make(cell, devices)
    run.setup(tracer)
    setup_s = time.perf_counter() - t_start
    run.window(float(seconds), tracer)
    return run, setup_s


def judge(cell: Cell, run, control_dtype=None) -> tuple[bool, dict]:
    """Free the program's state, then compare what the window produced
    with the plain reference.  With ``control_dtype`` the reference
    computed in that precision stands in the program's place."""
    run.free()
    ref = reference_module(cell)
    numbers = (run.check(ref) if control_dtype is None
               else run.control(ref, control_dtype))
    return compare(cell.limits, numbers)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             out=sys.stdout, err=sys.stderr) -> int:
    """One run of one cell; prints the result line; returns the exit code."""
    from bench import tracing

    cell = resolve(root, workload, seed)
    devices = devices_for(cell, require_tpu)
    tracer = tracing.Tracer(root, workload, seed, devices) if trace \
        else tracing.NullTracer()
    run, setup_s = drive(cell, devices, seconds, tracer, t_start)
    device = device_record(devices)
    metrics: dict[str, dict] = {}
    breakdown = None
    if trace:
        readings = run.readings()
        readings.cell = cell
        readings.trace = tracer.reduce()
        if devices[0].platform == "tpu":
            readings.peaks = peaks_for(root, devices[0].device_kind)
        metrics = read_per_layer(cell, readings)
        if readings.trace is not None:
            device["busy_s"] = readings.trace.busy_s
            device["window_s"] = readings.trace.window_s
            breakdown = readings.trace.breakdown()
    else:
        e2e = run.end_to_end()
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for name in units:
            if name not in e2e:
                raise BenchError(f"the {cell.traffic['driver']} driver gave "
                                 f"no {name!r}")
            metrics[name] = {"value": float(e2e[name]), "unit": units[name]}
    attempted, failed = run.attempted, run.failed
    correct, checks = judge(cell, run)
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err,
              flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0
