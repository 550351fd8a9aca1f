"""Every cell of BENCHMARK.json resolves its configuration, mix, driver,
reference, limits and metric readers by name, and the file keeps to the
benchmark's naming and budget rules."""
from __future__ import annotations

import json
import re

import pytest

from benchutil import ROOT

from bench import harness

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_every_file_by_name(workload):
    cell = harness.resolve(ROOT, workload)
    assert harness.driver_module(cell).make
    assert harness.reference_module(cell).allocate
    assert set(cell.limits["limits"])
    for m in cell.per_layer:
        assert callable(harness.metric_reader(cell, m["name"]).read)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names, (m["name"], workload)


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in seen
            seen.add(entry["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in layer for layer in layers)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")


def test_four_chip_share_and_check_budget():
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(BENCH["workloads"]) // 2)
    seconds = BENCH["run_seconds"]
    full = (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200
    assert 1 <= seconds <= 51 and full <= 43200


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for(ROOT, "TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peaks_for(ROOT, "cpu")
