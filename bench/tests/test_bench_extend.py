"""A configuration, a traffic mix and a per-layer metric are added by new
files and new entries alone: nothing already there is edited."""
from __future__ import annotations

import hashlib
import json
import shutil

from benchutil import ROOT

from bench import harness


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    cfg = json.loads((tmp_path / "bench/configs/paper_selfish.json")
                     .read_text())
    cfg.update(name="paper_coop_cold", policy="coop", warm_start=False,
               intra_backend="reference")
    (tmp_path / "bench/configs/paper_coop_cold.json").write_text(
        json.dumps(cfg))
    mix = json.loads((tmp_path / "bench/traffic/sweep_fig12.json")
                     .read_text())
    mix.update(p_arrive=2.0)
    (tmp_path / "bench/traffic/sweep_dense.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/paper_coop_cold.sweep_dense.json").write_text(
        json.dumps({"limits": {"duration_mismatch_share": 0.05}}))
    (tmp_path / "bench/metrics/calls_seen.sweep.py").write_text(
        "def read(r):\n    return r.counters.get('calls')\n")

    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "paper_coop_cold",
                             "source": "https://arxiv.org/abs/2101.03627",
                             "file": "bench/configs/paper_coop_cold.json",
                             "reduced": [], "why": "cold DISBA"})
    bench["workloads"].append({"name": "paper_coop_cold.sweep_dense",
                               "config": "paper_coop_cold",
                               "traffic": "sweep_dense", "chips": 1,
                               "why": "denser arrivals"})
    bench["per_layer"].append({"name": "calls_seen.sweep", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "fleet engine",
                               "moves": "episodes_per_s",
                               "workloads": ["paper_coop_cold.sweep_dense"]})
    for m in bench["end_to_end"]:
        if m["name"] == "episodes_per_s":
            m["workloads"].append("paper_coop_cold.sweep_dense")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.resolve(tmp_path, "paper_coop_cold.sweep_dense")
    assert cell.config["warm_start"] is False
    assert cell.config["policy"] == "coop"
    assert cell.traffic["p_arrive"] == 2.0
    assert harness.driver_module(cell).make
    assert harness.reference_module(cell).allocate
    names = [m["name"] for m in cell.per_layer]
    assert "calls_seen.sweep" in names
    assert "episodes_per_s" in [m["name"] for m in cell.end_to_end]
    reader = harness.metric_reader(cell, "calls_seen.sweep")
    assert reader.read(harness.Readings(counters={"calls": 7})) == 7
    after = _digests(tmp_path)
    assert all(after[k] == v for k, v in before.items())
