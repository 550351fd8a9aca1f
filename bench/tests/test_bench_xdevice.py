"""The cross-device cell ``xdevice_selfish.sweep_xdevice``: its client axis
spans several lane groups, so the kernels' row blocks follow the lanes
(``kernels/tiling.row_tile``).  At a size a test run can hold, the cell
agrees with the plain reference with its launches split into several grid
steps, and ``vmem_block_share.sweep`` reads the VMEM bytes of the widest
launch's grid step from the call record."""
from __future__ import annotations

import json

import numpy as np
import pytest

from benchfaults import _fresh_step_caches, tiny  # noqa: F401
from benchutil import ROOT, run_cell

from bench import harness
from repro import obs
from repro.core import policy as policy_mod
from repro.fl import simulator
from repro.kernels import bisect_alloc, dual_demand, market_clear, tiling

CELL = "xdevice_selfish.sweep_xdevice"
SHARE = "vmem_block_share.sweep"
ROW_BLOCKS = {"bisect_alloc": bisect_alloc.row_blocks,
              "dual_demand": dual_demand.row_blocks,
              "mbdf_demand": market_clear.mbdf_row_blocks}


def test_the_cell_is_the_cross_device_deployment():
    cell = harness.resolve(ROOT, CELL)
    net = cell.config["network"]
    assert cell.config["policy"] == "selfish"
    assert cell.config["k_max"] == 1792 == 14 * tiling.LANES
    assert cell.config["k_max"] >= net["mean_clients"] + 5 * net[
        "var_clients"] ** 0.5
    assert net["total_bandwidth_mhz"] == 100.0
    assert cell.traffic["episodes_per_chip"] == 128


@pytest.fixture
def wide(tiny, monkeypatch):
    """The tiny copy with the cell's client axis at 3 lane groups on the
    kernels (interpreted), and a VMEM budget small enough that a chunk's
    rows take several grid steps."""
    path = tiny / "bench" / "configs" / "xdevice_selfish.json"
    cfg = json.loads(path.read_text())
    cfg.update(k_max=384, intra_backend="pallas")
    cfg["network"].update(mean_clients=250.0, var_clients=1406.25)
    path.write_text(json.dumps(cfg))
    monkeypatch.setattr(tiling, "VMEM_BUDGET",
                        bisect_alloc.row_blocks(32, 384).block_bytes)
    _fresh_step_caches()
    yield tiny
    monkeypatch.undo()
    _fresh_step_caches()


def test_a_wide_cell_in_several_row_blocks_agrees_with_the_reference(wide):
    result = run_cell(wide, CELL, seed=2 ** 31 + 4321, seconds=0.25)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    work = obs.calls()[-1]
    assert work["lanes"] == 384
    assert work["rows"] // work["row_tile"] >= 2
    assert 0 < work["block_bytes"] <= work["vmem_budget_bytes"]


@pytest.mark.parametrize("policy,warm,k,kernel", [
    ("selfish", False, 128, "bisect_alloc"),
    ("selfish", False, 1792, "bisect_alloc"),
    ("selfish", False, 4096, "mbdf_demand"),
    ("coop", True, 128, "dual_demand"),
    ("coop", True, 1792, "dual_demand"),
    ("es", False, 1792, "bisect_alloc")])
def test_block_bytes_of_the_widest_launch_from_its_row_tile(policy, warm, k,
                                                            kernel):
    """A chunk of 64 episodes of 10 services: the call record's
    ``block_bytes`` is the grid step of the widest kernel's launch (its
    module's rule) at ``launch_tile`` rows, and the share reads it over the
    budget.  At 128
    lanes the tile is the accepted cells' 320 rows."""
    cfg = simulator.SimConfig(policy=policy, warm_start=warm,
                              intra_backend="pallas", n_services_total=10,
                              k_max=k, max_periods=400,
                              collect_history=False)
    shape = dict(intra_backend="pallas", warm_start=warm, n=10, k=k,
                 batch=simulator.FLEET_CHUNK)
    tile = policy_mod.launch_tile(policy, **shape)
    launch = ROW_BLOCKS[kernel](640, k)
    assert tile == launch.tile
    if k == 128:
        assert tile == 320
    seeds = simulator.FLEET_CHUNK
    out = {"periods": np.full(seeds, 200),
           "totals": {"n_active": np.full(seeds, 2000),
                      "n_clients": np.full(seeds, 2000 * 50)}}
    work = simulator.fleet_work(cfg, out, n_dev=1, chunk=seeds, n_chunks=1,
                                padded_to=seeds)
    want = launch.block_bytes
    assert work["block_bytes"] == want
    assert work["vmem_budget_bytes"] == tiling.VMEM_BUDGET
    obs.record_call(work)
    reader = harness.metric_reader(harness.resolve(ROOT, CELL), SHARE)
    value = reader.read(harness.Readings(counters={"calls": 1}))
    assert value == pytest.approx(100.0 * want / tiling.VMEM_BUDGET)
    assert 0 < value <= 100


def test_a_call_record_without_block_bytes_reads_nothing():
    """The record of a program that keeps no VMEM account, or of a policy
    whose kernels fold no rows, leaves the share out."""
    obs.record_call({"block_bytes": None, "vmem_budget_bytes": None})
    reader = harness.metric_reader(harness.resolve(ROOT, CELL), SHARE)
    assert reader.read(harness.Readings(counters={"calls": 1})) is None
    obs.record_call({"rows": 640})
    assert reader.read(harness.Readings(counters={"calls": 1})) is None
