"""The readers of the program's own record of ``run_fleet`` calls: a
traced run of the sweep cell prints each of them, and a program without
that record gives them nothing to read, which leaves them out."""
from __future__ import annotations

import sys

import pytest

from benchfaults import tiny  # noqa: F401
from benchutil import ROOT, run_cell

from bench import harness

SWEEP = "paper_selfish.sweep_fig12"
SHARES = ("chunk_period_share.sweep", "live_row_share.sweep",
          "live_lane_share.sweep")
PROGRAM = SHARES + ("fleet_host_ms.sweep", "setup_compile_s")


def test_a_traced_sweep_run_reads_the_program_record(tiny):
    metrics = run_cell(tiny, SWEEP, seed=2 ** 31 + 99, seconds=0.5,
                       trace=True)["metrics"]
    for name in SHARES:
        assert 0 < metrics[name]["value"] <= 100, name
        assert metrics[name]["unit"] == "%"
    assert metrics["fleet_host_ms.sweep"]["value"] > 0
    assert metrics["setup_compile_s"]["value"] > 0
    # A chunk is live while any of its episodes is.
    assert metrics["chunk_period_share.sweep"]["value"] >= \
        metrics["useful_period_share.sweep"]["value"]


@pytest.mark.parametrize("name", PROGRAM)
def test_without_the_program_record_a_reader_reads_nothing(name,
                                                           monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # import fails
    reader = harness.metric_reader(harness.resolve(ROOT, SWEEP), name)
    assert reader.read(harness.Readings(counters={"calls": 3})) is None


@pytest.mark.parametrize("name", ("live_row_share.sweep",
                                  "live_lane_share.sweep"))
def test_a_share_of_a_call_without_a_launch_shape_reads_nothing(name):
    """A policy whose solves share no one shape (``policy.launch_shape``
    is None) records no launched rows or lanes."""
    from repro import obs

    obs.record_call({"live_rows": 5, "rows_in_live_chunks": None,
                     "live_lanes": 9, "lanes_of_live_rows": None})
    reader = harness.metric_reader(harness.resolve(ROOT, SWEEP), name)
    assert reader.read(harness.Readings(counters={"calls": 1})) is None
