"""``correct`` of the sweep cells at a size a test run can hold: the
program passes, the control (the reference in bfloat16, in the program's
place) fails, and a run whose timed path is broken underneath comes out
not correct, for each fault a sweep cell can have; the mixes are
deterministic from the seed."""
from __future__ import annotations

import io

import jax
import numpy as np
import pytest

from benchfaults import altered, broken, state_unchanged, tiny  # noqa: F401
from benchutil import run_cell

from bench import control, harness, tracing
from repro.fl import simulator

SWEEP = ["paper_selfish.sweep_fig12", "paper_coop.sweep_fig12"]


@pytest.mark.parametrize("workload", SWEEP)
def test_program_is_correct(tiny, workload):
    result = run_cell(tiny, workload, seed=2 ** 31 + 12345, seconds=0.25)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("workload", SWEEP)
def test_control_in_bfloat16_is_not_correct(tiny, workload):
    rows = control.readings(tiny, workload, [5], [7], 0.25,
                            require_tpu=False, out=io.StringIO())
    assert [r["side"] for r in rows] == ["program", "control"]
    assert rows[0]["correct"] and not rows[1]["correct"], rows


@pytest.mark.parametrize("workload", SWEEP)
@pytest.mark.parametrize("fault", [altered, state_unchanged])
def test_sweep_fault_is_not_correct(tiny, broken, fault, workload):
    broken(fault)
    assert not run_cell(tiny, workload, seed=11)["correct"]


@pytest.mark.parametrize("workload", SWEEP)
def test_sweep_half_the_fleet_left_out_is_not_correct(tiny, monkeypatch,
                                                      workload):
    original = simulator.run_fleet

    def half(cfg, seeds, net=None, **kw):
        out = original(cfg, seeds, net, **kw)
        s = len(seeds) // 2
        for key in ("durations", "finished", "periods"):
            out[key] = np.concatenate([out[key][:s], out[key][:s]])
        return out

    monkeypatch.setattr(simulator, "run_fleet", half)
    assert not run_cell(tiny, workload, seed=11)["correct"]


def test_a_traced_window_profiles_one_chunk_a_chip(tiny, monkeypatch):
    """The profiled call is one chunk of episodes, warmed in set-up so the
    window traces nothing anew; the calls after it are whole."""
    class Profiled(tracing.NullTracer):
        enabled = True

        def start(self):
            self.started = len(run.calls)

    monkeypatch.setattr(simulator, "FLEET_CHUNK", 8)
    cell = harness.resolve(tiny, SWEEP[0], 2 ** 31 + 21)
    run = harness.driver_module(cell).make(cell, jax.devices()[:1])
    tracer = Profiled()
    run.setup(tracer)
    run.window(1.0, tracer)        # raises where the window retraced
    sizes = [len(c["seeds"]) for c in run.calls]
    assert tracer.started == 0 and sizes[0] == 8
    assert len(sizes) > 1 and set(sizes[1:]) == {run.per_call} != {8}
    assert run.attempted == sum(sizes)


def test_mixes_are_deterministic_from_the_seed(tiny):
    def log(workload, seed):
        cell = harness.resolve(tiny, workload, seed)
        run = harness.driver_module(cell).make(cell, jax.devices()[:1])
        run.setup(tracing.NullTracer())
        run.window(0.25, tracing.NullTracer())
        return [c["seeds"] for c in run.calls]

    a, b = log(SWEEP[0], 2 ** 31 + 5), log(SWEEP[0], 2 ** 31 + 5)
    n = min(len(a), len(b))
    assert n > 0 and a[:n] == b[:n]
    assert log(SWEEP[0], 6)[0] != a[0]
