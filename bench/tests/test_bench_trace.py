"""The reduction from a trace to busy time, idle share, kernel time,
roofline share and the breakdown, on a small synthetic trace."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from benchutil import ROOT

from bench import harness, tracing
from bench.tracing import Event, HostSpans, TraceSummary


def _summary():
    # Window 0..10 s.  Device 0 busy 1-3 (kernel) and 2-4 (overlapping
    # op), 6-7; device 1 busy 0-5.  The host waited 4-6 and ticked 0-4.
    ops = {0: [Event("fusion.1", 1, 3), Event("mbdf_kernel", 2, 4),
               Event("mbdf_kernel", 6, 7)],
           1: [Event("fusion.1", 0, 5)]}
    modules = {0: [Event("jit_step(1)", 1, 4), Event("jit_step(2)", 6, 7)],
               1: []}
    host = HostSpans([Event(tracing.WINDOW, 0, 10),
                      Event("bench.tick", 0, 4), Event("bench.wait", 4, 6),
                      Event("bench.step_call", 1, 4)])
    return TraceSummary(window=(0.0, 10.0), ops=ops, modules=modules,
                        host=host)


def test_busy_union_and_idle_share():
    s = _summary()
    assert tracing.busy_seconds(s.ops[0], s.window) == pytest.approx(4.0)
    assert s.busy_s == pytest.approx((4.0 + 5.0) / 2)
    assert s.window_s == 10.0
    assert s.idle_share() == pytest.approx(1 - 4.5 / 10)
    assert tracing.idle_gaps(s.ops[0], s.window) == [(0, 1), (4, 6), (7, 10)]


def test_kernel_and_program_time():
    s = _summary()
    kernel = s.op_events("mbdf")
    assert len(kernel) == 2
    assert sum(e.end - e.start for e in kernel) == pytest.approx(3.0)
    steps = s.module_events(r"^jit_step\b")
    assert [e.end - e.start for e in steps] == [3, 1]


def test_breakdown_names_gaps_by_host_span():
    b = _summary().breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(3.5)]
    gaps = dict(b["idle_gaps"])
    assert gaps["bench.wait"] == pytest.approx(2 / 2)
    assert gaps["bench.tick"] == pytest.approx(1 / 2)
    assert gaps["outside any bench span"] == pytest.approx((3 + 5) / 2)


def test_roofline_share_of_a_kernel():
    from bench.roofline import share

    cell = harness.resolve(ROOT, "paper_selfish.sweep_fig12")
    r = harness.Readings(trace=_summary(), cell=cell,
                         peaks={"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
                         kernel_calls={"mbdf_demand": dict(n=8, k=4, m=2,
                                                           iters=3)})
    flops, nbytes = r.cost("mbdf_demand")
    assert flops == 8 * 2 * (3 * (6 * 4 + 8) + 6 * 4)
    assert share(r, "mbdf_demand", "mbdf") == pytest.approx(
        100 * max(flops, nbytes) / 1e9 / 1.5)
    assert share(r, "bisect_alloc", "bisect") is None   # nothing to read


def test_dual_demand_cost_counts_the_kernel_arithmetic():
    cell = harness.resolve(ROOT, "paper_coop.sweep_fig12")
    cost = harness.kernel_cost(cell, "dual_demand").cost
    flops, nbytes = cost(n=10, k=48, iters=24)
    assert flops == 10 * (24 * (6 * 48 + 8) + 19 * 48 + 31)
    assert nbytes == 4 * (2 * 10 * 48 + 3 * 10)
    assert cost(n=10, k=48, iters=48)[0] > flops


def test_dual_demand_roofline_counts_the_launches_of_a_period():
    """Two periods of the cell's warm dual solve in a synthetic trace: the
    least time of ``WARM_ITERS`` launches at ``WARM_INNER_ITERS`` trips and
    one at ``BISECT_ITERS`` over a chunk's 640 rows of 48 clients, twice,
    over the launches' time; nothing where nothing can be read."""
    from repro.core.disba import BISECT_ITERS, WARM_INNER_ITERS, WARM_ITERS

    cell = harness.resolve(ROOT, "paper_coop.sweep_fig12")
    launches = 2 * (WARM_ITERS + 1)
    ops = {0: [Event("%dual_demand.3", 10.0 * i, 10.0 * i + 2.0)
               for i in range(launches)] + [Event("%fusion.1", 0.0, 1.0)]}
    trace = TraceSummary(window=(0.0, 10.0 * launches), ops=ops, modules={},
                         host=[])
    r = harness.Readings(trace=trace, cell=cell,
                         peaks={"flops_per_s": 1e9, "hbm_bytes_per_s": 1e8},
                         kernel_calls={"dual_demand": dict(n=640, k=48)})
    cost = harness.kernel_cost(cell, "dual_demand").cost

    def least(iters):
        flops, nbytes = cost(n=640, k=48, iters=iters)
        return max(flops / 1e9, nbytes / 1e8)

    want = 100.0 * 2 * (WARM_ITERS * least(WARM_INNER_ITERS)
                        + least(BISECT_ITERS)) / (2.0 * launches)
    reader = harness.metric_reader(cell, "dual_demand_roofline")
    assert reader.read(r) == pytest.approx(want)
    r.trace = TraceSummary(window=trace.window, ops={0: ops[0][-1:]},
                           modules={}, host=[])
    assert reader.read(r) is None            # no launch of the kernel
    r.trace, r.peaks = trace, None
    assert reader.read(r) is None            # no peaks off the chip


def test_start_hands_the_profiler_its_options(tmp_path, monkeypatch):
    """No Python tracer, and libtpu's own trace mode: every mode held the
    same operation events on the chip, so no cell asks for another."""
    import jax

    given = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options: given.append(
                            profiler_options))
    tracer = tracing.Tracer(tmp_path, "w", 0, jax.devices()[:1])
    tracer.start()
    tracer._window.__exit__(None, None, None)
    (options,) = given
    assert options.advanced_configuration == {}
    assert options.python_tracer_level == 0


def test_host_spans_read_back_from_a_recorded_trace(tmp_path):
    import jax

    tracer = tracing.Tracer(tmp_path, "w", 0, jax.devices()[:1])
    tracer.start()
    with tracer.span("bench.tick"):
        jax.numpy.ones(8).block_until_ready()
    tracer.stop()
    s = tracer.reduce()
    assert s is not None and s.window_s > 0
    assert [e.name for e in s.host if e.name == "bench.tick"]
    assert tracer.spans["bench.tick"][0] > 0
    assert not tracer.dir.exists()


def _plane(**lines):
    return SimpleNamespace(lines=[
        SimpleNamespace(name=name, events=[SimpleNamespace(name=e)
                                           for e in events])
        for name, events in lines.items()])


def test_a_plane_with_dropped_buffers_is_incomplete():
    ops = ["%fusion.1", "%while.2"]
    assert not tracing.dropped(_plane(**{"XLA Ops": ops,
                                         "XLA Modules": ["jit_fn(1)"]}))
    assert tracing.dropped(_plane(**{"XLA Ops": ops,
                                     "XLA TraceMe": [tracing.DROPPED]}))

