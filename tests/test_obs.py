"""In-process telemetry (``repro.obs``) and the fleet engine's record of
each ``run_fleet`` call.

* spans time their block, the call log is bounded, counters add, and a
  fresh ``jit`` adds to the compile seconds;
* ``policy.launch_shape`` and ``launch_tile`` are the launch the traced,
  vmapped policy step hands its kernels, under every policy and backend;
* ``run_fleet``'s work counts equal a recount from ``run_batch``'s history
  over the padded fleet, with rows, lanes and row block from the traced
  step;
* a profiler capture of one ``run_fleet`` call holds its ``repro.fleet.*``
  spans, nested, on a host plane.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jaxpr_shapes import kernel_launches, pallas_launches

from repro import obs
from repro.compat import flat_mesh
from repro.core import policy as policy_mod
from repro.core.types import ServiceSet
from repro.fl import simulator

FLEET_SPANS = ("repro.fleet.call", "repro.fleet.prepare",
               "repro.fleet.device", "repro.fleet.collect")


def _cfg(**kw):
    return simulator.SimConfig(**{
        **dict(policy="selfish", n_services_total=3, rounds_required=60,
               p_arrive=2.0, max_periods=30, k_max=12), **kw})


def test_span_times_its_block():
    with obs.span("repro.test.outer") as outer:
        with obs.span("repro.test.inner") as inner:
            assert inner.end is None
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert outer.seconds >= inner.seconds >= 0
    with pytest.raises(KeyError):
        with obs.span("repro.test.raises") as failed:
            raise KeyError("x")
    assert failed.end is not None
    with pytest.raises(ValueError, match="repro."):
        with obs.span("fleet.call"):
            pass


def test_call_log_is_bounded():
    for i in range(obs.CALL_LOG + 5):
        obs.record_call({"i": i})
    log = obs.calls()
    assert len(log) == obs.CALL_LOG
    assert log[0] == {"i": 5} and log[-1] == {"i": obs.CALL_LOG + 4}


def test_counters_add_and_reset_by_name():
    obs.reset("repro.test.a", "repro.test.b")
    obs.count("repro.test.a")
    obs.count("repro.test.a", 4)
    obs.count("repro.test.b", 2)
    assert obs.counter("repro.test.a") == 5
    assert obs.counter("repro.test.b") == 2
    obs.reset("repro.test.a")
    assert obs.counter("repro.test.a") == 0 and obs.counter("repro.test.b") == 2


def test_trace_count_is_the_period_step_counter():
    simulator.reset_trace_count()
    obs.count("repro.test.other")
    assert simulator.trace_count() == 0
    obs.count(simulator.TRACE_COUNTER, 3)
    assert simulator.trace_count() == 3
    simulator.reset_trace_count()
    assert simulator.trace_count() == 0
    assert obs.counter("repro.test.other") >= 1


def test_a_fresh_jit_adds_to_the_compile_seconds():
    import time

    t0 = time.perf_counter()
    before, before_t0 = obs.compile_seconds(), obs.compile_seconds(until=t0)

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + 1.25

    fresh(jnp.arange(11.0)).block_until_ready()
    assert obs.compile_seconds() > before
    assert obs.compile_seconds(until=t0) == before_t0
    n = len(obs._compiles)
    obs._on_duration("/jax/other/duration", 1.0)
    assert len(obs._compiles) == n


def test_compile_seconds_is_the_union_of_nested_events(monkeypatch):
    monkeypatch.setattr(obs, "_compiles", [
        (0.0, 3.0), (1.0, 2.0), (3.0, 3.5), (5.0, 6.0)])
    assert obs.compile_seconds() == pytest.approx(4.5)
    assert obs.compile_seconds(until=3.5) == pytest.approx(3.5)
    assert obs.compile_seconds(until=0.5) == 0.0


def _step_launches(policy, backend, warm, n, k, batch):
    """The set of (rows, lanes, row block) the policy's step, vmapped over
    ``batch`` service sets as the fleet engine runs it, hands
    ``pallas_call``."""
    pol = policy_mod.get_stateful_policy(policy, warm_start=warm,
                                         intra_backend=backend)
    ones = jnp.ones((batch, n, k), jnp.float32)
    svc = ServiceSet(alpha=ones, t_comp=ones, mask=ones > 0)
    step = jax.vmap(lambda s: pol.step(s, jnp.float32(10.0),
                                       pol.init_state(n)))
    return set(pallas_launches(step, svc))


@pytest.mark.parametrize("batch", [1, simulator.FLEET_CHUNK])
@pytest.mark.parametrize("policy,warm", [(p, False) for p in
                                         simulator.POLICIES]
                         + [("coop", True)])
@pytest.mark.parametrize("backend", policy_mod.INTRA_BACKENDS)
def test_launch_shape_is_what_the_policy_step_launches(policy, warm,
                                                       backend, batch):
    """At the benchmark cell's (10, 48), at one episode a launch and at the
    fleet's chunk: the kernels' padding of the folded rows, the sets
    themselves where no kernel runs, and None only for cold coop on
    ``pallas``, whose dual bisection runs on the reference beside a kernel
    f*(b).  ``launch_tile`` is the rows a grid step takes."""
    n, k = 10, 48
    launches = _step_launches(policy, backend, warm, n, k, batch)
    shape = dict(intra_backend=backend, warm_start=warm, n=n, k=k,
                 batch=batch)
    got = policy_mod.launch_shape(policy, **shape)
    tile = policy_mod.launch_tile(policy, **shape)
    if (policy, backend, warm) == ("coop", "pallas", False):
        assert got is None and tile is None and launches
    elif launches:
        assert {got + (tile,)} == launches
    else:
        assert got == (batch * n, k) and tile is None


@pytest.mark.parametrize("policy,warm", [("selfish", False), ("coop", True),
                                         ("es", False)])
def test_launch_at_a_wide_client_axis_is_the_kernel_with_the_largest_block(
        policy, warm):
    """At 4096 lanes each kernel's row block fits its own VMEM planes, so
    the auction's ``mbdf_demand`` and ``bisect_alloc`` take different
    blocks.  ``launch_shape`` and
    ``launch_tile`` are the traced launch whose grid step holds the most
    VMEM bytes, and ``launch_block_bytes`` those bytes."""
    from repro.kernels import bisect_alloc, dual_demand, market_clear, tiling

    rule = {"_bisect_kernel": bisect_alloc.row_blocks,
            "_dual_demand_kernel": dual_demand.row_blocks,
            "_mbdf_kernel": market_clear.mbdf_row_blocks}
    n, k, batch = 10, 4096, simulator.FLEET_CHUNK
    pol = policy_mod.get_stateful_policy(policy, warm_start=warm,
                                         intra_backend="pallas")
    ones = jnp.ones((batch, n, k), jnp.float32)
    svc = ServiceSet(alpha=ones, t_comp=ones, mask=ones > 0)
    step = jax.vmap(lambda s: pol.step(s, jnp.float32(10.0),
                                       pol.init_state(n)))
    launches = []
    for name, traced in kernel_launches(step, svc).items():
        launch = rule[name](batch * n, k)
        assert launch[:3] == traced
        launches.append(launch)
    shape = dict(intra_backend="pallas", warm_start=warm, n=n, k=k,
                 batch=batch)
    widest = max(launches, key=lambda launch: launch.block_bytes)
    assert policy_mod.launch_shape(policy, **shape) + (
        policy_mod.launch_tile(policy, **shape),) == widest[:3]
    assert policy_mod.launch_block_bytes(policy, **shape) == \
        widest.block_bytes <= tiling.VMEM_BUDGET
    if policy == "selfish":
        assert len({launch.tile for launch in launches}) == 2


def _recount(cfg, seeds, chunk, n_dev=1):
    """The work counts, recounted from ``run_batch``'s per-period history
    of the padded fleet, with rows, lanes and row block from the step
    traced at the chunk."""
    per_dev = -(-len(seeds) // n_dev)
    n_chunks = -(-per_dev // chunk)
    padded = seeds + [seeds[-1]] * (n_dev * n_chunks * chunk - len(seeds))
    h = simulator.run_batch(dataclasses.replace(cfg, collect_history=True),
                            padded)["history"]
    T = cfg.max_periods
    periods = np.array([list(row).index(True) + 1 if row.any() else T
                        for row in h["all_done"]])
    live = np.arange(T)[None, :] < periods[:, None]
    n, k = cfg.n_services_total, cfg.k_max
    (rows, lanes, tile), = (_step_launches(cfg.policy, cfg.intra_backend,
                                           cfg.warm_start, n, k, chunk)
                            or {(chunk * n, k, None)})
    chunk_max = [max(periods[i:i + chunk]) for i in range(0, len(padded),
                                                         chunk)]
    live_rows = int((h["n_active"] * live).sum())
    return {
        "episodes": len(seeds), "padded_episodes": len(padded),
        "chunk": chunk, "n_chunks": n_chunks, "max_periods": T,
        "step_launches": n_dev * n_chunks * T,
        "scanned_periods": len(padded) * T,
        "live_periods": int(periods.sum()),
        "chunk_live_periods": int(sum(chunk_max)),
        "rows": rows, "lanes": lanes, "row_tile": tile,
        "live_rows": live_rows,
        "live_lanes": int((h["n_clients"] * live).sum()),
        "rows_in_live_chunks": int(sum(chunk_max)) * rows,
        "lanes_of_live_rows": live_rows * lanes,
    }


@pytest.mark.parametrize("collect_history", [False, True])
@pytest.mark.parametrize("policy,backend,warm", [
    ("selfish", "reference", False), ("selfish", "pallas", False),
    ("coop", "megakernel", True)])
def test_fleet_work_equals_a_recount_from_batch_history(policy, backend, warm,
                                                        collect_history):
    """Fleet of 5 on chunk 2: a remainder chunk and one pad episode, whose
    work counts as the devices ran it.  The megakernel pads each episode's
    rows to its own tile of 128; the other kernels pad the chunk's folded
    rows once."""
    cfg = _cfg(policy=policy, intra_backend=backend, warm_start=warm,
               collect_history=collect_history)
    seeds = [3, 8, 13, 21, 34]
    out = simulator.run_fleet(cfg, seeds, mesh=flat_mesh(1, axis_name="seeds"),
                              chunk_size=2)
    work = out["fleet"]["work"]
    assert obs.calls()[-1] is work
    want = _recount(cfg, seeds, chunk=2)
    assert {k: work[k] for k in want} == want
    assert 0 < work["live_rows"] < work["rows_in_live_chunks"]
    assert 0 < work["live_lanes"] <= work["lanes_of_live_rows"]
    assert work["live_periods"] <= work["chunk_live_periods"] * 2
    # Each share counts its waste within the one before it, so together
    # they are live lanes over every lane launched.
    shares = (work["chunk_live_periods"] / work["step_launches"]
              * work["live_rows"] / work["rows_in_live_chunks"]
              * work["live_lanes"] / work["lanes_of_live_rows"])
    assert shares == pytest.approx(work["live_lanes"] / (
        work["step_launches"] * work["rows"] * work["lanes"]))
    assert work["call_s"] >= work["prepare_s"] + work["device_s"] \
        + work["collect_s"] > 0


def test_fleet_call_spans_on_a_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    cfg = _cfg(collect_history=False, max_periods=20)
    mesh = flat_mesh(1, axis_name="seeds")
    simulator.run_fleet(cfg, [1, 2, 3], mesh=mesh)        # compile outside
    with jax.profiler.trace(str(tmp_path)):
        simulator.run_fleet(cfg, [4, 5, 6], mesh=mesh)
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    events = {ev.name: ev for plane in data.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name in FLEET_SPANS}
    assert set(events) == set(FLEET_SPANS)
    call, *children = (events[name] for name in FLEET_SPANS)
    # The three children run in order inside the call.
    bounds = [call.start_ns]
    for ev in children:
        assert bounds[-1] <= ev.start_ns <= ev.end_ns
        bounds.append(ev.end_ns)
    assert bounds[-1] <= call.end_ns
