"""Fleet engine acceptance: device-sharded, memory-bounded episode sweeps.

* batched device-side static draws: one dispatch for a whole fleet, bitwise
  identical to the looped per-seed reference for every arrival process;
* ``run_fleet`` per-seed bitwise equality vs ``run_batch`` / ``run_scan``
  under chunking, padding (uneven fleet sizes), ``collect_history`` on/off,
  and warm-start carry across chunk boundaries -- on 1 device in-process and
  on 8 forced-host devices in a subprocess;
* single-trace compilation for every (policy, scenario, warm) combination;
* a 4096-episode aggregate-only sweep whose outputs contain no (S, T) array.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import scenarios
from repro.compat import flat_mesh
from repro.fl import simulator

BASE = dict(policy="es", n_services_total=3, rounds_required=100,
            p_arrive=2.0, max_periods=100, k_max=32)

FULL_STACK = dict(
    channel_process=scenarios.spec("gauss_markov", rho=0.9),
    arrival_process=scenarios.spec("mmpp", burst=6.0),
    churn_process=scenarios.spec("bernoulli", p_drop=0.1),
)


def _cfg(**kw) -> simulator.SimConfig:
    return simulator.SimConfig(**{**BASE, **kw})


def _mesh1():
    return flat_mesh(1, axis_name="seeds")


# ---------------------------------------------------------------------------
# Vectorized static draws.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arrival", ["poisson", "periodic", "batched", "mmpp"])
def test_static_draws_batch_bitwise_equals_looped_reference(arrival):
    """One batched draw == looping the per-seed path, for every arrival
    process: fleet setup can be O(1) dispatches without changing a single
    episode."""
    cfg = _cfg(arrival_process=arrival)
    net = simulator._default_net(cfg)
    seeds = [0, 3, 11, 42]
    arrivals, counts = simulator._static_draws_batch(cfg, net, seeds)
    assert arrivals.shape == counts.shape == (4, cfg.n_services_total)
    for i, s in enumerate(seeds):
        a_ref, c_ref = simulator._static_draws(
            dataclasses.replace(cfg, seed=s), net)
        np.testing.assert_array_equal(arrivals[i], a_ref)
        np.testing.assert_array_equal(counts[i], c_ref)


def test_static_draws_respect_client_bounds():
    cfg = _cfg(mean_clients=6.0, var_clients=100.0, k_max=9)
    net = simulator._default_net(cfg)
    _, counts = simulator._static_draws_batch(cfg, net, list(range(32)))
    assert counts.min() >= net.k_min
    assert counts.max() <= 9


# ---------------------------------------------------------------------------
# run_fleet parity vs run_batch / run_scan (single device, in-process).
# ---------------------------------------------------------------------------

def test_fleet_bitwise_equals_batch_and_scan_uneven_chunked():
    """Fleet of 5 on chunk 2: remainder chunk + padding.  Every per-seed
    output must be bitwise identical to run_batch AND to the seed's own
    run_scan."""
    cfg = _cfg()
    seeds = [0, 1, 2, 3, 4]
    fleet = simulator.run_fleet(cfg, seeds, mesh=_mesh1(), chunk_size=2)
    assert fleet["fleet"] == {"n_devices": 1, "mesh_axis": "seeds",
                              "chunk": 2, "n_chunks": 3, "padded_to": 6,
                              "device_ids": [jax.devices()[0].id]}
    batch = simulator.run_batch(cfg, seeds)
    np.testing.assert_array_equal(fleet["durations"], batch["durations"])
    np.testing.assert_array_equal(fleet["finished"], batch["finished"])
    for key in ("freq_sum", "objective", "n_active", "n_clients"):
        np.testing.assert_array_equal(fleet["history"][key],
                                      batch["history"][key])
    single = simulator.run_scan(dataclasses.replace(cfg, seed=3))
    assert list(fleet["durations"][3]) == single["durations"]
    p = single["periods"]
    np.testing.assert_array_equal(fleet["history"]["freq_sum"][3][:p],
                                  single["history"]["freq_sum"])


@pytest.mark.parametrize("chunk_size", [1, 3, None])
def test_fleet_invariant_to_chunk_size(chunk_size):
    cfg = _cfg(collect_history=False)
    seeds = [0, 1, 2, 3]
    fleet = simulator.run_fleet(cfg, seeds, mesh=_mesh1(),
                                chunk_size=chunk_size)
    batch = simulator.run_batch(cfg, seeds)
    np.testing.assert_array_equal(fleet["durations"], batch["durations"])
    np.testing.assert_array_equal(fleet["periods"], batch["periods"])
    for key in simulator._AGG_KEYS:
        np.testing.assert_array_equal(fleet["totals"][key],
                                      batch["totals"][key])


def test_fleet_warm_start_carry_across_chunks():
    """Warm-started policy state rides inside each episode's scan carry;
    chunking the fleet must not perturb it -- durations and float history
    stay bitwise equal to the flat warm batch."""
    cfg = _cfg(policy="coop", rounds_required=80, max_periods=80, k_max=24,
               warm_start=True)
    seeds = [0, 1, 2]
    fleet = simulator.run_fleet(cfg, seeds, mesh=_mesh1(), chunk_size=1)
    batch = simulator.run_batch(cfg, seeds)
    np.testing.assert_array_equal(fleet["durations"], batch["durations"])
    for key in ("freq_sum", "objective"):
        np.testing.assert_array_equal(fleet["history"][key],
                                      batch["history"][key])


def test_fleet_rejects_empty_and_multiaxis():
    with pytest.raises(ValueError, match="at least one seed"):
        simulator.run_fleet(_cfg(), [])
    mesh2d = jax.make_mesh((1, 1), ("a", "b"))
    with pytest.raises(ValueError, match="one-axis mesh"):
        simulator.run_fleet(_cfg(), [0], mesh=mesh2d)


def test_legacy_resume_rejects_foreign_draw_stream():
    """A legacy-engine checkpoint written under a different episode-static
    draw stream (e.g. the pre-fleet host-NumPy draws) must be refused on
    resume: arrivals are re-derived from cfg.seed, so continuing would
    silently diverge from the snapshot's recorded progress."""
    cfg = _cfg(max_periods=12)
    part = simulator.run(dataclasses.replace(cfg, max_periods=4))
    state = dict(part["state"])
    assert state["draw_stream"] == simulator.DRAW_STREAM
    # same-stream resume still works ...
    resumed = simulator.run(cfg, state=dict(state))
    full = simulator.run(cfg)
    assert resumed["durations"] == full["durations"]
    # ... a foreign or missing stream tag does not
    state["draw_stream"] = "numpy/v0"
    with pytest.raises(ValueError, match="draw stream"):
        simulator.run(cfg, state=state)
    state.pop("draw_stream")
    with pytest.raises(ValueError, match="draw stream"):
        simulator.run(cfg, state=state)


# ---------------------------------------------------------------------------
# Single-trace compilation across policy x scenario x warm combos.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("warm_start", [False, True])
@pytest.mark.parametrize("pol", simulator.POLICIES)
def test_fleet_single_trace_every_policy_warm_combo(pol, warm_start):
    cfg = simulator.SimConfig(policy=pol, n_services_total=3,
                              rounds_required=60, p_arrive=2.0,
                              max_periods=60, warm_start=warm_start)
    simulator.reset_trace_count()
    out = simulator.run_fleet(cfg, [0, 1, 2], mesh=_mesh1(), chunk_size=2)
    assert out["finished"].all()
    assert simulator.trace_count() == 1
    # same combo again: fully cached, no retrace
    simulator.run_fleet(cfg, [3, 4, 5], mesh=_mesh1(), chunk_size=2)
    assert simulator.trace_count() == 1


@pytest.mark.parametrize("warm_start", [False, True])
@pytest.mark.parametrize("pol", ["coop", "es"])
def test_fleet_single_trace_with_stateful_scenarios(pol, warm_start):
    cfg = simulator.SimConfig(policy=pol, n_services_total=3,
                              rounds_required=60, p_arrive=2.0,
                              max_periods=60, warm_start=warm_start,
                              **FULL_STACK)
    simulator.reset_trace_count()
    simulator.run_fleet(cfg, [0, 1, 2], mesh=_mesh1(), chunk_size=2)
    assert simulator.trace_count() == 1


# ---------------------------------------------------------------------------
# Memory-bounded sweeps: no (S, T) history in aggregate-only mode.
# ---------------------------------------------------------------------------

def test_fleet_4096_aggregate_only_materializes_no_history():
    """A 4096-episode chunked sweep in aggregate-only mode completes and
    returns per-seed scalars only -- no output array carries a period axis,
    so peak memory stays O(chunk) + O(S) summaries."""
    cfg = simulator.SimConfig(policy="ec", n_services_total=2,
                              rounds_required=2000, p_arrive=2.0,
                              mean_clients=6.0, var_clients=2.0,
                              max_periods=6, collect_history=False)
    n_seeds = 4096
    out = simulator.run_fleet(cfg, range(n_seeds), mesh=_mesh1())
    assert out["history"] is None
    assert out["fleet"]["chunk"] == simulator.FLEET_CHUNK
    assert out["fleet"]["n_chunks"] == n_seeds // simulator.FLEET_CHUNK
    allowed = {(n_seeds,), (n_seeds, cfg.n_services_total)}
    for name in ("avg_duration", "std_duration", "durations", "finished",
                 "periods"):
        assert np.asarray(out[name]).shape in allowed, name
    for key, val in out["totals"].items():
        assert val.shape == (n_seeds,), key
    # the pad-free seed axis survives intact
    assert list(out["seeds"]) == list(range(n_seeds))


# ---------------------------------------------------------------------------
# 8 forced-host devices (subprocess so the XLA flag doesn't leak).
# ---------------------------------------------------------------------------

MULTIDEV_FLEET_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro import scenarios
    from repro.fl import simulator

    assert jax.device_count() == 8
    cfg = simulator.SimConfig(
        policy="coop", n_services_total=3, rounds_required=80, p_arrive=2.0,
        max_periods=80, k_max=24, warm_start=True,
        channel_process=scenarios.spec("gauss_markov", rho=0.9),
        churn_process=scenarios.spec("bernoulli", p_drop=0.1))
    seeds = list(range(11))   # uneven over 8 devices -> pad + remainder
    simulator.reset_trace_count()
    fleet = simulator.run_fleet(cfg, seeds, chunk_size=2)
    assert simulator.trace_count() == 1, simulator.trace_count()
    assert fleet["fleet"]["n_devices"] == 8, fleet["fleet"]
    assert fleet["fleet"]["device_ids"] == [d.id for d in jax.devices()]
    batch = simulator.run_batch(cfg, seeds)
    np.testing.assert_array_equal(fleet["durations"], batch["durations"])
    for key in ("freq_sum", "objective", "n_active", "n_clients"):
        np.testing.assert_array_equal(fleet["history"][key],
                                      batch["history"][key])
    print("FLEET-8DEV-OK")
    """
)


def test_fleet_eight_devices_bitwise_parity():
    """run_fleet sharded over 8 forced-host devices (default mesh from
    launch.mesh.make_fleet_mesh): bitwise per-seed parity with the flat
    single-device run_batch, warm start + stateful scenarios enabled."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", MULTIDEV_FLEET_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "FLEET-8DEV-OK" in out.stdout, out.stderr[-2000:]
