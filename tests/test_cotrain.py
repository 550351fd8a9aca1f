"""Differential test layer for the training-in-the-loop co-simulation.

The coupling contract, pinned here:

* **The allocation stream is untouched by training.**  For every policy
  (warm and cold, calm and full scenario stack) a co-trained episode's
  durations and per-period allocation stats are *bitwise* equal to the
  duration engine's ``run_scan``, and the period step still traces exactly
  once.
* **Limits recover the decoupled halves.**  With a vanishing period no
  rounds execute and the models stay bitwise at their init (the
  zero-bandwidth limit); with an infinite straggler deadline the executed
  rounds replay plain ``launch/train.py``-style FedAvg (a hand-rolled
  ``make_fl_round_step`` loop on the same batches) to numerical identity;
  with an impossible deadline every round is all-straggler -- learning
  freezes, the allocation stream does not.
* **Engine parity.**  Batch composition is bitwise-irrelevant per seed, the
  sharded/chunked fleet engine matches the flat batch bitwise, and the
  golden ``tests/golden/cotrain_summary.json`` pins the co-trained
  trajectories (regen: ``python tests/golden/regen_cotrain.py``).
* **Service bookkeeping is live.**  ``FLService`` records are driven by the
  episode (arrival/rounds/duration/finished), and a retiring service frees
  its bandwidth slot for the survivors the very next period.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.compat import flat_mesh
from repro.core import network
from repro.fl import cotrain, simulator

NET = network.NetworkConfig(period_s=1.0, mean_clients=5.0, var_clients=2.0)
BASE = dict(n_services_total=3, rounds_required=30, p_arrive=2.0,
            max_periods=50, k_max=12, mean_clients=5.0, var_clients=2.0)
TRAIN = cotrain.TrainSpec(vocab=16, seq_len=6, batch_size=2, eval_batch=8,
                          rounds_cap=2)

FULL_STACK = dict(
    channel_process=scenarios.spec("gauss_markov", rho=0.9),
    arrival_process=scenarios.spec("mmpp", burst=6.0),
    churn_process=scenarios.spec("bernoulli", p_drop=0.1),
)

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "cotrain_summary.json")


def _cfg(**kw) -> simulator.SimConfig:
    return simulator.SimConfig(**{**BASE, **kw})


def _init_params(cfg: simulator.SimConfig, train: cotrain.TrainSpec):
    """The exact stacked init the episode derives from its key stream."""
    task = cotrain._build_task(train, cfg.k_max)
    k_init = jax.random.fold_in(jax.random.key(cfg.seed + 7),
                                cotrain.COTRAIN_SALT)
    return jax.vmap(lambda i: task.init(jax.random.fold_in(k_init, i)))(
        jnp.arange(cfg.n_services_total, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# (a) Coupling must not perturb the allocation stream -- every policy.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", simulator.POLICIES)
def test_durations_bitwise_unchanged_by_coupling(policy):
    cfg = _cfg(policy=policy)
    simulator.reset_trace_count()
    co = cotrain.run_cotrain_scan(cfg, TRAIN, NET)
    assert simulator.trace_count() == 1
    ref = simulator.run_scan(cfg, NET)
    assert co["durations"] == ref["durations"]
    assert co["periods"] == ref["periods"]
    assert co["finished"] == ref["finished"]
    for key in ("freq_sum", "objective"):
        np.testing.assert_array_equal(co["history"][key],
                                      ref["history"][key])


def test_duration_parity_warm_start_full_scenario_stack():
    """Warm-started coop under correlated fading + bursty arrivals + churn:
    the policy/scenario carries thread through the co-trained scan exactly
    as through the duration engine's."""
    cfg = _cfg(policy="coop", warm_start=True, rounds_required=25,
               **FULL_STACK)
    simulator.reset_trace_count()
    co = cotrain.run_cotrain_scan(cfg, TRAIN, NET)
    assert simulator.trace_count() == 1
    ref = simulator.run_scan(cfg, NET)
    assert co["durations"] == ref["durations"]
    np.testing.assert_array_equal(co["history"]["freq_sum"],
                                  ref["history"]["freq_sum"])


# ---------------------------------------------------------------------------
# (b) Limits: zero bandwidth / plain FedAvg / all-straggler.
# ---------------------------------------------------------------------------

def test_zero_round_limit_keeps_params_at_init():
    """A vanishing period grants zero rounds everywhere: no training ever
    executes, the stacked params stay at their init (to compilation-context
    rounding of the init draw; the *bitwise* frozenness proof is the exactly
    flat eval curves below), and the (all unfinished) duration stream still
    matches the duration engine."""
    net0 = dataclasses.replace(NET, period_s=1e-6)
    cfg = _cfg(policy="es", max_periods=8)
    co = cotrain.run_cotrain_scan(cfg, TRAIN, net0)
    assert co["trained_rounds"] == [0, 0, 0]
    assert co["clipped_rounds"] == 0
    assert not co["finished"]
    np.testing.assert_allclose(np.asarray(co["params"]),
                               np.asarray(_init_params(cfg, TRAIN)),
                               rtol=1e-6, atol=1e-8)
    h = co["history"]
    for key in ("loss", "acc"):
        np.testing.assert_array_equal(
            h[key], np.broadcast_to(h[key][:1], h[key].shape))
    ref = simulator.run_scan(cfg, net0)
    assert co["durations"] == ref["durations"]


def test_infinite_deadline_recovers_plain_fedavg():
    """With straggler drop disabled, the rounds the co-simulation executes
    are plain FedAvg: a hand-rolled launch/train.py-style loop (same round
    step, same batches, full participation) reproduces the trained params
    and per-period training losses."""
    cfg = simulator.SimConfig(policy="coop", n_services_total=1,
                              rounds_required=10, p_arrive=2.0,
                              max_periods=30, k_max=8, mean_clients=4.0,
                              var_clients=1.0)
    net = network.NetworkConfig(mean_clients=4.0, var_clients=1.0)
    train = dataclasses.replace(TRAIN, deadline_x=float("inf"),
                                rounds_cap=10)
    co = cotrain.run_cotrain_scan(cfg, train, net)
    assert co["finished"] and co["clipped_rounds"] == 0
    assert sum(co["trained_rounds"]) == cfg.rounds_required

    arrivals, counts = simulator._static_draws(cfg, net)
    task = cotrain._build_task(train, cfg.k_max)
    params = _init_params(cfg, train)
    params = jax.tree.map(lambda x: x[0], params)
    weights = (np.arange(cfg.k_max) < int(counts[0])).astype(np.float32)
    h = co["history"]
    # full participation whenever rounds ran
    ran = np.asarray(h["trained"])[:, 0] > 0
    assert np.all(np.asarray(h["participants"])[ran, 0] == int(counts[0]))
    r = 0
    for p in range(co["periods"]):
        losses = []
        for _ in range(int(np.asarray(h["rounds"])[p, 0])):
            batches = task.batch_fn(jnp.int32(0), jnp.int32(r))
            params, metrics = task.round_step(params, batches,
                                              jnp.asarray(weights))
            losses.append(float(metrics["loss"]))
            r += 1
        if losses:
            np.testing.assert_allclose(float(h["train_loss"][p, 0]),
                                       np.mean(losses), rtol=1e-5)
    assert r == cfg.rounds_required
    np.testing.assert_allclose(np.asarray(co["params"])[0],
                               np.asarray(params), rtol=1e-5, atol=1e-6)
    # and the training had real signal: eval loss below the init params'
    init_loss, _ = task.eval_fn(
        jax.tree.map(lambda x: x[0], _init_params(cfg, train)), jnp.int32(0))
    assert h["loss"][co["periods"] - 1, 0] < float(init_loss) - 0.05


def test_all_straggler_rounds_freeze_learning_not_allocation():
    """An impossible deadline drops every client from every round: the new
    zero-participant FedAvg path leaves params untouched (flat eval curves)
    while the simulated rounds -- and therefore the durations -- proceed
    exactly as in the duration engine."""
    cfg = _cfg(policy="pp", rounds_required=25)
    train = dataclasses.replace(TRAIN, deadline_x=1e-3)
    co = cotrain.run_cotrain_scan(cfg, train, NET)
    ref = simulator.run_scan(cfg, NET)
    assert co["durations"] == ref["durations"]
    assert co["finished"]
    h = co["history"]
    assert int(np.asarray(h["participants"]).sum()) == 0
    assert sum(co["trained_rounds"]) > 0          # rounds simulated...
    np.testing.assert_allclose(                   # ...but nothing learned
        np.asarray(co["params"]), np.asarray(_init_params(cfg, train)),
        rtol=1e-6, atol=1e-8)
    np.testing.assert_array_equal(
        h["acc"], np.broadcast_to(h["acc"][:1], h["acc"].shape))


# ---------------------------------------------------------------------------
# (c) Engine parity: batch composition + fleet.
# ---------------------------------------------------------------------------

def test_batch_composition_bitwise_identity():
    cfg = _cfg(policy="es")
    full = cotrain.run_cotrain_batch(cfg, TRAIN, [0, 1, 2], NET)
    alone = cotrain.run_cotrain_batch(cfg, TRAIN, [1], NET)
    for key in ("loss", "acc", "b", "trained"):
        np.testing.assert_array_equal(full["history"][key][1],
                                      alone["history"][key][0])
    np.testing.assert_array_equal(full["durations"][1],
                                  alone["durations"][0])
    single = cotrain.run_cotrain_scan(dataclasses.replace(cfg, seed=2),
                                      TRAIN, NET)
    assert list(full["durations"][2]) == single["durations"]
    assert full["periods"][2] == single["periods"]
    p = single["periods"]
    for key in ("loss", "acc", "train_loss", "b", "f"):
        np.testing.assert_array_equal(full["history"][key][2][:p],
                                      single["history"][key])
    np.testing.assert_array_equal(full["trained_rounds"][2],
                                  single["trained_rounds"])


def test_fleet_bitwise_equals_batch_uneven_chunked():
    """Fleet of 5 on chunk 2 (remainder chunk + pad row): every per-seed
    curve, duration, and final parameter bitwise equals the flat batch; the
    allocation step traces once."""
    cfg = _cfg(policy="es", rounds_required=20)
    seeds = [0, 1, 2, 3, 4]
    simulator.reset_trace_count()
    fleet = cotrain.run_cotrain_fleet(
        cfg, TRAIN, seeds, NET,
        mesh=flat_mesh(1, axis_name="seeds"), chunk_size=2)
    assert simulator.trace_count() == 1
    assert fleet["fleet"] == {"n_devices": 1, "mesh_axis": "seeds",
                              "chunk": 2, "n_chunks": 3, "padded_to": 6}
    batch = cotrain.run_cotrain_batch(cfg, TRAIN, seeds, NET)
    np.testing.assert_array_equal(fleet["durations"], batch["durations"])
    np.testing.assert_array_equal(fleet["trained_rounds"],
                                  batch["trained_rounds"])
    np.testing.assert_array_equal(fleet["clipped_rounds"],
                                  batch["clipped_rounds"])
    for key in cotrain._CURVE_KEYS:
        np.testing.assert_array_equal(fleet["history"][key],
                                      batch["history"][key])
    np.testing.assert_array_equal(np.asarray(fleet["params"]),
                                  np.asarray(batch["params"]))
    for a, b in zip(fleet["services"], batch["services"]):
        assert a == b


# ---------------------------------------------------------------------------
# (d) Live FLService bookkeeping + bandwidth release on retirement.
# ---------------------------------------------------------------------------

def test_service_retirement_frees_bandwidth_next_period():
    """Seed chosen so both services share the band, then one finishes first:
    its FLService record flips finished, its slot drops to b = 0, and the
    survivor's share snaps from B/2 to the full budget the next period."""
    cfg = simulator.SimConfig(policy="es", n_services_total=2,
                              rounds_required=60, p_arrive=3.0,
                              max_periods=80, k_max=12, mean_clients=5.0,
                              var_clients=2.0, seed=0)
    co = cotrain.run_cotrain_scan(cfg, TRAIN, NET)
    arrivals, counts = simulator._static_draws(cfg, NET)
    svcs = co["services"]
    assert [s.service_id for s in svcs] == [0, 1]
    assert [s.n_clients for s in svcs] == [int(c) for c in counts]
    assert [s.arrived_period for s in svcs] == [int(a) for a in arrivals]
    assert [s.periods_active for s in svcs] == co["durations"]
    assert all(s.finished and s.rounds_done == 60 for s in svcs)

    h = co["history"]
    active = np.asarray(h["active"]).astype(bool)
    b = np.asarray(h["b"])
    both = active[:, 0] & active[:, 1]
    assert both.any(), "test premise: services must overlap"
    # Equal-Service splits exactly while both are live ...
    np.testing.assert_array_equal(b[both], 5.0)
    # ... and the retiring service's bandwidth is re-cleared to the survivor
    # on the very next period.
    t = int(np.where(active[:, 0])[0][-1])
    assert active[t + 1, 1] and not active[t + 1, 0]
    assert b[t + 1, 0] == 0.0
    assert b[t + 1, 1] == 10.0


# ---------------------------------------------------------------------------
# (e) Golden regression (regen: python tests/golden/regen_cotrain.py).
# ---------------------------------------------------------------------------

def test_golden_cotrain_summary():
    with open(GOLDEN) as fp:
        golden = json.load(fp)
    cfg_kw = dict(golden["config"])
    train = cotrain.TrainSpec(**golden["train"])
    net = network.NetworkConfig(**golden["net"])
    for pol, exp in golden["policies"].items():
        out = cotrain.run_cotrain_batch(
            simulator.SimConfig(policy=pol, **cfg_kw), train,
            golden["seeds"], net)
        np.testing.assert_array_equal(out["durations"], exp["durations"])
        np.testing.assert_array_equal(out["trained_rounds"],
                                      exp["trained_rounds"])
        np.testing.assert_array_equal(out["periods"], exp["periods"])
        final = np.asarray([out["history"]["loss"][i, p - 1]
                            for i, p in enumerate(out["periods"])])
        np.testing.assert_allclose(final, exp["final_loss"], rtol=1e-4)
        final_acc = np.asarray([out["history"]["acc"][i, p - 1]
                                for i, p in enumerate(out["periods"])])
        np.testing.assert_allclose(final_acc, exp["final_acc"],
                                   rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# (f) Zoo task + spec validation.
# ---------------------------------------------------------------------------

def test_zoo_task_trains_with_duration_parity():
    """A smoke-scaled zoo transformer rides the same co-simulation: the
    duration stream still matches the duration engine and the eval metrics
    are well-formed."""
    net = network.NetworkConfig(mean_clients=3.0, var_clients=1.0)
    cfg = simulator.SimConfig(policy="es", n_services_total=2,
                              rounds_required=4, p_arrive=2.0,
                              max_periods=16, k_max=5, mean_clients=3.0,
                              var_clients=1.0)
    train = cotrain.TrainSpec(task="zoo", arch="gemma3-1b", seq_len=8,
                              batch_size=2, eval_batch=2, rounds_cap=2,
                              client_lr=0.1)
    co = cotrain.run_cotrain_scan(cfg, train, net)
    ref = simulator.run_scan(cfg, net)
    assert co["durations"] == ref["durations"]
    h = co["history"]
    assert np.all(np.isfinite(h["loss"]))
    assert np.all((h["acc"] >= 0.0) & (h["acc"] <= 1.0))
    assert sum(co["trained_rounds"]) > 0


def test_train_spec_validation():
    with pytest.raises(ValueError, match="rounds_cap"):
        cotrain.TrainSpec(rounds_cap=0)
    with pytest.raises(ValueError, match="deadline_x"):
        cotrain.TrainSpec(deadline_x=0.0)
    with pytest.raises(ValueError, match="unknown train task"):
        cotrain._build_task(cotrain.TrainSpec(task="nope"), 4)
    with pytest.raises(ValueError, match="encoder-decoder"):
        cotrain._build_task(
            cotrain.TrainSpec(task="zoo", arch="seamless-m4t-large-v2"), 4)
    with pytest.raises(ValueError, match="comp_levels"):
        cotrain.TrainSpec(comp_levels=())
    with pytest.raises(ValueError, match="comp_levels"):
        cotrain.TrainSpec(comp_levels=["topk"])      # list, not tuple
    with pytest.raises(ValueError, match="compression"):
        cotrain.TrainSpec(comp_levels=("topk", "gzip"))
    with pytest.raises(ValueError, match="comp_policy"):
        cotrain.TrainSpec(comp_policy="sometimes")
    with pytest.raises(ValueError, match="topk_frac"):
        cotrain.TrainSpec(topk_frac=0.0)
    with pytest.raises(ValueError, match="comp_threshold"):
        cotrain.TrainSpec(comp_threshold=0.0)


# ---------------------------------------------------------------------------
# (g) The closed compression->allocation loop.
# ---------------------------------------------------------------------------

def test_topk_compression_shortens_durations():
    """Pricing topk into the dynamic s^UT column makes every round cheaper:
    the compressed episode's durations never exceed the dense stream's, the
    priced multiplier shows up verbatim in the ``ul_mult`` history, and the
    whole episode still traces exactly once."""
    cfg = _cfg(policy="es")
    train = dataclasses.replace(TRAIN, compression="topk", topk_frac=0.05,
                                index_bits=16)
    simulator.reset_trace_count()
    co = cotrain.run_cotrain_scan(cfg, train, NET)
    assert simulator.trace_count() == 1
    ref = simulator.run_scan(cfg, NET)
    assert all(c <= r for c, r in zip(co["durations"], ref["durations"]))
    assert sum(co["durations"]) < sum(ref["durations"])
    # ul_mult records the priced ratio: 0.05 * (32 + 16) / 32 = 0.075
    np.testing.assert_allclose(np.asarray(co["history"]["ul_mult"]), 0.075)
    assert np.all(np.asarray(co["history"]["comp_id"]) == 1)


def test_all_none_levels_bitwise_equal_dense_spec():
    """An explicit all-dense level assignment compiles to the identical
    no-compression episode: the gating is static, so the traced graph (and
    every output) is bitwise the baseline spec's."""
    cfg = _cfg(policy="coop")
    dense = cotrain.run_cotrain_scan(cfg, TRAIN, NET)
    leveled = cotrain.run_cotrain_scan(
        cfg, dataclasses.replace(TRAIN, comp_levels=("none",) * 3), NET)
    assert leveled["durations"] == dense["durations"]
    for key in ("loss", "acc", "train_loss", "b"):
        np.testing.assert_array_equal(leveled["history"][key],
                                      dense["history"][key])
    np.testing.assert_array_equal(np.asarray(leveled["params"]),
                                  np.asarray(dense["params"]))
    np.testing.assert_array_equal(np.asarray(leveled["history"]["ul_mult"]),
                                  1.0)


def test_mixed_levels_price_per_service():
    """Heterogeneous static levels: each service slot carries its own s^UT
    multiplier into the allocator, constant over the episode."""
    cfg = _cfg(policy="es")
    train = dataclasses.replace(
        TRAIN, comp_levels=("none", "topk", "int8"), topk_frac=0.05,
        index_bits=16)
    co = cotrain.run_cotrain_scan(cfg, train, NET)
    ul = np.asarray(co["history"]["ul_mult"])
    np.testing.assert_allclose(ul[:, 0], 1.0)
    np.testing.assert_allclose(ul[:, 1], 0.075)
    np.testing.assert_allclose(ul[:, 2], 0.25)
    assert np.all(np.isfinite(np.asarray(co["history"]["loss"])))


def test_adaptive_compression_reacts_to_tight_bandwidth():
    """The adaptive controller starts dense (reactive: the first period has
    no allocation to judge), then compresses exactly the services whose
    share fell below comp_threshold x fair, re-pricing their s^UT the next
    period."""
    cfg = _cfg(policy="pp")
    train = dataclasses.replace(TRAIN, compression="topk", topk_frac=0.05,
                                index_bits=16, comp_policy="adaptive",
                                comp_threshold=1.5)
    co = cotrain.run_cotrain_scan(cfg, train, NET)
    h = co["history"]
    comp_id = np.asarray(h["comp_id"])
    ul = np.asarray(h["ul_mult"])
    assert np.all(comp_id[0] == 0), "first period must apply dense"
    assert comp_id.max() == 1, "threshold 1.5x fair must trigger under pp"
    # the applied multiplier is a pure function of the applied level
    np.testing.assert_allclose(ul[comp_id == 1], 0.075)
    np.testing.assert_allclose(ul[comp_id == 0], 1.0)
    # the controller's decision matches the previous period's shares
    active = np.asarray(h["active"]).astype(bool)
    b = np.asarray(h["b"])
    for t in range(1, co["periods"]):
        n_act = max(int(active[t - 1].sum()), 1)
        fair = NET.total_bandwidth_mhz / n_act
        want = active[t - 1] & (b[t - 1] < train.comp_threshold * fair)
        np.testing.assert_array_equal(comp_id[t] == 1, want)


def test_error_feedback_episode_trains_and_keeps_allocation():
    """EF residuals ride the episode carry: the allocation stream is
    untouched (bitwise vs the same spec without EF -- EF changes params,
    never s^UT), metrics stay finite, and training makes progress."""
    cfg = _cfg(policy="es")
    train = dataclasses.replace(TRAIN, compression="topk", topk_frac=0.25,
                                error_feedback=True)
    co = cotrain.run_cotrain_scan(cfg, train, NET)
    plain = cotrain.run_cotrain_scan(
        cfg, dataclasses.replace(train, error_feedback=False), NET)
    assert co["durations"] == plain["durations"]
    for key in ("b", "f", "ul_mult", "rounds"):
        np.testing.assert_array_equal(co["history"][key],
                                      plain["history"][key])
    h = co["history"]
    assert np.all(np.isfinite(h["loss"])) and np.all(np.isfinite(h["train_loss"]))
    assert np.all((h["acc"] >= 0.0) & (h["acc"] <= 1.0))
    assert sum(co["trained_rounds"]) > 0
    # EF genuinely changes the learning trajectory under lossy compression
    assert not np.array_equal(np.asarray(co["params"]),
                              np.asarray(plain["params"]))
