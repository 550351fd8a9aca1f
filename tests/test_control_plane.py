"""Control-plane contracts (fl.control_plane + launch.allocd).

The load-bearing one is the differential replay: a live daemon that never
serves stale produces an allocation stream bitwise equal to
``simulator.run_scan`` fed the same admission trace -- the online path and
the offline reference share one ``_period_step``, and the healthy heartbeat
mask is a bitwise no-op.  The rest pin admission bookkeeping, heartbeat
liveness, COMMIT-protocol checkpoint/resume, and the deadline-miss
(stale-decision) degradation of the asyncio front end.
"""
import asyncio
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro import scenarios
from repro.checkpoint import CheckpointManager
from repro.compile_cache import CACHE_ENV, enable_compile_cache
from repro.distributed import fault
from repro.fl import control_plane
from repro.fl.control_plane import ControlPlane, ControlPlaneConfig
from repro.launch import allocd

_FAST = dict(capacity=6, k_max=6, rounds_required=60, seed=3)
# Services that never complete within a test's horizon (a lone service can
# clear 60 rounds in one 20 s period at full bandwidth).
_PERSIST = dict(capacity=6, k_max=6, rounds_required=100_000, seed=3)


def _drive(plane: ControlPlane, schedule: dict, n_periods: int,
           heartbeat_all: bool = False):
    """Scripted synchronous serving: admissions land before the period."""
    for p in range(n_periods):
        for sid, k in schedule.get(p, ()):
            plane.admit(sid, k)
        if heartbeat_all:
            for sid in list(plane.services):
                plane.heartbeat(sid)
        plane.tick()
    return plane


_SCHEDULE = {0: [("a", 4), ("b", 3)], 2: [("c", 5)], 5: [("d", 2)]}


@pytest.mark.parametrize("channel,churn", [
    ("iid", "none"),
    ("gauss_markov", "gilbert"),
])
def test_differential_replay_bitwise(channel, churn):
    """Live decisions == run_scan(collect_alloc) on the recorded trace,
    bit for bit, including under stochastic channel evolution and seeded
    churn, across admissions AND completion-based departures."""
    cfg = ControlPlaneConfig(channel_process=channel, churn_process=churn,
                             **_FAST)
    plane = _drive(ControlPlane(cfg), _SCHEDULE, 12)
    assert plane.metrics["admitted"] == 4
    assert plane.metrics["retired"] > 0, (
        "schedule must exercise completion-based departure")
    assert plane.replayable
    ref = plane.replay_reference()["history"]
    live_b = np.stack([d.b for d in plane.decisions])
    live_f = np.stack([d.f for d in plane.decisions])
    live_active = np.stack([d.active for d in plane.decisions])
    assert np.array_equal(np.asarray(ref["b"]), live_b)
    assert np.array_equal(np.asarray(ref["f"]), live_f)
    assert np.array_equal(np.asarray(ref["active"]), live_active)


def test_healthy_heartbeats_are_a_bitwise_noop():
    """Liveness tracking on + every client heartbeating == liveness off:
    the all-True availability mask must not perturb one bit."""
    base = _drive(ControlPlane(ControlPlaneConfig(**_FAST)), _SCHEDULE, 8)
    hb_cfg = ControlPlaneConfig(heartbeat_timeout_periods=2, **_FAST)
    hb = _drive(ControlPlane(hb_cfg), _SCHEDULE, 8, heartbeat_all=True)
    assert hb.metrics["heartbeat_drops"] == 0
    for d0, d1 in zip(base.decisions, hb.decisions):
        assert np.array_equal(d0.b, d1.b) and np.array_equal(d0.f, d1.f)


def test_heartbeat_timeout_drops_then_reclears():
    """A silent client is dropped from the clear after the timeout and
    re-enters the next period after heartbeating again -- never silently:
    the drops land in ``metrics['heartbeat_drops']``."""
    cfg = ControlPlaneConfig(heartbeat_timeout_periods=1, **_PERSIST)
    plane = ControlPlane(cfg)
    twin = ControlPlane(ControlPlaneConfig(**_PERSIST))  # liveness off
    for p in [plane, twin]:
        p.admit("a", 4)
        p.admit("b", 4)
    starved = []
    for period in range(6):
        plane.heartbeat("b")                 # "a" goes silent after admit
        d = plane.tick()
        t = twin.tick()
        if period >= 2:                      # past the 1-period timeout
            starved.append((d, t))
    assert plane.metrics["heartbeat_drops"] > 0
    # The drops are recorded per period, so the masked episode is STILL
    # replayable: run_scan fed the recorded ``avail`` planes reproduces the
    # served stream bitwise (PR 8 -- drops no longer falsify the trace).
    assert plane.replayable
    assert plane.recorded_avail() is not None
    ref = plane.replay_reference()
    b_ref = np.asarray(ref["history"]["b"])
    for d in plane.decisions:
        np.testing.assert_array_equal(np.asarray(d.b), b_ref[d.period])
    # Dropping every client of "a" must change the clear vs the healthy twin.
    assert any(not np.array_equal(d.b, t.b) for d, t in starved)
    # Re-clear: once "a" heartbeats again its cohort re-enters the solve.
    drops_before = plane.metrics["heartbeat_drops"]
    plane.heartbeat("a")
    plane.heartbeat("b")
    plane.tick()
    assert plane.metrics["heartbeat_drops"] == drops_before


def test_admission_validation_and_slot_accounting():
    plane = ControlPlane(ControlPlaneConfig(capacity=2, k_max=4,
                                            rounds_required=10_000))
    plane.admit("a", 3)
    with pytest.raises(ValueError, match="already admitted"):
        plane.admit("a", 2)
    with pytest.raises(ValueError, match="n_clients"):
        plane.admit("b", 5)
    plane.admit("b", 2)
    assert plane.free_slots == 0
    with pytest.raises(RuntimeError, match="slots occupied"):
        plane.admit("c", 2)
    assert plane.metrics["rejected"] == 1
    plane.retire("a")
    assert plane.free_slots == 1
    assert not plane.replayable          # forced retire breaks the trace
    with pytest.raises(RuntimeError, match="not replayable"):
        plane.replay_reference()


def test_allocation_of_reports_latest_decision():
    plane = ControlPlane(ControlPlaneConfig(**_PERSIST))
    plane.admit("a", 4)
    plane.tick()
    got = plane.allocation_of("a")
    assert got["b_mhz"] > 0 and got["stale"] is False
    with pytest.raises(KeyError):
        plane.allocation_of("ghost")


def test_checkpoint_resume_bitwise(tmp_path):
    """COMMIT-protocol snapshot at period 4; a fresh plane restored from it
    serves periods 4..7 bitwise-identically, registry included."""
    cfg = ControlPlaneConfig(**_FAST)
    a = ControlPlane(cfg)
    mgr = CheckpointManager(tmp_path / "cp")
    for p in range(4):
        for sid, k in _SCHEDULE.get(p, ()):
            a.admit(sid, k)
        a.tick()
    a.snapshot(mgr)
    tail_a = [a.tick() for _ in range(4)]

    b = ControlPlane(cfg)
    assert b.restore(mgr)
    assert b.period == 4
    assert set(b.services) == set(a.services) | set()
    tail_b = [b.tick() for _ in range(4)]
    for da, db in zip(tail_a, tail_b):
        assert da.period == db.period
        assert np.array_equal(da.b, db.b)
        assert np.array_equal(da.f, db.f)


def test_run_resumable_crash_resumes_bit_identically(tmp_path):
    """The scripted serving loop through fault.resumable_loop: a crash at
    period 5 with save_every=3 resumes to the same final state as an
    uninterrupted run (and the resumed trace still replays offline)."""
    cfg = ControlPlaneConfig(**_FAST)
    schedule = {0: (4, 3), 2: (5,)}
    clean_mgr = CheckpointManager(tmp_path / "clean")
    clean, _ = control_plane.run_resumable(cfg, schedule, 8, clean_mgr,
                                           fault.RestartPolicy(save_every=3))
    crash_mgr = CheckpointManager(tmp_path / "crash")
    policy = fault.RestartPolicy(save_every=3)
    with pytest.raises(RuntimeError, match="injected"):
        control_plane.run_resumable(cfg, schedule, 8, crash_mgr, policy,
                                    fail_at=5)
    resumed, plane = control_plane.run_resumable(cfg, schedule, 8, crash_mgr,
                                                 policy)
    for leaf_a, leaf_b in zip(jax.tree.leaves(clean),
                              jax.tree.leaves(resumed)):
        assert np.array_equal(np.asarray(leaf_a), np.asarray(leaf_b))
    assert plane.period == 8
    ref = plane.replay_reference()       # trace survives the crash/restore
    assert np.asarray(ref["history"]["b"]).shape[0] == 8


def test_daemon_stale_decision_on_deadline_miss():
    """Solver overrun -> the daemon serves the previous allocation rescaled
    to the live mask, flags and counts it, and the in-flight solve still
    commits; the fresh-solve stream stays stale-free."""
    daemon = allocd.AllocDaemon(ControlPlaneConfig(**_PERSIST))

    async def drive():
        daemon.submit(allocd.Admit("a", 4))
        daemon.submit(allocd.Admit("b", 3))
        await daemon.step_period()               # compile + fresh
        daemon.solver_timeout_s = 0.02
        daemon._solver_delay_s = 0.4
        stale = await daemon.step_period()       # deadline miss
        daemon.solver_timeout_s = None
        daemon._solver_delay_s = 0.0
        fresh = await daemon.step_period()       # pending solve commits
        await daemon.close()
        return stale, fresh

    stale, fresh = asyncio.run(drive())
    assert stale.stale and not fresh.stale
    assert daemon.plane.metrics["stale_decisions"] == 1
    assert [d.stale for d in daemon.served] == [False, True, False]
    assert not any(d.stale for d in daemon.plane.decisions)
    # budget-preserving rescale over the live slots
    B = daemon.plane.net.total_bandwidth_mhz
    np.testing.assert_allclose(stale.b.sum(), B, rtol=1e-5)


def test_daemon_records_rejections_instead_of_raising():
    # admit_max_retries=0 keeps capacity rejections immediate (the retry
    # path is covered by tests/test_chaos.py)
    daemon = allocd.AllocDaemon(ControlPlaneConfig(capacity=1, k_max=4,
                                                   rounds_required=10_000),
                                admit_max_retries=0)

    async def drive():
        daemon.submit(allocd.Admit("a", 3))
        daemon.submit(allocd.Admit("b", 3))      # no free slot
        daemon.submit(allocd.Heartbeat("ghost"))
        await daemon.step_period()
        await daemon.close()

    asyncio.run(drive())
    assert len(daemon.rejections) == 2
    assert daemon.plane.metrics["admitted"] == 1


def test_daemon_capacity_rejection_retries_before_giving_up():
    """With retries enabled, a full-capacity admit is queued with period
    backoff instead of rejected on the spot -- and only rejected once the
    bounded attempts are exhausted."""
    daemon = allocd.AllocDaemon(ControlPlaneConfig(capacity=1, k_max=4,
                                                   rounds_required=10_000),
                                admit_max_retries=2)

    async def drive():
        daemon.submit(allocd.Admit("a", 3))
        daemon.submit(allocd.Admit("b", 3))      # no free slot -> queued
        await daemon.step_period()
        first = len(daemon.rejections)
        # backoff is 1 then 2 periods; by period 4 both retries have fired
        for _ in range(4):
            await daemon.step_period()
        await daemon.close()
        return first

    rejected_at_first_period = asyncio.run(drive())
    assert rejected_at_first_period == 0
    assert daemon._retry_queue == []
    assert daemon.plane.metrics["admit_retries"] >= 2
    assert len(daemon.rejections) == 1
    sid, reason = daemon.rejections[0]
    assert sid == "b" and "gave up after 2 retries" in reason


def test_daemon_checkpoint_restart_resumes(tmp_path):
    mgr = CheckpointManager(tmp_path / "cp")
    cfg = ControlPlaneConfig(**_PERSIST)
    d1 = allocd.AllocDaemon(cfg, manager=mgr, save_every=2)
    assert not d1.resumed

    async def drive(daemon, n):
        daemon.submit(allocd.Admit("a", 4))
        for _ in range(n):
            await daemon.step_period()
        await daemon.close()

    asyncio.run(drive(d1, 5))
    d2 = allocd.AllocDaemon(cfg, manager=mgr, save_every=2)
    assert d2.resumed and d2.plane.period == 5
    assert "a" in d2.plane.services


def test_replay_requires_matched_override_pair():
    from repro.fl import simulator
    cfg = simulator.SimConfig(n_services_total=4, max_periods=2,
                              rounds_required=10, collect_history=True)
    with pytest.raises(ValueError, match="arrivals"):
        simulator.run_scan(cfg, arrivals=np.zeros(4, np.int32))


def test_collect_alloc_requires_history():
    from repro.fl import simulator
    cfg = simulator.SimConfig(n_services_total=4, max_periods=2,
                              rounds_required=10, collect_history=False,
                              collect_alloc=True)
    with pytest.raises(ValueError, match="collect_alloc"):
        simulator.run_scan(cfg)


# ---------------------------------------------------------------------------
# Entry points: allocd --check, the compile cache, chip_smoke.py.
# ---------------------------------------------------------------------------

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _skew_replay(monkeypatch):
    real = ControlPlane.replay_reference

    def skewed(self):
        out = real(self)
        out["history"]["b"] = np.asarray(out["history"]["b"]) + 1.0
        return out

    monkeypatch.setattr(ControlPlane, "replay_reference", skewed)


def _break_replayability(monkeypatch):
    real = ControlPlane.tick

    def tick(self):
        self._mark_unreplayable("injected fault")
        return real(self)

    monkeypatch.setattr(ControlPlane, "tick", tick)


@pytest.mark.parametrize("fault,rc", [
    (None, 0),
    (_skew_replay, 1),
    (_break_replayability, 1),
])
def test_allocd_check_exit_code(monkeypatch, tmp_path, fault, rc):
    """``allocd --check`` exits non-zero on a replay mismatch and on a
    trace it cannot replay, never just printing the verdict."""
    # Set before the call, the variable makes the cache helper a no-op.
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    if fault is not None:
        fault(monkeypatch)
    argv = ["--capacity", "8", "--k-max", "6", "--periods", "6",
            "--rate", "1.0", "--rounds", "100000", "--check"]
    assert allocd.main(argv) == rc


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(_REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", _REPO_ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_chip_smoke_refuses_a_host_without_tpu(capsys, argv):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert _chip_smoke().main(argv) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_paper_cell_replays_at_toy_size():
    """The smoke's served paper-cell phase (admissions, replay, counters) at
    a size the CPU runs in seconds."""
    _chip_smoke().paper_cell(capacity=8, k_max=48, n_serve=4)
