"""Faults planted in the timed path for the benchmark's CPU tests, and the
fixtures that hold a tiny copy of the benchmark."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from benchutil import tiny_copy

from repro.fl import simulator


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


def _fresh_step_caches():
    simulator._fleet_fn.cache_clear()
    jax.clear_caches()


@pytest.fixture
def broken(monkeypatch):
    """Install a fault in the period step that ``run_fleet`` scans."""
    original = simulator._period_step

    def install(fault):
        def step(*args, **kwargs):
            return fault(args, original(*args, **kwargs))

        monkeypatch.setattr(simulator, "_period_step", step)
        _fresh_step_caches()

    yield install
    monkeypatch.setattr(simulator, "_period_step", original)
    _fresh_step_caches()


def altered(args, out):
    """The largest share, or every frequency, off by a few percent."""
    *carry, stats, extras = out
    b = extras["b"]
    b = b * jnp.where(jnp.arange(b.shape[0]) == jnp.argmax(b), 1.02, 1.0)
    f = extras["f"] * 1.05
    rounds_done = jnp.minimum(
        args[0] + jnp.where(extras["active"], jnp.floor(f * 20.0), 0)
        .astype(jnp.int32), carry[0].max())
    return (rounds_done, *carry[1:], stats, dict(extras, b=b, f=f))


def state_unchanged(args, out):
    """The step returns the carry it was given."""
    *_, stats, extras = out
    return (*args[:5], stats, extras)
