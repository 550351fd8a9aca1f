"""Benchmark-harness tests: roofline composition math, collective-byte HLO
parsing, the per-period policy ordering that Figs. 11-12 rely on,
MODEL_FLOPS sanity for dense vs MoE archs, and schema validation of the
committed repo-root BENCH_*.json trajectory artifacts."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import roofline
from repro.launch.dryrun import collective_bytes, _shape_bytes

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("artifact,validator_module", [
    ("BENCH_allocation.json", "bench_allocation"),
    ("BENCH_fleet.json", "bench_fleet"),
    ("BENCH_cotrain.json", "paper_figs_cotrain"),
    ("BENCH_serve.json", "bench_serve"),
    ("BENCH_fault.json", "bench_fault"),
    ("BENCH_robust.json", "bench_robust"),
])
def test_committed_bench_artifacts_validate(artifact, validator_module):
    """The repo-root bench trajectory must stay machine-reconstructable:
    every committed artifact parses, passes its schema checker, and carries
    the commit/date/backend provenance stamp."""
    import importlib
    import warnings

    mod = importlib.import_module(f"benchmarks.{validator_module}")
    with open(os.path.join(_REPO_ROOT, artifact)) as fp:
        data = json.load(fp)
    mod.validate(data)
    assert data["tiny"] is False, f"{artifact} must be a full-size run"
    if data.get("dirty"):
        warnings.warn(
            f"\n{'!' * 70}\n"
            f"{artifact} carries a DIRTY provenance stamp: the numbers were\n"
            f"measured with uncommitted changes on top of commit\n"
            f"{data.get('commit', '?')[:12]}, so that commit alone does NOT\n"
            f"reproduce them.  Regenerate from a clean tree (commit the code\n"
            f"first, run the bench, then commit the artifact).\n"
            f"{'!' * 70}",
            UserWarning, stacklevel=2)


def test_shape_bytes_parser():
    assert _shape_bytes("f32[16,128]") == 16 * 128 * 4
    assert _shape_bytes("bf16[2,4,8]") == 2 * 4 * 8 * 2
    assert _shape_bytes("pred[10]") == 10
    assert _shape_bytes("f32[8] u8[4]") == 36


def test_collective_bytes_parser():
    hlo = """
  %all-reduce.1 = f32[256,1024]{1,0} all-reduce(%x), replica_groups={}
  %ag = bf16[64,64]{1,0} all-gather-start(%y), dimensions={0}
  %t = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%a, %b)
  %other = f32[2,2]{1,0} add(%p, %q)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 256 * 1024 * 4
    assert out["all-gather"] == 64 * 64 * 2
    assert out["all-to-all"] == 2 * 8 * 8 * 4
    assert "add" not in out


def test_corrected_terms_composition():
    cell = {"arch": "gemma-2b", "shape": "train_4k", "n_chips": 256,
            "flops": 1e12, "bytes_accessed": 1e11,
            "collective_bytes": {"all-reduce": 1e9}}
    block = {"flops": 5e11, "bytes_accessed": 5e10,
             "collective_bytes": {"all-gather": 2e8}}
    out = roofline.corrected_terms(cell, block, trips=2)
    np.testing.assert_allclose(out["flops_corrected"], 1e12 + 5e11)
    np.testing.assert_allclose(out["collective_bytes_corrected"], 1e9 + 2e8)
    assert out["bottleneck"] in ("compute", "memory", "collective")
    assert 0 < out["roofline_fraction"] <= 1.0


def test_model_flops_moe_counts_active_only():
    dense = roofline.model_flops("gemma-2b", "train_4k")
    moe_total = roofline.model_flops("deepseek-v2-236b", "train_4k")
    # deepseek-v2 has ~21B active of 236B total; active FLOPs must be far
    # below 6*236e9*tokens
    from repro import configs
    total_params = configs.get_config("deepseek-v2-236b").param_count()
    tokens = 4096 * 256
    assert moe_total < 0.25 * 6 * total_params * tokens
    assert dense > 0


def test_per_period_policy_ordering():
    """The structural claim behind Fig. 11: on the proportional-fairness
    objective, Coop >= ES/PP/EC for any drawn period."""
    from repro.core import baselines, disba, network
    for seed in range(3):
        svc, _ = network.sample_services(jax.random.key(seed), 5)
        B = network.B_TOTAL_MHZ
        coop = float(jnp.sum(jnp.log1p(disba.solve_lambda_bisect(svc, B).f)))
        for fn in (baselines.equal_client, baselines.equal_service,
                   baselines.proportional):
            _, f = fn(svc, B)
            assert coop >= float(jnp.sum(jnp.log1p(f))) - 1e-5


def test_bench_fleet_measures_in_process_on_an_accelerator(monkeypatch,
                                                           tmp_path):
    """Where the parent holds accelerator devices a child could not reach
    them, so every device count is measured in-process: no worker spawns."""
    from benchmarks import bench_fleet
    from repro.compile_cache import CACHE_ENV

    def no_spawn(*args, **kwargs):
        raise AssertionError("spawned a worker process")

    # Set before the call, the variable makes the cache helper a no-op.
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    monkeypatch.setattr(bench_fleet, "_accelerator_devices", lambda: 1)
    monkeypatch.setattr(bench_fleet, "_spawn_worker", no_spawn)
    data = bench_fleet.run(tiny=True)
    bench_fleet.validate(data)
    assert {row["devices"] for row in data["rows"]} == {1}
