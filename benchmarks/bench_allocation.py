"""Per-period allocation micro-benchmark -> repo-root ``BENCH_allocation.json``.

The long-term simulation re-solves the inter-service allocation every period;
this benchmark pins the wall-clock of that per-period solve so future PRs
have a perf trajectory (the first entry of the repo's BENCH series).

Measured on real wall-clock (jitted, median of repeats):

* ``coop`` market clearing at N services: the cold ``solve_lambda_bisect``
  (48 dual bisection trips x 48 inner trips per demand evaluation) vs the
  warm-started safeguarded Newton ``solve_lambda_newton_warm`` (<= 6 fused
  demand+slope evaluations seeded from the previous period's dual price).
  On CPU hosts the fused demand evaluation dispatches to the pure-jnp
  reference (the ``kernels/ops.dual_demand`` convention); the Pallas kernel
  itself is additionally timed in interpret mode for the record -- interpret
  timings validate numerics, they do not represent TPU performance.
* auction charge computation across an N sweep: the leave-one-out clearing
  rerun (O(N^2 M log NM)) vs the closed-form prefix-sum path (O(NM log NM)),
  with fitted log-log scaling exponents.
* (schema v2) the market N-sweep: warm + cold clearing wall-clock at
  N = 64 .. 8192 services per dual-solve backend -- the pure-jnp reference
  vs the whole-market ``market_clear`` megakernel (ONE fused launch for the
  entire safeguarded-Newton iteration; compiled on TPU, interpret mode
  recorded off-TPU) -- with fitted log-log scaling exponents and the
  megakernel's max deviation vs the reference finals at every swept N.

Usage:
  PYTHONPATH=src python -m benchmarks.bench_allocation [--tiny] [--out PATH]

``--tiny`` shrinks every size for the CI smoke step (same schema, same
validation path, seconds instead of minutes).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import common
from repro.compile_cache import enable_compile_cache
from repro.core import auction, disba, network
from repro.core.types import mask_inactive

SCHEMA = "bench_allocation/v2"
DEFAULT_OUT = "BENCH_allocation.json"

# Log-spaced market sizes; the smallest sits below the megakernel's 128-row
# tile (pad-up edge), the largest is the ROADMAP's 8192-service regime.
MARKET_NS_FULL = (64, 256, 1024, 4096, 8192)
MARKET_NS_TINY = (16, 48)
MARKET_MASKED_FRACTION = 0.1   # ~10% inactive fixed-capacity slots


def _fit_exponent(ns, us) -> float:
    """Least-squares slope of log(time) vs log(N)."""
    return float(np.polyfit(np.log(np.asarray(ns, float)),
                            np.log(np.asarray(us, float)), 1)[0])


def _bench_coop(n: int, k: int, repeats: int, time_kernel: bool) -> dict:
    svc, _ = network.sample_services(jax.random.key(2), n, k_max=k)
    B = network.B_TOTAL_MHZ
    ref = disba.solve_lambda_bisect(svc, B)
    # The "previous period" seed: the same market moved a few percent, the
    # temporal coherence the warm start exploits.
    lam_prev = ref.lam * jnp.float32(1.03)

    us_cold = common.time_fn(
        lambda: disba.solve_lambda_bisect(svc, B), iters=repeats)
    us_warm = common.time_fn(
        lambda: disba.solve_lambda_newton_warm(svc, B, lam_prev),
        iters=repeats)
    us_newton_cold = common.time_fn(
        lambda: disba.solve_lambda_newton(svc, B), iters=repeats)
    warm = disba.solve_lambda_newton_warm(svc, B, lam_prev)
    dev = float(jnp.max(jnp.abs(warm.b - ref.b)))

    out = {
        "n": n,
        "k": k,
        "cold_bisect_us": us_cold,
        "warm_newton_us": us_warm,
        "cold_newton12_us": us_newton_cold,
        "speedup_warm_vs_cold": us_cold / us_warm,
        "warm_vs_cold_max_dev_mhz": dev,
        "dual_evals": {"cold_bisect": disba.BISECT_ITERS,
                       "warm_newton": disba.WARM_ITERS},
    }
    if time_kernel:
        # Interpret-mode launch of the fused kernel (numerical deployment
        # path off-TPU is the jnp reference; this row only records that the
        # kernel runs and agrees -- see EXPERIMENTS.md §Perf).
        kern = jax.jit(lambda lp: disba.solve_lambda_newton_warm(
            svc, B, lp, backend="pallas"))
        out["warm_newton_kernel_interpret_us"] = common.time_fn(
            lambda: kern(lam_prev), iters=max(2, repeats // 3))
        out["kernel_vs_reference_max_dev_mhz"] = float(
            jnp.max(jnp.abs(kern(lam_prev).b - warm.b)))
    return out


def _bench_market(ns: tuple[int, ...], k: int, repeats: int) -> dict:
    """The schema-v2 N-sweep: cold + warm whole-market clearing per backend.

    ``reference`` is the pure-jnp solver (cold: 12 safeguarded-Newton trips
    with full-depth inner bisections; warm: the 6-trip warm-started variant).
    ``megakernel`` is the single fused ``market_clear`` Pallas launch behind
    ``backend="megakernel"`` with the *same* trip configuration -- compiled
    on TPU, interpret mode elsewhere (interpret timings validate numerics
    and scaling shape, not absolute TPU performance).  Every swept N also
    records the kernel's max deviation vs the reference finals on a masked
    market (~10% inactive fixed-capacity slots riding in the padding).
    """
    B = network.B_TOTAL_MHZ
    kernel_mode = ("compiled" if jax.default_backend() == "tpu"
                   else "interpret")
    sweep = []
    for n in ns:
        svc, _ = network.sample_services(jax.random.key(5), n, k_max=k)
        n_off = max(1, round(n * MARKET_MASKED_FRACTION))
        svc = mask_inactive(svc, jnp.arange(n) >= n_off)

        ref_cold = jax.jit(lambda s=svc: disba.solve_lambda_newton(s, B))
        ref_warm = jax.jit(lambda lp, s=svc: disba.solve_lambda_newton_warm(
            s, B, lp))
        kern_cold = jax.jit(lambda s=svc: disba.solve_lambda_newton_warm(
            s, B, disba.WARM_COLD, iters=12,
            newton_inner_iters=disba.BISECT_ITERS, backend="megakernel"))
        kern_warm = jax.jit(lambda lp, s=svc: disba.solve_lambda_newton_warm(
            s, B, lp, backend="megakernel"))

        # The "previous period" seed the warm paths exploit.
        lam_prev = ref_cold().lam * jnp.float32(1.03)
        warm = ref_warm(lam_prev)
        kwarm = kern_warm(lam_prev)
        dev = float(jnp.max(jnp.abs(kwarm.b - warm.b)))
        dev = max(dev, float(jnp.max(jnp.abs(kern_cold().b - ref_cold().b))))

        row = {
            "n": n,
            "k": k,
            "reference": {
                "cold_us": common.time_fn(ref_cold, iters=repeats),
                "warm_us": common.time_fn(lambda: ref_warm(lam_prev),
                                          iters=repeats),
            },
            "megakernel": {
                "mode": kernel_mode,
                "cold_us": common.time_fn(kern_cold, iters=repeats),
                "warm_us": common.time_fn(lambda: kern_warm(lam_prev),
                                          iters=repeats),
            },
            "max_dev_vs_reference_mhz": dev,
        }
        row["speedup_warm_vs_cold_reference"] = (
            row["reference"]["cold_us"] / row["reference"]["warm_us"])
        sweep.append(row)

    ns_list = [r["n"] for r in sweep]
    return {
        "ns": list(ns),
        "k": k,
        "masked_fraction": MARKET_MASKED_FRACTION,
        "kernel_mode": kernel_mode,
        "dual_trips": {"cold": 12, "warm": disba.WARM_ITERS},
        "sweep": sweep,
        "scaling_exponent": {
            "reference_cold": _fit_exponent(
                ns_list, [r["reference"]["cold_us"] for r in sweep]),
            "reference_warm": _fit_exponent(
                ns_list, [r["reference"]["warm_us"] for r in sweep]),
            "megakernel_cold": _fit_exponent(
                ns_list, [r["megakernel"]["cold_us"] for r in sweep]),
            "megakernel_warm": _fit_exponent(
                ns_list, [r["megakernel"]["warm_us"] for r in sweep]),
        },
        "note": ("interpret-mode megakernel timings exercise the exact "
                 "launch geometry off-TPU; absolute numbers are not TPU "
                 "performance"),
    }


def _bench_auction(ns: tuple[int, ...], k: int, n_bids: int,
                   repeats: int) -> dict:
    B = network.B_TOTAL_MHZ
    sweep = []
    for n in ns:
        svc, _ = network.sample_services(jax.random.key(3), n, k_max=k)
        bid = auction.uniform_truthful_bids(svc, n_bids, 0.5)
        b, _ = auction.allocate(bid, B)
        rerun = jax.jit(lambda s, bd, bb: auction.charges(
            s, bd, bb, B, 0.5, method="rerun"))
        prefix = jax.jit(lambda s, bd, bb: auction.charges(
            s, bd, bb, B, 0.5, method="prefix"))
        np.testing.assert_allclose(
            np.asarray(rerun(svc, bid, b)), np.asarray(prefix(svc, bid, b)),
            rtol=1e-3, atol=1e-3)
        us_rerun = common.time_fn(lambda: rerun(svc, bid, b), iters=repeats)
        us_prefix = common.time_fn(lambda: prefix(svc, bid, b), iters=repeats)
        sweep.append({"n": n, "rerun_us": us_rerun, "prefix_us": us_prefix,
                      "speedup": us_rerun / us_prefix})
    return {
        "n_bids": n_bids,
        "k": k,
        "sweep": sweep,
        "scaling_exponent": {
            "rerun": _fit_exponent([r["n"] for r in sweep],
                                   [r["rerun_us"] for r in sweep]),
            "prefix": _fit_exponent([r["n"] for r in sweep],
                                    [r["prefix_us"] for r in sweep]),
        },
    }


def run(tiny: bool = False, time_kernel: bool | None = None) -> dict:
    if time_kernel is None:
        time_kernel = tiny or jax.default_backend() == "tpu"
    coop_n, coop_k = (16, 8) if tiny else (64, 32)
    auction_ns = (8, 16, 32) if tiny else (32, 64, 128, 256, 512)
    market_ns = MARKET_NS_TINY if tiny else MARKET_NS_FULL
    repeats = 3 if tiny else 10
    return {
        "schema": SCHEMA,
        "tiny": tiny,
        **common.provenance(),
        "b_total_mhz": network.B_TOTAL_MHZ,
        "coop": _bench_coop(coop_n, coop_k, repeats, time_kernel),
        "auction_charges": _bench_auction(auction_ns, 8 if tiny else 16,
                                          5, repeats),
        "market_sweep": _bench_market(market_ns, 8 if tiny else 32,
                                      3 if tiny else 5),
    }


def validate(data: dict) -> None:
    """Schema check used by CI and tests: required keys present + parseable
    numbers."""
    assert data["schema"] == SCHEMA
    common.validate_provenance(data)
    coop = data["coop"]
    for key in ("cold_bisect_us", "warm_newton_us", "speedup_warm_vs_cold",
                "warm_vs_cold_max_dev_mhz"):
        assert isinstance(coop[key], (int, float)), key
    assert coop["dual_evals"]["warm_newton"] < coop["dual_evals"]["cold_bisect"]
    sweep = data["auction_charges"]["sweep"]
    assert len(sweep) >= 2
    for row in sweep:
        assert row["rerun_us"] > 0 and row["prefix_us"] > 0
    assert isinstance(
        data["auction_charges"]["scaling_exponent"]["prefix"], float)
    market = data["market_sweep"]
    assert market["kernel_mode"] in ("interpret", "compiled")
    assert len(market["sweep"]) >= 2
    if not data["tiny"]:
        assert max(market["ns"]) >= 4096, \
            "full runs must sweep the >=4096-service regime"
    for row in market["sweep"]:
        for backend in ("reference", "megakernel"):
            assert row[backend]["cold_us"] > 0 and row[backend]["warm_us"] > 0
        # exact-to-dtype across the whole sweep; the committed value is the
        # measured deviation, this is only the sanity ceiling
        assert row["max_dev_vs_reference_mhz"] < 1e-2, row["n"]
    for key in ("reference_cold", "reference_warm",
                "megakernel_cold", "megakernel_warm"):
        assert isinstance(market["scaling_exponent"][key], float), key


def run_rows(tiny: bool = False) -> list[dict]:
    """benchmarks.run adapter: execute the study, write the JSON, and return
    the usual ``name,us_per_call,derived`` rows.  Tiny (CI-sized) runs land
    in artifacts/bench/ so they never clobber the committed repo-root
    trajectory; full runs refresh ``BENCH_allocation.json`` itself."""
    data = run(tiny=tiny)
    validate(data)
    if tiny:
        common.save_artifact("bench_allocation_tiny", data)
    else:
        with open(DEFAULT_OUT, "w") as fp:
            json.dump(data, fp, indent=1, default=float)
            fp.write("\n")
    coop = data["coop"]
    rows = [
        common.row(f"allocation/coop_cold_bisect_N{coop['n']}",
                   coop["cold_bisect_us"], ""),
        common.row(f"allocation/coop_warm_newton_N{coop['n']}",
                   coop["warm_newton_us"],
                   f"speedup={coop['speedup_warm_vs_cold']:.1f}x "
                   f"max_dev={coop['warm_vs_cold_max_dev_mhz']:.2e}"),
    ]
    for row in data["auction_charges"]["sweep"]:
        rows.append(common.row(
            f"allocation/charges_prefix_N{row['n']}", row["prefix_us"],
            f"rerun_us={row['rerun_us']:.0f} speedup={row['speedup']:.1f}x"))
    exps = data["auction_charges"]["scaling_exponent"]
    rows.append(common.row(
        "allocation/charges_scaling", None,
        f"rerun_exp={exps['rerun']:.2f} prefix_exp={exps['prefix']:.2f}"))
    market = data["market_sweep"]
    for row in market["sweep"]:
        rows.append(common.row(
            f"allocation/market_megakernel_warm_N{row['n']}",
            row["megakernel"]["warm_us"],
            f"ref_warm_us={row['reference']['warm_us']:.0f} "
            f"mode={row['megakernel']['mode']} "
            f"max_dev={row['max_dev_vs_reference_mhz']:.2e}"))
    mexp = market["scaling_exponent"]
    rows.append(common.row(
        "allocation/market_scaling", None,
        f"ref_warm=N^{mexp['reference_warm']:.2f} "
        f"kernel_warm=N^{mexp['megakernel_warm']:.2f} "
        f"({market['kernel_mode']})"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (seconds, interpret-mode kernel row)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help=f"output path (default: {DEFAULT_OUT} at repo root)")
    args = ap.parse_args()
    enable_compile_cache()
    data = run(tiny=args.tiny)
    validate(data)
    with open(args.out, "w") as fp:
        json.dump(data, fp, indent=1, default=float)
        fp.write("\n")
    coop = data["coop"]
    print(f"coop N={coop['n']}: cold {coop['cold_bisect_us']:.0f}us -> "
          f"warm {coop['warm_newton_us']:.0f}us "
          f"({coop['speedup_warm_vs_cold']:.1f}x)")
    for row in data["auction_charges"]["sweep"]:
        print(f"auction charges N={row['n']}: rerun {row['rerun_us']:.0f}us "
              f"prefix {row['prefix_us']:.0f}us ({row['speedup']:.1f}x)")
    exps = data["auction_charges"]["scaling_exponent"]
    print(f"charge scaling exponents: rerun N^{exps['rerun']:.2f} "
          f"prefix N^{exps['prefix']:.2f}")
    market = data["market_sweep"]
    for row in market["sweep"]:
        print(f"market N={row['n']}: ref cold "
              f"{row['reference']['cold_us']:.0f}us warm "
              f"{row['reference']['warm_us']:.0f}us | megakernel "
              f"({row['megakernel']['mode']}) cold "
              f"{row['megakernel']['cold_us']:.0f}us warm "
              f"{row['megakernel']['warm_us']:.0f}us "
              f"max_dev={row['max_dev_vs_reference_mhz']:.2e}")
    mexp = market["scaling_exponent"]
    print(f"market scaling exponents: ref cold N^"
          f"{mexp['reference_cold']:.2f} warm N^{mexp['reference_warm']:.2f} "
          f"| megakernel cold N^{mexp['megakernel_cold']:.2f} "
          f"warm N^{mexp['megakernel_warm']:.2f}")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
