"""Byzantine-robustness benchmark -> repo-root ``BENCH_robust.json``.

``BENCH_fault.json`` pinned the stack against *infrastructure* faults; this
artifact pins it against *adversarial participants* on both markets:

* **Breakdown curves** -- the tuned co-trained episode (see EXPERIMENTS.md
  §Adversarial robustness) runs every registered aggregator against the
  client-attack catalogue (``chaos.clients``) across Byzantine fractions,
  recording final bigram accuracy, the drop vs the clean baseline, and
  whether the served model stayed finite.  The curves show plain FedAvg
  collapsing under a 20% sign-flip cohort while the robust registry
  (trimmed-mean / median / norm-clip / Krum) holds within
  ``chaos.invariants.ROBUST_ACC_DROP`` -- and a NaN cohort poisoning FedAvg
  outright while every robust aggregator masks it.
* **Manipulation-gain curves** -- seeded unilateral bid deviations
  (``chaos.bids``) against the fairness-adjusted auction, per deviation kind
  and magnitude: the empirical gain must stay under the Eq. 31 truthfulness
  gap (``invariants.regret_bounded``), which is the paper's Prop. 5 checked
  by attack rather than by algebra.
* **Determinism** -- every attacked episode runs twice from its spec; the
  trajectory digests must match bitwise (the attack rides the PR 8 chaos
  channels, so the whole adversarial trajectory replays from the seed).
  The allocation stream of every attacked run is also checked bitwise
  against the duration engine: the adversary corrupts uploads, never the
  market.

Usage:
  PYTHONPATH=src python -m benchmarks.bench_robust [--tiny] [--out PATH]

``--tiny`` shrinks the grid to 2 attacks x 2 aggregators for the CI smoke
step (same schema, same validation path; the accuracy-separation gate is
full-size only -- tiny episodes are too short to separate).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os

from repro.compile_cache import enable_compile_cache

SCHEMA = "bench_robust/v1"
DEFAULT_OUT = "BENCH_robust.json"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Aggregators whose rows must pass the robustness gates (fedavg is the
# deliberately breakable seed path -- its breakage is *recorded*).
_ROBUST = ("trimmed_mean", "median", "norm_clip", "krum", "multi_krum")


def _plan(tiny: bool) -> dict:
    if tiny:
        return {
            "aggregators": ["fedavg", "median"],
            "attacks": {"sign_flip": [0.2], "nan": [0.2]},
            "scale": 20.0, "attack_seed": 1,
            "trim_frac": 0.25, "byz_f": 2,
            "episode": {"policy": "coop", "n_services_total": 2,
                        "rounds_required": 10, "p_arrive": 2.0,
                        "max_periods": 16, "k_max": 8,
                        "mean_clients": 5.0, "var_clients": 1.0},
            "train": {"vocab": 16, "seq_len": 6, "batch_size": 2,
                      "eval_batch": 8, "rounds_cap": 2},
            "bid": {"n_providers": 4, "n_trials": 6, "n_bids": 5,
                    "seed": 7, "factors": {"overbid": [2.0, 4.0],
                                           "shade": [0.3, 0.7],
                                           "free_ride": [0.0]}},
        }
    return {
        "aggregators": ["fedavg", "trimmed_mean", "median", "norm_clip",
                        "krum", "multi_krum"],
        "attacks": {"sign_flip": [0.1, 0.2, 0.3],
                    "scaled_delta": [0.1, 0.2, 0.3],
                    "nan": [0.2]},
        "scale": 20.0, "attack_seed": 1,
        "trim_frac": 0.25, "byz_f": 3,
        "episode": {"policy": "coop", "n_services_total": 2,
                    "rounds_required": 40, "p_arrive": 2.0,
                    "max_periods": 60, "k_max": 12,
                    "mean_clients": 9.0, "var_clients": 1.0},
        "train": {"vocab": 16, "seq_len": 6, "batch_size": 2,
                  "eval_batch": 32, "rounds_cap": 3},
        "bid": {"n_providers": 6, "n_trials": 24, "n_bids": 5,
                "seed": 7, "factors": {"overbid": [1.5, 2.0, 3.0, 4.0],
                                       "shade": [0.2, 0.4, 0.6, 0.8],
                                       "free_ride": [0.0]}},
    }


def _scenario(plan: dict):
    from repro.core import network
    from repro.fl import cotrain, simulator

    ep = plan["episode"]
    cfg = simulator.SimConfig(**ep)
    net = network.NetworkConfig(period_s=1.0,
                                mean_clients=ep["mean_clients"],
                                var_clients=ep["var_clients"])
    train = cotrain.TrainSpec(**plan["train"])
    return cfg, net, train


def _episode(plan: dict, aggregator: str | None, attack: str | None,
             byz_frac: float) -> dict:
    """One co-trained episode; ``aggregator=None`` is the clean FedAvg
    baseline.  Returns final accuracy, params finiteness, the duration
    stream, and a bitwise trajectory digest."""
    import dataclasses

    import jax
    import numpy as np

    from repro.chaos import invariants
    from repro.chaos.clients import AttackSpec
    from repro.fl import cotrain

    cfg, net, train = _scenario(plan)
    if aggregator is None:
        out = cotrain.run_cotrain_scan(cfg, train, net)
    else:
        spec = dataclasses.replace(train, aggregator=aggregator,
                                   trim_frac=plan["trim_frac"],
                                   byz_f=plan["byz_f"])
        atk = AttackSpec(attack=attack, byz_frac=byz_frac,
                         scale=plan["scale"], seed=plan["attack_seed"])
        out = cotrain.run_cotrain_scan(cfg, spec, net, attack=atk)
    acc_hist = np.asarray(out["history"]["acc"])
    digest = hashlib.sha256()
    digest.update(acc_hist.tobytes())
    digest.update(np.asarray(out["durations"], np.int64).tobytes())
    for leaf in jax.tree.leaves(out["params"]):
        digest.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    final_acc = float(acc_hist[out["periods"] - 1].mean())
    return {
        "final_acc": final_acc,
        "params_finite": bool(invariants.params_finite(out["params"])["ok"]),
        "durations": [int(d) for d in out["durations"]],
        "digest": digest.hexdigest(),
    }


def _breakdown_rows(plan: dict) -> tuple[dict, list[dict]]:
    """Clean baseline + every aggregator x attack x fraction, each attacked
    episode run twice (the second hits the jit cache) to pin determinism,
    and every duration stream checked bitwise against the duration engine."""
    from repro.fl import simulator

    cfg, net, _ = _scenario(plan)
    engine = simulator.run_scan(cfg, net)["durations"]

    clean = _episode(plan, None, None, 0.0)
    clean["durations_match_engine"] = clean["durations"] == engine
    rows = []
    for agg in plan["aggregators"]:
        for attack, fracs in plan["attacks"].items():
            for frac in fracs:
                r1 = _episode(plan, agg, attack, frac)
                r2 = _episode(plan, agg, attack, frac)
                rows.append({
                    "aggregator": agg, "attack": attack, "byz_frac": frac,
                    "final_acc": r1["final_acc"],
                    "drop": clean["final_acc"] - r1["final_acc"],
                    "params_finite": r1["params_finite"],
                    "digest": r1["digest"],
                    "digest_repeat_equal": r1["digest"] == r2["digest"],
                    "durations_match_engine": r1["durations"] == engine,
                })
    return clean, rows


def _bid_section(plan: dict) -> dict:
    """Manipulation-gain curves (per deviation kind and magnitude, worst
    provider) + the seeded BidChaos campaign, gated by Eq. 31."""
    import jax
    import numpy as np

    from repro.chaos import invariants
    from repro.chaos.bids import BidChaos, audit_deviation
    from repro.core import network

    bp = plan["bid"]
    svc, _ = network.sample_services(jax.random.key(0), bp["n_providers"])
    B = network.B_TOTAL_MHZ

    curves = []
    for kind, factors in bp["factors"].items():
        for factor in factors:
            audits = [audit_deviation(svc, B, n, kind, factor,
                                      n_bids=bp["n_bids"])
                      for n in range(bp["n_providers"])]
            worst = max(audits, key=lambda r: r["gain"] - r["delta_bound"])
            curves.append({
                "deviation": kind, "factor": factor,
                "max_gain": float(max(r["gain"] for r in audits)),
                "worst_excess": float(worst["gain"] - worst["delta_bound"]),
                "delta_bound": worst["delta_bound"],
                "bounded": bool(all(r["gain"] <= r["delta_bound"] + 1e-3
                                    for r in audits)),
            })

    trials = BidChaos(seed=bp["seed"]).run(svc, B, bp["n_trials"],
                                           n_bids=bp["n_bids"])
    replay = BidChaos(seed=bp["seed"]).run(svc, B, bp["n_trials"],
                                           n_bids=bp["n_bids"])
    gate = invariants.regret_bounded(trials)
    return {
        "n_providers": bp["n_providers"],
        "total_bandwidth_mhz": float(B),
        "curves": curves,
        "trials": trials,
        "trials_replay_equal": trials == replay,
        "regret_gate": {k: v for k, v in gate.items()},
        "worst_gain": float(max((r["gain"] for r in trials), default=0.0)),
    }


def run(tiny: bool = False) -> dict:
    from benchmarks import common

    plan = _plan(tiny)
    clean, rows = _breakdown_rows(plan)
    return {
        "schema": SCHEMA,
        "tiny": tiny,
        **common.provenance(),
        "plan": plan,
        "clean": clean,
        "rows": rows,
        "bids": _bid_section(plan),
    }


def validate(data: dict) -> None:
    """Schema check used by CI and tests: every attacked episode replays
    bitwise and leaves the allocation stream untouched; robust-aggregator
    rows keep finite params unconditionally; on the full grid the robust
    registry holds the ``ROBUST_ACC_DROP`` accuracy gate at <=20% Byzantine
    clients where plain FedAvg demonstrably breaks; no audited bid deviation
    beats the Eq. 31 truthfulness bound."""
    from benchmarks import common
    from repro.chaos.invariants import ROBUST_ACC_DROP

    assert data["schema"] == SCHEMA
    common.validate_provenance(data)
    assert data["rows"], "no breakdown rows"
    assert data["clean"]["durations_match_engine"] is True, (
        "clean co-trained episode perturbed the allocation stream")
    assert data["clean"]["params_finite"] is True

    for row in data["rows"]:
        key = (f"{row['aggregator']}/{row['attack']}"
               f"@{row['byz_frac']}")
        assert row["digest_repeat_equal"] is True, (
            f"attacked episode {key} is not replayable from its spec")
        assert row["durations_match_engine"] is True, (
            f"attack {key} leaked into the allocation stream")
        assert len(row["digest"]) == 64
        if row["aggregator"] in _ROBUST:
            assert row["params_finite"] is True, (
                f"robust aggregator served non-finite params: {key}")

    if not data["tiny"]:
        fedavg_broke = False
        for row in data["rows"]:
            robust = row["aggregator"] in _ROBUST
            gradient_attack = row["attack"] in ("sign_flip", "scaled_delta")
            if robust and gradient_attack and row["byz_frac"] <= 0.2:
                assert row["drop"] <= ROBUST_ACC_DROP, (
                    f"robust aggregator broke: {row}")
            if (row["aggregator"] == "fedavg" and row["attack"] == "sign_flip"
                    and row["byz_frac"] >= 0.2):
                fedavg_broke |= row["drop"] > ROBUST_ACC_DROP
        assert fedavg_broke, (
            "plain FedAvg did not break under the sign-flip cohort -- "
            "the separation the robust registry exists for is gone")
        nan_rows = [r for r in data["rows"]
                    if r["attack"] == "nan" and r["aggregator"] == "fedavg"]
        for row in nan_rows:
            assert row["params_finite"] is False, (
                "plain FedAvg absorbed a NaN cohort -- the masking "
                "asymmetry the catalogue demonstrates is gone")

    bids = data["bids"]
    assert bids["trials_replay_equal"] is True, (
        "bid-chaos campaign is not replayable from its seed")
    assert bids["regret_gate"]["ok"] is True, bids["regret_gate"]
    for pt in bids["curves"]:
        assert pt["bounded"] is True, (
            f"deviation {pt['deviation']}@{pt['factor']} beat the "
            f"truthfulness bound by {pt['worst_excess']}")


def run_rows(tiny: bool = False) -> list[dict]:
    """benchmarks.run adapter: execute, write the artifact, emit CSV rows."""
    from benchmarks import common

    data = run(tiny=tiny)
    validate(data)
    if tiny:
        common.save_artifact("bench_robust_tiny", data)
    else:
        with open(os.path.join(_REPO_ROOT, DEFAULT_OUT), "w") as fp:
            json.dump(data, fp, indent=1, default=float)
            fp.write("\n")
    rows = []
    for row in data["rows"]:
        rows.append(common.row(
            f"robust/{row['aggregator']}/{row['attack']}"
            f"@{row['byz_frac']:g}", row["final_acc"],
            f"drop={row['drop']:+.3f} finite={row['params_finite']} "
            f"deterministic={row['digest_repeat_equal']}"))
    bids = data["bids"]
    rows.append(common.row(
        "robust/bid_regret", bids["worst_gain"],
        f"trials={len(bids['trials'])} "
        f"worst_excess={bids['regret_gate']['worst_excess']:+.4f} "
        f"bounded={bids['regret_gate']['ok']}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (2 attacks x 2 aggregators)")
    ap.add_argument("--out", default=os.path.join(_REPO_ROOT, DEFAULT_OUT),
                    help=f"output path (default: {DEFAULT_OUT} at repo root)")
    args = ap.parse_args()
    enable_compile_cache()
    data = run(tiny=args.tiny)
    validate(data)
    with open(args.out, "w") as fp:
        json.dump(data, fp, indent=1, default=float)
        fp.write("\n")
    print(f"clean final acc: {data['clean']['final_acc']:.4f}")
    for row in data["rows"]:
        print(f"{row['attack']:13s} {row['aggregator']:13s} "
              f"frac={row['byz_frac']:.1f} acc={row['final_acc']:.4f} "
              f"drop={row['drop']:+.4f} finite={row['params_finite']} "
              f"deterministic={row['digest_repeat_equal']}")
    bids = data["bids"]
    print(f"bid regret: worst_gain={bids['worst_gain']:+.5f} "
          f"gate_ok={bids['regret_gate']['ok']} "
          f"replay={bids['trials_replay_equal']}")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
