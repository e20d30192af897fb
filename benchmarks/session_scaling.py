"""Online-session scaling: delta-aware re-advising vs fresh rebuilds.

Drives a drifting N-statement workload (default 200) through alternating
drift rounds — "churn" rounds add + remove statements and reweight others,
"reweight" rounds only shift weights — and re-advises after every round
twice: through the persistent `AdvisorSession` (incremental engines) and
through a fresh `DesignAdvisor` built on the resulting workload (the
one-shot rebuild a non-incremental tool pays).

Gates two things:

* **Parity (hard assert):** after EVERY round the session's recommendation
  is identical — config, cost, used_bytes — to the fresh advisor's.  The
  session only ever replays values that are pure functions of the same
  inputs, so this is exact equality, not a tolerance.
* **Speedup:** the median per-round re-advise speedup must reach
  `--min-speedup` (5x default; relaxed to 1x in --smoke).  Per-round
  speedups, the min/mean, and the session's incrementality counters
  (replay/selection/SampleCF cache hits) are all recorded in
  BENCH_session.json (smoke runs write BENCH_session.smoke.json).

Usage:
    PYTHONPATH=src python benchmarks/session_scaling.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import (AdvisorOptions, AdvisorSession, DesignAdvisor,
                        WorkloadDelta, base_configuration,
                        make_scaled_workload, make_tpch_like)
from repro.core.backend import enable_compile_cache


def make_delta(rng: np.random.Generator, wl_cur, drift_pool, k: int,
               kind: str, n_move: int, n_reweight: int):
    """One drift round's mutation batch (and the drift-pool cursor)."""
    names = [s.name for s in wl_cur.statements]
    added, removed = (), ()
    if kind == "churn":
        removed = tuple(rng.choice(names, size=n_move, replace=False))
        added = tuple(drift_pool[k:k + n_move])
        k += n_move
    survivors = [n for n in names if n not in set(removed)]
    rw = tuple((n, float(rng.uniform(0.5, 2.0)))
               for n in rng.choice(survivors,
                                   size=min(n_reweight, len(survivors)),
                                   replace=False))
    return WorkloadDelta(added=added, removed=removed, reweighted=rw), k


def identical(a, b) -> bool:
    return (a.config == b.config and a.cost == b.cost
            and a.used_bytes == b.used_bytes)


def run(statements: int, scale: float, seed: int, rounds: int, n_move: int,
        n_reweight: int, budget_frac: float, min_speedup: float,
        out_path: Path, backend: str = "numpy") -> dict:
    schema = make_tpch_like(scale=scale, z=0, seed=seed)
    wl = make_scaled_workload(schema, n_statements=statements, seed=seed)
    opt = dataclasses.replace(AdvisorOptions.dtac(), backend=backend)
    base_size = sum(DesignAdvisor(wl).sizes.size(i)
                    for i in base_configuration(schema).indexes)
    budget = budget_frac * base_size

    session = AdvisorSession(wl, opt)
    t0 = time.perf_counter()
    rec0 = session.recommend(budget)
    cold_seconds = time.perf_counter() - t0
    fresh0 = DesignAdvisor(wl, opt).recommend(budget)
    assert identical(rec0, fresh0), "cold-session parity broke"

    # fresh statements to drift in (renamed so names stay unique)
    drift_pool = [dataclasses.replace(s, name=f"d{i:04d}") for i, s in
                  enumerate(make_scaled_workload(
                      schema, n_statements=statements,
                      seed=seed + 101).statements)]
    rng = np.random.default_rng(seed + 7)

    wl_cur = wl
    k = 0
    round_rows = []
    for rnd in range(rounds):
        kind = "churn" if rnd % 2 == 0 else "reweight"
        delta, k = make_delta(rng, wl_cur, drift_pool, k, kind, n_move,
                              n_reweight)
        wl_cur = wl_cur.apply_delta(delta)

        t0 = time.perf_counter()
        session.apply(delta)
        rec_s = session.recommend(budget)
        t_session = time.perf_counter() - t0

        t0 = time.perf_counter()
        rec_f = DesignAdvisor(wl_cur, opt).recommend(budget)
        t_fresh = time.perf_counter() - t0

        assert identical(rec_s, rec_f), (
            f"parity broke at round {rnd}: session cost {rec_s.cost} "
            f"vs fresh {rec_f.cost}")
        round_rows.append({
            "round": rnd, "kind": kind,
            "added": len(delta.added), "removed": len(delta.removed),
            "reweighted": len(delta.reweighted),
            "session_seconds": round(t_session, 4),
            "fresh_seconds": round(t_fresh, 4),
            "speedup": round(t_fresh / max(t_session, 1e-12), 2),
            "identical": True,
        })

    speedups = [r["speedup"] for r in round_rows]
    med = statistics.median(speedups)
    report = {
        "backend": backend,
        "n_statements": statements,
        "schema_scale": scale,
        "rounds": rounds,
        "round_kinds": "alternating churn/reweight",
        "n_move_per_churn": n_move,
        "n_reweight_per_round": n_reweight,
        "budget_frac": budget_frac,
        "cold_session_seconds": round(cold_seconds, 4),
        "per_round": round_rows,
        "median_speedup": round(med, 2),
        "mean_speedup": round(sum(speedups) / len(speedups), 2),
        "min_speedup": round(min(speedups), 2),
        "max_speedup": round(max(speedups), 2),
        # guarded by the identical() asserts above: the report only
        # exists when every round matched the fresh advisor exactly
        "parity": {"identical_rounds": len(round_rows),
                   "bit_exact": True},
        "session_stats": session.stats,
    }
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report, indent=2))
    ok = med >= min_speedup
    if ok:
        print(f"OK: median re-advise speedup {med:.1f}x over "
              f"{rounds} drift rounds (min {min(speedups):.1f}x, "
              f"gate {min_speedup:.1f}x)")
    else:
        print(f"FAIL: median re-advise speedup {med:.1f}x < required "
              f"{min_speedup:.1f}x", file=sys.stderr)
    return report | {"ok": ok}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--statements", type=int, default=200)
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="unified advisor backend (AdvisorOptions.backend); "
                    "parity vs the fresh advisor is asserted either way")
    ap.add_argument("--moves", type=int, default=4,
                    help="statements added AND removed per churn round")
    ap.add_argument("--reweights", type=int, default=8,
                    help="statements reweighted per round")
    ap.add_argument("--budget-frac", type=float, default=0.25)
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="median per-round re-advise gate "
                    "(default 5.0; 1.0 in --smoke)")
    ap.add_argument("--out", type=Path, default=None,
                    help="output JSON path (default: BENCH_session.json at "
                    "the repo root; smoke runs write "
                    "BENCH_session.smoke.json so they never clobber the "
                    "committed trajectory)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (parity still asserted "
                    "every round; relaxed speedup gate)")
    args = ap.parse_args()
    enable_compile_cache()
    root = Path(__file__).resolve().parent.parent
    if args.smoke:
        args.statements = 40
        args.scale = 0.1
        args.rounds = 6
        args.moves = 3
    if args.min_speedup is None:
        args.min_speedup = 1.0 if args.smoke else 5.0
    if args.out is None:
        args.out = root / ("BENCH_session.smoke.json" if args.smoke
                           else "BENCH_session.json")
    report = run(args.statements, args.scale, args.seed, args.rounds,
                 args.moves, args.reweights, args.budget_frac,
                 args.min_speedup, args.out, args.backend)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
