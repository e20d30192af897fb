"""Advisor hot-path scaling: scalar what-if loop vs batched cost engine.

Builds an N-statement synthetic workload (default 200), runs the full DTAc
recommendation twice — once through the scalar statement-at-a-time what-if
path, once through the batched cost engine — asserts that both return the
same configuration and cost (1e-6 rel), and reports wall-clock speedup for
(a) the advisor hot path (candidate costing + greedy enumeration, the
O(pool × statements) part the engine vectorizes) and (b) the end-to-end
`recommend` call (which also contains the shared size-estimation work).

Writes a machine-readable trajectory to BENCH_advisor.json so future PRs can
track the hot path.

Usage:
    PYTHONPATH=src python benchmarks/advisor_scaling.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from repro.core import (AdvisorOptions, DesignAdvisor, base_configuration,
                        make_scaled_workload, make_tpch_like)
from repro.core.backend import enable_compile_cache
from repro.core import candidates as cand
from repro.core.cost_engine import CostEngine
from repro.core.enumeration import greedy_enumerate, greedy_enumerate_scalar


def _select_pool(adv, per_query_exp, merged_all, base, engine):
    """The candidate-selection stage of DesignAdvisor.recommend."""
    pool = {}
    n_cand = 0
    for q in adv.workload.queries():
        costed = cand.cost_candidates(q, per_query_exp[q.name], base,
                                      adv.optimizer, adv.sizes, engine=engine)
        n_cand += len(costed)
        sel = cand.select_skyline(costed)
        sel = cand.skyline_representatives(sel, adv.opt.max_skyline_points)
        for c in sel:
            pool.setdefault(c.index.key, c.index)
    for idx in merged_all:
        pool.setdefault(idx.key, idx)
    return list(pool.values()), n_cand


def run(n_statements: int, scale: float, budget_frac: float, seed: int,
        backend: str, min_speedup: float, out_path: Path) -> dict:
    schema = make_tpch_like(scale=scale, z=0, seed=seed)
    wl = make_scaled_workload(schema, n_statements=n_statements, seed=seed)
    base = base_configuration(schema)
    budget = budget_frac * sum(
        DesignAdvisor(wl).sizes.size(i) for i in base.indexes)

    # ---- shared setup (identical for both paths): candidates + sizes ----
    adv = DesignAdvisor(wl, AdvisorOptions.dtac())
    per_query_exp, merged_all, all_cands = adv._candidate_universe()
    t0 = time.perf_counter()
    adv.estimate_sizes(all_cands)
    est_seconds = time.perf_counter() - t0

    # ---- hot path, scalar reference ----
    t0 = time.perf_counter()
    pool_s, n_cand = _select_pool(adv, per_query_exp, merged_all, base,
                                  engine=None)
    res_s = greedy_enumerate_scalar(adv.optimizer, adv.sizes, pool_s, base,
                                    budget)
    scalar_seconds = time.perf_counter() - t0
    scalar_calls = adv.optimizer.calls

    # ---- hot path, batched engine (fresh advisor: no warm scalar cache) ----
    adv2 = DesignAdvisor(wl, AdvisorOptions.dtac())
    adv2.estimate_sizes(all_cands)
    t0 = time.perf_counter()
    engine = CostEngine(wl, adv2.sizes, backend=backend)
    pool_b, _ = _select_pool(adv2, per_query_exp, merged_all, base,
                             engine=engine)
    res_b = greedy_enumerate(adv2.optimizer, adv2.sizes, pool_b, base,
                             budget, engine=engine)
    batched_seconds = time.perf_counter() - t0

    # ---- parity ----
    # numpy backend is float64 and formula-identical to the scalar path;
    # the jax scoring kernel runs in f32, so it gets a looser gate.
    tol = 1e-6 if backend == "numpy" else 1e-3
    assert [p.key for p in pool_s] == [p.key for p in pool_b], \
        "candidate pools diverged between scalar and batched selection"
    rel_err = abs(res_b.cost - res_s.cost) / max(abs(res_s.cost), 1e-12)
    same_config = res_b.config == res_s.config
    assert same_config, (
        "scalar and batched enumeration chose different configurations:\n"
        f"  batched-only: {sorted(i.label() for i in res_b.config.indexes - res_s.config.indexes)}\n"
        f"  scalar-only:  {sorted(i.label() for i in res_s.config.indexes - res_b.config.indexes)}")
    assert rel_err <= tol, f"cost parity violated: rel err {rel_err:.3e}"

    # ---- end-to-end recommend (includes shared estimation work) ----
    t0 = time.perf_counter()
    rec_b = DesignAdvisor(wl, AdvisorOptions.dtac()).recommend(budget)
    e2e_batched = time.perf_counter() - t0
    t0 = time.perf_counter()
    rec_s = DesignAdvisor(wl, AdvisorOptions(use_engine=False)).recommend(
        budget)
    e2e_scalar = time.perf_counter() - t0
    assert rec_b.config == rec_s.config, \
        "end-to-end recommend diverged between scalar and batched paths"
    e2e_rel = abs(rec_b.cost - rec_s.cost) / max(abs(rec_s.cost), 1e-12)
    assert e2e_rel <= 1e-6, f"recommend cost parity violated: {e2e_rel:.3e}"
    # (end-to-end recommend always uses the numpy engine: strict gate)

    speedup = scalar_seconds / max(batched_seconds, 1e-12)
    report = {
        "n_statements": n_statements,
        "schema_scale": scale,
        "budget_frac": budget_frac,
        "backend": backend,
        "pool_size": len(pool_s),
        "candidate_count": n_cand,
        "estimation_seconds": round(est_seconds, 4),
        "scalar": {
            "hot_path_seconds": round(scalar_seconds, 4),
            "recommend_seconds": round(e2e_scalar, 4),
            "whatif_calls": scalar_calls,
        },
        "batched": {
            "hot_path_seconds": round(batched_seconds, 4),
            "recommend_seconds": round(e2e_batched, 4),
            "config_evals": engine.config_evals,
            "batch_scores": engine.batch_scores,
        },
        "speedup_hot_path": round(speedup, 2),
        "speedup_recommend": round(e2e_scalar / max(e2e_batched, 1e-12), 2),
        "parity": {"same_config": bool(same_config),
                   "rel_cost_err": rel_err},
        "recommendation": {
            "cost": res_b.cost,
            "improvement": rec_b.improvement,
            "n_indexes": len(res_b.config.indexes),
        },
    }
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report, indent=2))
    if speedup < min_speedup:
        print(f"FAIL: hot-path speedup {speedup:.1f}x < required "
              f"{min_speedup:.1f}x", file=sys.stderr)
        return report | {"ok": False}
    print(f"OK: hot-path speedup {speedup:.1f}x "
          f"({scalar_calls} scalar what-if calls -> "
          f"{engine.batch_scores} vectorized scores)")
    return report | {"ok": True}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--statements", type=int, default=200)
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--budget-frac", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy")
    ap.add_argument("--min-speedup", type=float, default=5.0)
    ap.add_argument("--out", type=Path, default=None,
                    help="output JSON path (default: BENCH_advisor.json at "
                    "the repo root; smoke runs write "
                    "BENCH_advisor.smoke.json so they never clobber the "
                    "committed trajectory)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (relaxed speedup gate)")
    args = ap.parse_args()
    enable_compile_cache()
    root = Path(__file__).resolve().parent.parent
    if args.smoke:
        args.statements = 40
        args.scale = 0.1
        # seed chosen to avoid degenerate equal-cost optima at this tiny
        # scale: some seeds produce two clustered orderings whose total
        # costs agree to the last ulp, where scalar/batched summation
        # order legitimately breaks the tie differently
        args.seed = 2
        args.min_speedup = 1.0
    if args.out is None:
        args.out = root / ("BENCH_advisor.smoke.json" if args.smoke
                           else "BENCH_advisor.json")
    report = run(args.statements, args.scale, args.budget_frac, args.seed,
                 args.backend, args.min_speedup, args.out)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
