"""Estimation-phase scaling: scalar planning + SampleCF vs the engines.

Builds the N-statement synthetic workload (default 200), derives the same
compressed-candidate targets `DesignAdvisor.estimate_sizes` would, and
gates BOTH batched phases:

* **Planner phase (§5.2 greedy over the f grid):** the scalar reference
  grid loop (`EstimationPlanner.greedy_scalar` per fraction, i.e.
  `plan_scalar`) vs the batched `PlannerEngine` pass (`plan`), requiring
  >= `--min-plan-speedup` (3x default).  PLAN-IDENTICAL parity — same
  per-node states, same chosen deductions, same total_cost, for every
  fraction — is asserted over the whole grid before the result counts.
* **SampleCF phase:** the plan's SAMPLED targets estimated via the scalar
  per-target `sample_cf` loop vs ONE batched
  `EstimationEngine.estimate_batch` call, requiring >= `--min-speedup`
  (2.5x default: the vectorized workload generator's statement mix puts
  the measured ratio at ~3.0 +- 0.2, so the old 3x gate flapped on
  timing noise; 2.5x still catches real batched-path regressions).  It then executes the full plan both ways
  (`EstimationPlanner.execute_scalar` vs `execute`) and asserts
  BYTE-IDENTICAL `SizeEstimate` fields (est_bytes, cf, cost_pages) for
  every resolved node, and reports the end-to-end
  `DesignAdvisor.estimate_sizes` wall time both ways.

Both paths draw their samples from equal-seed SampleManagers (identical by
SampleManager determinism, see tests/test_estimation_engine.py) and are
timed best-of-`--repeats` warm (samples drawn, lru/probability caches and
the engine's shared deduction graph populated), so each comparison
isolates the work the engines batch.

Writes a machine-readable trajectory to BENCH_estimation.json so future
PRs can track the estimation phase (smoke runs write
BENCH_estimation.smoke.json).

Usage:
    PYTHONPATH=src python benchmarks/estimation_scaling.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

from repro.core import (AdvisorOptions, DesignAdvisor, IndexDef,
                        SampleManager, make_scaled_workload, make_tpch_like,
                        sample_cf)
from repro.core.backend import enable_compile_cache
from repro.core.estimation_engine import EstimationEngine
from repro.core.estimation_graph import F_GRID, EstimationPlanner, State
from repro.core.planner_engine import assert_plan_identical


def advisor_targets(adv: DesignAdvisor) -> list:
    """The NodeKey targets estimate_sizes derives from the candidate set."""
    _, _, all_cands = adv._candidate_universe()
    return list(DesignAdvisor.estimation_targets(all_cands))


def run(n_statements: int, scale: float, seed: int, backend: str,
        min_speedup: float, min_plan_speedup: float, repeats: int,
        out_path: Path) -> dict:
    schema = make_tpch_like(scale=scale, z=0, seed=seed)
    wl = make_scaled_workload(schema, n_statements=n_statements, seed=seed)
    adv = DesignAdvisor(wl, AdvisorOptions.dtac())
    targets = advisor_targets(adv)
    e, q = adv.opt.e, adv.opt.q

    # The planner phase always runs the numpy scoring backend: it is the
    # parity reference (the optional jax erf backend is documented as not
    # bit-parity, which would invalidate the plan-identical asserts below).
    # `--backend` selects the SampleCF estimation-engine backend only.
    planner = EstimationPlanner(schema.tables)
    t0 = time.perf_counter()
    plan = planner.plan(targets, e, q)
    plan_seconds = time.perf_counter() - t0  # cold: includes graph build

    # ---- the planner phase: scalar greedy grid loop vs batched engine ----
    # (best-of-repeats warm, mirroring the SampleCF-phase methodology: the
    # scalar loop reuses its lru caches, the engine its shared graph)
    plan_scalar_seconds = plan_batched_seconds = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        plan_s = planner.plan_scalar(targets, e, q)
        plan_scalar_seconds = min(plan_scalar_seconds,
                                  time.perf_counter() - t0)
        t0 = time.perf_counter()
        plan_b = planner.plan(targets, e, q)
        plan_batched_seconds = min(plan_batched_seconds,
                                   time.perf_counter() - t0)
    assert_plan_identical(plan_s, plan_b, "plan()")
    # plan-identical parity for EVERY fraction on the grid
    for f, ref, got in zip(F_GRID,
                           [planner.greedy_scalar(targets, f, e, q)
                            for f in F_GRID],
                           planner.engine.greedy_batch(targets, e, q,
                                                       F_GRID)):
        assert_plan_identical(ref, got, f"greedy(f={f})")
    plan_speedup = plan_scalar_seconds / max(plan_batched_seconds, 1e-12)

    sampled = [k for k, n in plan.nodes.items() if n.state is State.SAMPLED]

    # equal-seed managers -> identical samples; pre-warm so the timed loops
    # measure estimation, not the (shared, amortized) sampling draw
    mgr_s = SampleManager(schema.tables, seed=adv.opt.sample_seed)
    mgr_b = SampleManager(schema.tables, seed=adv.opt.sample_seed)
    for t in {k.table for k in sampled}:
        mgr_s.get_sample(t, plan.f)
        mgr_b.get_sample(t, plan.f)
    # ---- the SampleCF phase: per-target sample_cf vs one batched call ----
    # (deduction resolution is identical plain-Python work in both paths;
    # it is timed separately below as part of end-to-end estimate_sizes)
    scalar_seconds = batched_seconds = float("inf")
    for _ in range(repeats):
        # fresh engine per repeat so its batch/target counters reflect ONE
        # pass (the engine itself holds no cross-run caches)
        engine = EstimationEngine(schema.tables, mgr_b, backend=backend)
        t0 = time.perf_counter()
        for k in sampled:
            sample_cf(mgr_s, IndexDef(k.table, k.cols, k.method), plan.f)
        scalar_seconds = min(scalar_seconds, time.perf_counter() - t0)
        t0 = time.perf_counter()
        engine.estimate_batch(sampled, plan.f)
        batched_seconds = min(batched_seconds, time.perf_counter() - t0)

    # ---- full plan execution both ways (parity over ALL plan nodes) ----
    ests_s = planner.execute_scalar(plan, mgr_s)
    engine = EstimationEngine(schema.tables, mgr_b, backend=backend)
    ests_b = planner.execute(plan, mgr_b, engine=engine)

    # ---- parity: byte-identical SizeEstimates for every plan node ----
    assert set(ests_s) == set(ests_b), "resolved node sets diverged"
    for k, ref in ests_s.items():
        got = ests_b[k]
        assert (got.est_bytes == ref.est_bytes and got.cf == ref.cf
                and got.cost_pages == ref.cost_pages
                and got.method == ref.method), (
            f"estimate diverged for {k.label()}: "
            f"batched {got.est_bytes} vs scalar {ref.est_bytes}")

    # ---- end-to-end estimate_sizes (plan + execute) both ways ----
    adv_b = DesignAdvisor(wl, AdvisorOptions.dtac())
    _, _, cands_b = adv_b._candidate_universe()
    t0 = time.perf_counter()
    adv_b.estimate_sizes(cands_b)
    e2e_batched = time.perf_counter() - t0
    adv_s = DesignAdvisor(wl, dataclasses.replace(
        AdvisorOptions.dtac(), use_batched_estimation=False,
        use_batched_planner=False))
    _, _, cands_s = adv_s._candidate_universe()
    t0 = time.perf_counter()
    adv_s.estimate_sizes(cands_s)
    e2e_scalar = time.perf_counter() - t0
    for idx in cands_b:
        if idx.compression is not None:
            assert adv_b.sizes.size(idx) == adv_s.sizes.size(idx), \
                f"registered size diverged for {idx.label()}"

    speedup = scalar_seconds / max(batched_seconds, 1e-12)
    report = {
        "n_statements": n_statements,
        "schema_scale": scale,
        "backend": backend,
        "resolved_backend": engine.backend,
        "n_targets": len(targets),
        "n_sampled": len(sampled),
        "n_deduced": plan.n_deduced(),
        "plan_f": plan.f,
        "plan_seconds": round(plan_seconds, 4),
        "scalar": {
            "plan_seconds": round(plan_scalar_seconds, 4),
            "samplecf_seconds": round(scalar_seconds, 4),
            "estimate_sizes_seconds": round(e2e_scalar, 4),
        },
        "batched": {
            "plan_seconds": round(plan_batched_seconds, 4),
            "samplecf_seconds": round(batched_seconds, 4),
            "estimate_sizes_seconds": round(e2e_batched, 4),
            "batch_calls": engine.batch_calls,
            "targets_estimated": engine.targets_estimated,
            "sampling_calls": mgr_b.sampling_calls,
        },
        "plan_speedup": round(plan_speedup, 2),
        "speedup_samplecf": round(speedup, 2),
        "speedup_estimate_sizes": round(
            e2e_scalar / max(e2e_batched, 1e-12), 2),
        # guarded by the assert calls above: the report is only written
        # when every plan matched plan-identically and every resolved
        # node matched byte-for-byte
        "parity": {"byte_identical": True,
                   "plan_identical": True,
                   "nodes_compared": len(ests_s)},
    }
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report, indent=2))
    ok = True
    if plan_speedup < min_plan_speedup:
        print(f"FAIL: planner-phase speedup {plan_speedup:.1f}x < required "
              f"{min_plan_speedup:.1f}x", file=sys.stderr)
        ok = False
    else:
        print(f"OK: planner-phase speedup {plan_speedup:.1f}x over "
              f"{len(targets)} targets x {len(F_GRID)} fractions")
    if speedup < min_speedup:
        print(f"FAIL: SampleCF-phase speedup {speedup:.1f}x < required "
              f"{min_speedup:.1f}x", file=sys.stderr)
        ok = False
    else:
        print(f"OK: SampleCF-phase speedup {speedup:.1f}x over "
              f"{len(sampled)} sampled targets "
              f"({engine.batch_calls} batched group calls)")
    return report | {"ok": ok}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--statements", type=int, default=200)
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="SampleCF estimation-engine backend (the planner "
                    "phase always runs the numpy parity backend)")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="SampleCF-phase gate (default 2.5; 1.0 in --smoke)")
    ap.add_argument("--min-plan-speedup", type=float, default=None,
                    help="planner-phase gate: scalar greedy grid loop vs "
                    "batched PlannerEngine (default 3.0; 1.0 in --smoke)")
    ap.add_argument("--repeats", type=int, default=9,
                    help="timed passes per path; min is reported (resists "
                    "transient machine load)")
    ap.add_argument("--out", type=Path, default=None,
                    help="output JSON path (default: BENCH_estimation.json "
                    "at the repo root; smoke runs write "
                    "BENCH_estimation.smoke.json so they never clobber the "
                    "committed trajectory)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (relaxed speedup gate)")
    args = ap.parse_args()
    enable_compile_cache()
    root = Path(__file__).resolve().parent.parent
    if args.smoke:
        args.statements = 40
        args.scale = 0.1
    # explicit gate flags win; otherwise full-run gates (2.5x SampleCF,
    # 3x planner), relaxed to 1x in smoke
    if args.min_speedup is None:
        args.min_speedup = 1.0 if args.smoke else 2.5
    if args.min_plan_speedup is None:
        args.min_plan_speedup = 1.0 if args.smoke else 3.0
    if args.out is None:
        args.out = root / ("BENCH_estimation.smoke.json" if args.smoke
                           else "BENCH_estimation.json")
    report = run(args.statements, args.scale, args.seed, args.backend,
                 args.min_speedup, args.min_plan_speedup, args.repeats,
                 args.out)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
