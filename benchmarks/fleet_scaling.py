"""Fleet scaling: a 100+ tenant drift storm through the advisor service.

Registers `--tenants` tenants spread over `--schema-groups` distinct
schemas (tenants within a group share one SampleManager and one
(NodeKey, f) SampleCF cache via `samplecf.schema_fingerprint` grouping),
then drives `--rounds` drift rounds.  Each round submits, for EVERY
tenant, one workload delta (churn + reweight) followed by one recommend,
and drains the fleet — so deltas and recommends of all tenants
interleave through the shared slots and the cross-tenant batched
SampleCF prefetch.

Gates:

* **Parity (hard assert):** every round, every tenant's recommendation
  is exactly `==` — config, cost, used_bytes — a fresh `DesignAdvisor`
  built on that tenant's current workload.  The report only exists if
  all tenants * rounds comparisons held.
* **Sharing:** the shared fleet must draw fewer samples than tenants *
  per-tenant sampling (evidenced by `sampling_calls` vs group count and
  by per-tenant SampleCF misses being (near-)zero after the prefetch).

Reported in BENCH_fleet.json: sustained recommends/sec (fleet wall time
over all rounds, excluding the fresh-advisor parity checks), p50/p99
submit->resolve latency per request kind, and the fleet's amortization
counters.

Usage:
    PYTHONPATH=src python benchmarks/fleet_scaling.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import (AdvisorOptions, DesignAdvisor, WorkloadDelta,
                        base_configuration, make_scaled_workload,
                        make_tpch_like)
from repro.core.backend import enable_compile_cache
from repro.serve.advisor_service import AdvisorFleetService, FleetConfig


def identical(a, b) -> bool:
    return (a.config == b.config and a.cost == b.cost
            and a.used_bytes == b.used_bytes)


def pct(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, dtype=float), q))


def make_tenant_workload(schema, tid: str, n: int, seed: int):
    wl = make_scaled_workload(schema, n_statements=n, seed=seed)
    return dataclasses.replace(
        wl, statements=[dataclasses.replace(s, name=f"{tid}_{s.name}")
                        for s in wl.statements])


def make_delta(rng, tid: str, rnd: int, wl, schema, n_move: int,
               n_reweight: int) -> WorkloadDelta:
    names = [s.name for s in wl.statements]
    removed = tuple(rng.choice(names, size=min(n_move, len(names) - 1),
                               replace=False))
    pool = make_scaled_workload(
        schema, n_statements=len(removed),
        seed=100_000 + rnd * 1000 + int(tid[1:])).statements
    added = tuple(dataclasses.replace(s, name=f"{tid}_r{rnd}_{j}")
                  for j, s in enumerate(pool))
    survivors = [n for n in names if n not in set(removed)]
    rw = tuple((n, float(rng.uniform(0.5, 2.0)))
               for n in rng.choice(survivors,
                                   size=min(n_reweight, len(survivors)),
                                   replace=False))
    return WorkloadDelta(added=added, removed=removed, reweighted=rw)


def run(tenants: int, schema_groups: int, statements: int, scale: float,
        rounds: int, slots: int, n_move: int, n_reweight: int, seed: int,
        budget_frac: float, out_path: Path,
        backend: str = "numpy") -> dict:
    schemas = [make_tpch_like(scale=scale, z=0, seed=seed + g)
               for g in range(schema_groups)]
    opt = dataclasses.replace(AdvisorOptions.dtac(), backend=backend)
    fleet = AdvisorFleetService(FleetConfig(slots=slots))

    wls = {}
    tenant_schema = {}
    budgets = {}
    for i in range(tenants):
        tid = f"t{i}"
        schema = schemas[i % schema_groups]
        wl = make_tenant_workload(schema, tid, statements, seed + 31 + i)
        wls[tid] = wl
        tenant_schema[tid] = schema
        adv = DesignAdvisor(wl, opt)
        budgets[tid] = budget_frac * sum(
            adv.sizes.size(i_)
            for i_ in base_configuration(schema).indexes)
        fleet.register_tenant(tid, wl, opt)
    assert fleet.stats["groups"] == schema_groups, \
        "fingerprint grouping did not collapse same-schema tenants"

    rng = np.random.default_rng(seed + 7)
    fleet_seconds = 0.0
    rec_latencies, delta_latencies = [], []
    round_rows = []
    parity_checks = 0
    for rnd in range(rounds):
        tickets = {}
        t0 = time.perf_counter()
        for tid in wls:
            delta = make_delta(rng, tid, rnd, wls[tid],
                               tenant_schema[tid], n_move, n_reweight)
            fleet.submit_delta(tid, delta)
            wls[tid] = wls[tid].apply_delta(delta)
            tickets[tid] = fleet.submit_recommend(tid, budgets[tid])
        fleet.run_until_drained()
        dt = time.perf_counter() - t0
        fleet_seconds += dt

        # parity: EVERY tenant vs a fresh advisor, EVERY round
        t1 = time.perf_counter()
        for tid, tk in tickets.items():
            fresh = DesignAdvisor(wls[tid], opt).recommend(budgets[tid])
            assert identical(tk.result(), fresh), \
                f"parity broke at round {rnd}, tenant {tid}"
            parity_checks += 1
            rec_latencies.append(tk.latency)
        fresh_seconds = time.perf_counter() - t1
        round_rows.append({
            "round": rnd,
            "fleet_seconds": round(dt, 4),
            "recommends_per_sec": round(tenants / dt, 2),
            "fresh_rebuild_seconds": round(fresh_seconds, 4),
        })

    total_recs = tenants * rounds
    s = fleet.stats
    misses = sum(fleet.tenant_stats(t)["samplecf_cache_misses"]
                 for t in wls)
    report = {
        "backend": backend,
        "tenants": tenants,
        "schema_groups": schema_groups,
        "statements_per_tenant": statements,
        "schema_scale": scale,
        "rounds": rounds,
        "slots": slots,
        "moves_per_round": n_move,
        "reweights_per_round": n_reweight,
        "total_recommends": total_recs,
        "fleet_seconds": round(fleet_seconds, 4),
        "sustained_recommends_per_sec": round(total_recs / fleet_seconds,
                                              2),
        "latency_seconds": {
            "recommend_p50": round(pct(rec_latencies, 50), 4),
            "recommend_p99": round(pct(rec_latencies, 99), 4),
            "recommend_max": round(max(rec_latencies), 4),
        },
        "per_round": round_rows,
        # guarded by the identical() asserts above
        "parity": {"checks": parity_checks, "bit_exact": True},
        "amortization": {
            "fleet_stats": s,
            "tenant_samplecf_misses_total": misses,
        },
    }
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report, indent=2))
    ok = parity_checks == total_recs and s["groups"] == schema_groups
    if ok:
        print(f"OK: {parity_checks} exact parity checks over {rounds} "
              f"rounds x {tenants} tenants; "
              f"{report['sustained_recommends_per_sec']}/s sustained, "
              f"p99 {report['latency_seconds']['recommend_p99']}s")
    else:
        print("FAIL: parity/sharing gate", file=sys.stderr)
    return report | {"ok": ok}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", type=int, default=100)
    ap.add_argument("--schema-groups", type=int, default=4)
    ap.add_argument("--statements", type=int, default=12)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--moves", type=int, default=2)
    ap.add_argument("--reweights", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-frac", type=float, default=0.25)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="unified advisor backend for every tenant; "
                    "per-tenant parity is asserted every round either way")
    ap.add_argument("--out", type=Path, default=None,
                    help="output JSON path (default: BENCH_fleet.json at "
                    "the repo root; smoke runs write "
                    "BENCH_fleet.smoke.json so they never clobber the "
                    "committed trajectory)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (parity still asserted "
                    "for every tenant every round)")
    args = ap.parse_args()
    enable_compile_cache()
    root = Path(__file__).resolve().parent.parent
    if args.smoke:
        args.tenants = 10
        args.schema_groups = 2
        args.statements = 10
        args.rounds = 2
        args.slots = 4
    if args.out is None:
        args.out = root / ("BENCH_fleet.smoke.json" if args.smoke
                           else "BENCH_fleet.json")
    report = run(args.tenants, args.schema_groups, args.statements,
                 args.scale, args.rounds, args.slots, args.moves,
                 args.reweights, args.seed, args.budget_frac, args.out,
                 args.backend)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
