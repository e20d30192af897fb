"""Fault recovery: a seeded fault storm + bounded-memory drift through
the advisor fleet, with exact per-tenant parity as the hard gate.

Phase 1 — **fault storm**.  Registers `--tenants` tenants on one schema
and drives `--rounds` drift rounds under a seeded `FaultInjector`
(transient apply/estimation/costing faults plus lost prefetch batches),
a scripted `crash_tenant` every round (checkpoint-restore readmission),
and per-recommend deadlines with a degraded-budget fallback.  Every
round, every tenant submits one delta and one recommend; a mirror
workload per tenant is advanced ONLY when the delta ticket succeeded.
Each resolved recommendation is then checked bit-exactly:

* normal result    -> `==` fresh `DesignAdvisor` on the mirror workload
* degraded result  -> `==` fresh advisor at the degraded compression
  budget on the same mirror (certificate attached)
* `TicketTimeout` / `TenantQuarantined` -> counted, never silently lost

The report only exists if every comparison held — the exact-parity
contract extended over retries, quarantines, crash/restore cycles and
deadline degradation.  Restore wall-times feed the recovery-latency
percentiles.

Phase 2 — **bounded drift**.  One tenant drifts for `--bounded-rounds`
rounds under absurdly tight memory bounds (shared SampleCF LRU via
`FleetConfig.cache_entries`, planner node-universe and replay-store
bounds via `AdvisorOptions`).  Per-round resident sizes are recorded and
gated: the cache never exceeds its bound, evictions actually fired at
every layer, and parity holds every round — evidence that eviction only
discards recomputable state.

Phase 3 — **durable recovery** (PR 10).  Drives a delta storm through a
fleet backed by `DurableStore` (write-ahead log + atomic snapshots)
under mild injected disk faults (torn writes, fsync failures), closes
the store — real process death, nothing survives in memory — and times
`AdvisorFleetService.recover(dir)` over fresh copies of the directory.
Two store configurations contrast the latency/compaction trade:
journal-only (`compact_after=None`, the longest possible replay) vs
aggressive compaction (short WAL suffix).  The gate: every recovered
tenant's post-restart recommendation is exactly `==` a fresh
`DesignAdvisor` on its mirror workload, a scripted torn tail is
truncated (not fatal), and the recovery-latency percentiles vs log
length / snapshot interval land in the report.

Usage:
    PYTHONPATH=src python benchmarks/fault_recovery.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.core import (AdvisorOptions, DesignAdvisor, DurableStore,
                        FaultInjector, WorkloadDelta, base_configuration,
                        make_scaled_workload, make_tpch_like)
from repro.core.backend import enable_compile_cache
from repro.serve.advisor_service import (AdvisorFleetService, FleetConfig,
                                         TenantQuarantined, TicketTimeout)


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


def make_delta(rng, tid: str, rnd: int, wl, schema) -> WorkloadDelta:
    names = [s.name for s in wl.statements]
    removed = tuple(rng.choice(names, size=min(1, len(names) - 1),
                               replace=False))
    pool = make_scaled_workload(
        schema, n_statements=2,
        seed=100_000 + rnd * 1000 + int(tid[1:])).statements
    added = tuple(dataclasses.replace(s, name=f"{tid}_r{rnd}_{j}")
                  for j, s in enumerate(pool))
    return WorkloadDelta(added=added, removed=removed)


# ---------------------------------------------------------------------------
# Phase 1: seeded fault storm
# ---------------------------------------------------------------------------

def run_storm(tenants: int, rounds: int, slots: int, statements: int,
              scale: float, seed: int, budget_frac: float,
              deadline: int, degraded_budget: int,
              backend: str = "numpy") -> dict:
    schema = make_tpch_like(scale=scale, z=0, seed=seed)
    opt = dataclasses.replace(AdvisorOptions.dtac(),
                              backend=backend)
    faults = FaultInjector(seed=seed + 1, specs={
        "apply_delta": 0.08, "estimation": 0.05, "costing": 0.05,
        "prefetch": 0.25, "planner_replay": 0.05})
    fc = FleetConfig(slots=slots, retry_backoff=(1, 2, 4),
                     quarantine_after=3, degraded_budget=degraded_budget)
    fleet = AdvisorFleetService(fc, faults=faults)

    mirrors, budgets = {}, {}
    for i in range(tenants):
        tid = f"t{i}"
        wl = make_tenant_workload(schema, tid, statements, seed + 31 + i)
        mirrors[tid] = wl
        adv = DesignAdvisor(wl, opt)
        budgets[tid] = budget_frac * sum(
            adv.sizes.size(ix)
            for ix in base_configuration(schema).indexes)
        fleet.register_tenant(tid, wl, opt)

    dopt = dataclasses.replace(opt, compression_budget=degraded_budget)
    rng = np.random.default_rng(seed + 7)
    counts = {"exact": 0, "degraded_exact": 0, "timeout": 0,
              "quarantined": 0, "delta_ok": 0, "delta_failed": 0,
              "crashes": 0}
    parity_failures = 0
    fleet_seconds = 0.0
    round_rows = []
    exact_by_tenant = {tid: 0 for tid in mirrors}
    for rnd in range(rounds):
        # scripted process loss: one victim per round, restored from its
        # checkpoint before the round's traffic (recovery latency is
        # recorded by the service); fault-storm quarantines from the
        # previous round are readmitted here too
        victim = f"t{int(rng.integers(tenants))}"
        if fleet.tenants[victim].quarantined_at is None:
            fleet.crash_tenant(victim)
            counts["crashes"] += 1
        for tid, t in fleet.tenants.items():
            if t.quarantined_at is not None:
                fleet.readmit_tenant(tid)

        t0 = time.perf_counter()
        dks, rks, deltas = {}, {}, {}
        for tid in mirrors:
            deltas[tid] = make_delta(rng, tid, rnd, mirrors[tid], schema)
            dks[tid] = fleet.submit_delta(tid, deltas[tid])
            rks[tid] = fleet.submit_recommend(
                tid, budgets[tid], deadline_steps=deadline)
        fleet.run_until_drained()
        fleet_seconds += time.perf_counter() - t0

        for tid in mirrors:
            # the mirror advances ONLY on a successful delta, so every
            # parity check below compares against the state the tenant's
            # session actually reached
            derr = dks[tid].exception(timeout=1.0)
            if derr is None:
                mirrors[tid] = mirrors[tid].apply_delta(deltas[tid])
                counts["delta_ok"] += 1
            else:
                counts["delta_failed"] += 1
            rerr = rks[tid].exception(timeout=1.0)
            if isinstance(rerr, TicketTimeout):
                counts["timeout"] += 1
                continue
            if isinstance(rerr, TenantQuarantined):
                counts["quarantined"] += 1
                continue
            if rerr is not None:
                parity_failures += 1
                print(f"FAIL: unexpected recommend error r{rnd} {tid}: "
                      f"{rerr!r}", file=sys.stderr)
                continue
            rec = rks[tid].result()
            ref_opt = dopt if rks[tid].degraded else opt
            fresh = DesignAdvisor(mirrors[tid], ref_opt).recommend(
                budgets[tid])
            if identical(rec, fresh):
                key = "degraded_exact" if rks[tid].degraded else "exact"
                counts[key] += 1
                exact_by_tenant[tid] += 1
            else:
                parity_failures += 1
                print(f"FAIL: parity broke at round {rnd}, tenant {tid} "
                      f"(degraded={rks[tid].degraded})", file=sys.stderr)
        s = fleet.stats
        round_rows.append({"round": rnd, "retries": s["retries"],
                           "timeouts": s["timeouts"],
                           "quarantines": s["quarantines"],
                           "restores": s["restores"],
                           "degraded": s["degraded_recommends"]})

    s = fleet.stats
    lat = fleet.restore_seconds
    return {
        "tenants": tenants, "rounds": rounds, "slots": slots,
        "deadline_steps": deadline, "degraded_budget": degraded_budget,
        "fleet_seconds": round(fleet_seconds, 4),
        "outcomes": counts,
        "parity_failures": parity_failures,
        "tenants_with_exact_result": sum(
            1 for v in exact_by_tenant.values() if v > 0),
        "fault_injector": faults.stats(),
        "fleet_stats": s,
        "per_round": round_rows,
        "recovery_latency_seconds": {
            "restores": len(lat),
            "p50": round(pct(lat, 50), 5) if lat else None,
            "p99": round(pct(lat, 99), 5) if lat else None,
            "max": round(max(lat), 5) if lat else None,
        },
    }


# ---------------------------------------------------------------------------
# Phase 2: bounded-memory drift
# ---------------------------------------------------------------------------

def run_bounded(rounds: int, statements: int, scale: float, seed: int,
                budget_frac: float, cache_entries: int, max_nodes: int,
                max_replay: int, backend: str = "numpy") -> dict:
    schema = make_tpch_like(scale=scale, z=0, seed=seed)
    opt = dataclasses.replace(AdvisorOptions.dtac(),
                              max_planner_nodes=max_nodes,
                              max_replay_entries=max_replay,
                              backend=backend)
    fleet = AdvisorFleetService(
        FleetConfig(slots=1, cache_entries=cache_entries))
    tid = "t0"
    wl = make_tenant_workload(schema, tid, statements, seed + 31)
    adv = DesignAdvisor(wl, opt)
    budget = budget_frac * sum(adv.sizes.size(ix)
                               for ix in base_configuration(schema).indexes)
    fleet.register_tenant(tid, wl, opt)

    rng = np.random.default_rng(seed + 9)
    series = []
    parity_failures = 0
    for rnd in range(rounds):
        delta = make_delta(rng, tid, rnd, wl, schema)
        fleet.submit_delta(tid, delta)
        wl = wl.apply_delta(delta)
        tk = fleet.submit_recommend(tid, budget)
        fleet.run_until_drained()
        if not identical(tk.result(),
                         DesignAdvisor(wl, opt).recommend(budget)):
            parity_failures += 1
            print(f"FAIL: bounded parity broke at round {rnd}",
                  file=sys.stderr)
        ts = fleet.tenant_stats(tid)
        series.append({
            "round": rnd,
            "shared_cache_entries": fleet.stats["shared_cache_entries"],
            "universe_nodes": ts["universe_nodes"],
            "universe_peak_nodes": ts["universe_peak_nodes"],
            "replay_entries": ts["replay_entries"],
        })
    ts = fleet.tenant_stats(tid)
    return {
        "rounds": rounds,
        "bounds": {"cache_entries": cache_entries,
                   "max_planner_nodes": max_nodes,
                   "max_replay_entries": max_replay},
        "parity_failures": parity_failures,
        "evictions": {
            "shared_cache": fleet.stats["shared_cache_evictions"],
            "universe": ts["universe_evictions"],
            "replay": ts["replay_evictions"],
        },
        "peak_shared_cache_entries": max(r["shared_cache_entries"]
                                         for r in series),
        "per_round": series,
    }


# ---------------------------------------------------------------------------
# Phase 3: durable recovery after real process death
# ---------------------------------------------------------------------------

def run_recovery(tenants: int, rounds: int, slots: int, statements: int,
                 scale: float, seed: int, budget_frac: float,
                 repeats: int, backend: str = "numpy") -> dict:
    schema = make_tpch_like(scale=scale, z=0, seed=seed)
    opt = dataclasses.replace(AdvisorOptions.dtac(), backend=backend)
    fc = FleetConfig(slots=slots, retry_backoff=(1, 2, 4, 8),
                     quarantine_after=None)
    configs = [
        {"name": "journal_only", "compact_after": None, "group_commit": 2},
        {"name": "compact_4", "compact_after": 4, "group_commit": 1},
    ]
    rng = np.random.default_rng(seed + 11)
    out_cfgs = []
    parity_failures = 0
    torn_tails_truncated = 0
    with tempfile.TemporaryDirectory(prefix="bench_durability_") as tmp:
        tmp = Path(tmp)
        for cfg in configs:
            base = tmp / f"{cfg['name']}_base"
            # mild disk faults while the storm writes: torn appends and
            # failed group commits, both retried by the fleet
            faults = FaultInjector(seed=seed + 13, specs={
                "disk_write": 0.05, "fsync": 0.05})
            store = DurableStore(base, group_commit=cfg["group_commit"],
                                 compact_after=cfg["compact_after"],
                                 faults=faults)
            fleet = AdvisorFleetService(fc, faults=faults, store=store)
            mirrors, budgets = {}, {}
            for i in range(tenants):
                tid = f"t{i}"
                wl = make_tenant_workload(schema, tid, statements,
                                          seed + 31 + i)
                mirrors[tid] = wl
                adv = DesignAdvisor(wl, opt)
                budgets[tid] = budget_frac * sum(
                    adv.sizes.size(ix)
                    for ix in base_configuration(schema).indexes)
                fleet.register_tenant(tid, wl, opt)
            for rnd in range(rounds):
                dks, deltas = {}, {}
                for tid in mirrors:
                    deltas[tid] = make_delta(rng, tid, rnd, mirrors[tid],
                                             schema)
                    dks[tid] = fleet.submit_delta(tid, deltas[tid])
                fleet.run_until_drained()
                for tid in mirrors:
                    if dks[tid].exception(timeout=1.0) is None:
                        mirrors[tid] = mirrors[tid].apply_delta(
                            deltas[tid])
            wal_records = {tid: len(rt.deltas) for tid, rt in
                           DurableStore(base).recover().items()}
            storm_stats = {k: fleet.stats[k] for k in
                           ("wal_appends", "wal_aborts", "fsyncs",
                            "compactions", "retries")}
            store.close()
            del fleet                  # the process is dead; only the
            del store                  # directory survives

            recover_seconds = []
            recovered = None
            for r in range(repeats):
                trial = tmp / f"{cfg['name']}_r{r}"
                shutil.copytree(base, trial)
                t0 = time.perf_counter()
                recovered = AdvisorFleetService.recover(trial, fc=fc)
                recover_seconds.append(time.perf_counter() - t0)
            assert recovered is not None
            if recovered.recovery_errors:
                parity_failures += len(recovered.recovery_errors)
                print(f"FAIL: {cfg['name']}: recovery errors "
                      f"{recovered.recovery_errors}", file=sys.stderr)
            # the restart-parity gate: every tenant's first
            # post-recovery recommendation == a fresh advisor on the
            # mirror (which only advanced on acknowledged deltas)
            rks = {tid: recovered.submit_recommend(tid, budgets[tid])
                   for tid in mirrors}
            recovered.run_until_drained()
            for tid in mirrors:
                if not identical(rks[tid].result(),
                                 DesignAdvisor(mirrors[tid], opt)
                                 .recommend(budgets[tid])):
                    parity_failures += 1
                    print(f"FAIL: restart parity broke for {tid} under "
                          f"{cfg['name']}", file=sys.stderr)

            # scripted torn tail: garbage appended to one WAL must be
            # truncated at recovery with the tenant fully recovered
            torn = tmp / f"{cfg['name']}_torn"
            shutil.copytree(base, torn)
            with open(torn / "wal" / "t0.wal", "ab") as f:
                f.write(b"DWAL" + b"\xff" * 20)
            tfleet = AdvisorFleetService.recover(torn, fc=fc)
            torn_tails_truncated += tfleet.stats["torn_tail_truncations"]
            tk = tfleet.submit_recommend("t0", budgets["t0"])
            tfleet.run_until_drained()
            if not identical(tk.result(),
                             DesignAdvisor(mirrors["t0"], opt)
                             .recommend(budgets["t0"])):
                parity_failures += 1
                print(f"FAIL: torn-tail parity broke under "
                      f"{cfg['name']}", file=sys.stderr)

            out_cfgs.append({
                "config": cfg,
                "storm": storm_stats,
                "wal_records_replayed": {
                    "total": sum(wal_records.values()),
                    "max_per_tenant": max(wal_records.values()),
                },
                "recovery_latency_seconds": {
                    "repeats": repeats,
                    "p50": round(pct(recover_seconds, 50), 5),
                    "p99": round(pct(recover_seconds, 99), 5),
                    "max": round(max(recover_seconds), 5),
                },
                "recovered_stats": {
                    k: recovered.stats[k] for k in
                    ("recoveries", "torn_tail_truncations",
                     "recovery_errors")},
            })
    return {
        "tenants": tenants, "rounds": rounds,
        "parity_failures": parity_failures,
        "torn_tails_truncated": torn_tails_truncated,
        "configs": out_cfgs,
    }


def run(args, out_path: Path) -> dict:
    storm = run_storm(args.tenants, args.rounds, args.slots,
                      args.statements, args.scale, args.seed,
                      args.budget_frac, args.deadline,
                      args.degraded_budget, args.backend)
    bounded = run_bounded(args.bounded_rounds, args.statements,
                          args.scale, args.seed, args.budget_frac,
                          args.cache_entries, args.max_nodes,
                          args.max_replay, args.backend)
    recovery = run_recovery(args.recovery_tenants, args.recovery_rounds,
                            args.slots, args.statements, args.scale,
                            args.seed, args.budget_frac,
                            args.recovery_repeats, args.backend)
    fired = storm["fault_injector"]["fired"]
    compacting = [c for c in recovery["configs"]
                  if c["config"]["compact_after"] is not None]
    ok = (
        storm["parity_failures"] == 0
        and bounded["parity_failures"] == 0
        # durable restart: every tenant recovered to exact parity, the
        # scripted torn tails were truncated (one per config), and the
        # compacting configuration actually compacted
        and recovery["parity_failures"] == 0
        and recovery["torn_tails_truncated"] == len(recovery["configs"])
        and all(c["recovered_stats"]["recoveries"] ==
                recovery["tenants"] for c in recovery["configs"])
        and all(c["storm"]["compactions"] > 0 for c in compacting)
        # the storm actually stormed...
        and sum(fired.values()) > 0
        and storm["fleet_stats"]["retries"] > 0
        and storm["outcomes"]["crashes"] > 0
        and storm["fleet_stats"]["restores"] >= storm["outcomes"][
            "crashes"]
        # ...and every tenant still produced exact answers through it
        and storm["tenants_with_exact_result"] == storm["tenants"]
        # bounded drift: bounds held and evictions fired at every layer
        and bounded["peak_shared_cache_entries"] <= args.cache_entries
        and all(v > 0 for v in bounded["evictions"].values())
    )
    report = {"backend": args.backend, "storm": storm,
              "bounded": bounded, "recovery": recovery, "ok": ok}
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report, indent=2))
    if ok:
        o = storm["outcomes"]
        rlat = [c["recovery_latency_seconds"]["p50"]
                for c in recovery["configs"]]
        print(f"OK: {o['exact']} exact + {o['degraded_exact']} degraded-"
              f"exact recommends through {sum(fired.values())} injected "
              f"faults, {o['crashes']} crashes, "
              f"{storm['fleet_stats']['restores']} restores; bounded "
              f"drift held every bound with evictions at every layer; "
              f"durable restart exact for {recovery['tenants']} tenants "
              f"(p50 recover {min(rlat)}-{max(rlat)}s across store "
              f"configs)")
    else:
        print("FAIL: durability gate", file=sys.stderr)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", type=int, default=12)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--statements", type=int, default=10)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-frac", type=float, default=0.25)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="unified advisor backend; exactness through "
                    "faults is asserted either way")
    ap.add_argument("--deadline", type=int, default=6,
                    help="recommend deadline in service steps (tight "
                    "enough that queue pressure exercises the degraded "
                    "path at the default sizes)")
    ap.add_argument("--degraded-budget", type=int, default=6)
    ap.add_argument("--bounded-rounds", type=int, default=6)
    ap.add_argument("--cache-entries", type=int, default=8)
    ap.add_argument("--max-nodes", type=int, default=20)
    ap.add_argument("--max-replay", type=int, default=10)
    ap.add_argument("--recovery-tenants", type=int, default=8,
                    help="tenants in the durable-recovery storm")
    ap.add_argument("--recovery-rounds", type=int, default=6,
                    help="delta rounds journaled before process death "
                    "(sets the replayed log length)")
    ap.add_argument("--recovery-repeats", type=int, default=3,
                    help="timed recover() runs per store configuration")
    ap.add_argument("--out", type=Path, default=None,
                    help="output JSON path (default: BENCH_faults.json at "
                    "the repo root; smoke runs write "
                    "BENCH_faults.smoke.json so they never clobber the "
                    "committed trajectory)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI (parity still asserted "
                    "for every resolved recommendation)")
    args = ap.parse_args()
    enable_compile_cache()
    root = Path(__file__).resolve().parent.parent
    if args.smoke:
        args.tenants = 6
        args.rounds = 3
        args.slots = 3
        args.statements = 8
        args.bounded_rounds = 3
        args.recovery_tenants = 4
        args.recovery_rounds = 5
        args.recovery_repeats = 2
    if args.out is None:
        args.out = root / ("BENCH_faults.smoke.json" if args.smoke
                           else "BENCH_faults.json")
    report = run(args, args.out)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
