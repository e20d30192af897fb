"""Workload-compression scaling: the advisor from 200 to 100k statements.

For each workload size the benchmark runs the compressed advisor end-to-end
(`AdvisorOptions.compression_budget`) and records wall time, tracemalloc
peak, process high-water RSS, and the certified compression error bound.
Two hard gates (the PR's acceptance criteria):

* the 10k-statement compressed recommend must finish within the
  200-statement *uncompressed* recommend wall time measured in the same
  process (self-calibrating: no stored reference timings), and
* its tracemalloc peak must stay under a fixed memory cap.

The exact-parity contract is asserted on every run: with the budget
disabled (or >= the statement count) the compressed advisor returns the
bit-identical recommendation of a plain `DesignAdvisor`.

At the largest size the benchmark sweeps the representative budget and
reports the quality-vs-compression tradeoff: the recommendation's true
full-workload cost (via `chunked_config_costs`, which never materializes
the dense statements x candidates matrix) against the certified bound.

Writes a machine-readable trajectory to BENCH_workload.json.

Usage:
    PYTHONPATH=src python benchmarks/workload_scaling.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time
import tracemalloc
from pathlib import Path

from repro.core import (AdvisorOptions, DesignAdvisor, base_configuration,
                        chunked_config_costs, make_scaled_workload,
                        make_tpch_like)
from repro.core.backend import enable_compile_cache
from repro.core.workload_compression import ClusterIndex


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _timed_recommend(wl, options, budget_bytes, trace=False):
    adv = DesignAdvisor(wl, options)
    if trace:
        tracemalloc.start()
    t0 = time.perf_counter()
    rec = adv.recommend(budget_bytes)
    wall = time.perf_counter() - t0
    peak_mb = None
    if trace:
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        peak_mb = peak / 2 ** 20
    return adv, rec, wall, peak_mb


def run(sizes, scale, comp_budget, budget_frac, seed, curve_budgets,
        gate_factor, mem_cap_mb, out_path: Path,
        backend: str = "numpy") -> dict:
    schema = make_tpch_like(scale=scale, z=0, seed=seed)
    base = base_configuration(schema)
    wl0 = make_scaled_workload(schema, n_statements=sizes[0], seed=seed)
    budget_bytes = budget_frac * sum(
        DesignAdvisor(wl0).sizes.size(i) for i in base.indexes)

    # ---- exact-parity contract at the smallest size ----
    rec_full = DesignAdvisor(wl0).recommend(budget_bytes)
    for b in (None, len(wl0.statements), 10 ** 9):
        rec_b = DesignAdvisor(wl0, AdvisorOptions(
            compression_budget=b)).recommend(budget_bytes)
        assert (rec_b.config == rec_full.config
                and rec_b.cost == rec_full.cost
                and rec_b.used_bytes == rec_full.used_bytes), \
            f"exact-parity contract violated at budget={b!r}"
    parity_ok = True

    # ---- self-calibrating reference: uncompressed recommend at sizes[0].
    # best-of-2 on both sides of the gate: single runs flap on scheduler
    # noise when the compressed and reference walls are close ----
    ref_wall = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        DesignAdvisor(wl0).recommend(budget_bytes)
        ref_wall = min(ref_wall, time.perf_counter() - t0)

    # ---- scaling rows ----
    rows = []
    opts = AdvisorOptions(compression_budget=comp_budget,
                          backend=backend)
    for n in sizes:
        t0 = time.perf_counter()
        wl = make_scaled_workload(schema, n_statements=n, seed=seed)
        gen_wall = time.perf_counter() - t0
        # wall time untraced, best-of-2 (tracemalloc roughly doubles
        # Python-alloc-heavy runs), then a traced pass for the peak
        adv, rec, wall, _ = _timed_recommend(wl, opts, budget_bytes)
        _, _, wall2, _ = _timed_recommend(wl, opts, budget_bytes)
        wall = min(wall, wall2)
        _, _, _, peak_mb = _timed_recommend(wl, opts, budget_bytes,
                                            trace=True)
        rows.append({
            "n_statements": n,
            "generate_seconds": round(gen_wall, 4),
            "recommend_seconds": round(wall, 4),
            "tracemalloc_peak_mb": round(peak_mb, 1),
            "rss_high_water_mb": round(_rss_mb(), 1),
            "n_representatives": rec.n_representatives,
            "compression_ratio": round(
                rec.n_statements_full / max(1, rec.n_representatives), 1),
            "cost": rec.cost,
            "error_bound": rec.compression_error_bound,
            "error_rel": rec.compression_error_rel,
        })
        print(f"  n={n:>7}  recommend {wall:7.3f}s  "
              f"peak {peak_mb:7.1f}MB  reps {rec.n_representatives:>4}  "
              f"eps_rel {rec.compression_error_rel:.3f}")

    # ---- gates on the 10k row (largest size <= 10k that was measured) ----
    gate_sizes = [n for n in sizes if n <= 10_000]
    gate_n = max(gate_sizes) if gate_sizes else sizes[0]
    gate_row = next(r for r in rows if r["n_statements"] == gate_n)
    gate_wall_ok = gate_row["recommend_seconds"] <= gate_factor * ref_wall
    gate_mem_ok = gate_row["tracemalloc_peak_mb"] <= mem_cap_mb

    # ---- quality-vs-compression curve at the largest size ----
    n_big = sizes[-1]
    wl_big = make_scaled_workload(schema, n_statements=n_big, seed=seed)
    ix = ClusterIndex.from_workload(wl_big)
    curve = []
    for b in curve_budgets:
        comp = ix.derive(b)
        if comp is None:      # budget >= n: nothing to measure
            continue
        t0 = time.perf_counter()
        inner = DesignAdvisor(comp.workload)
        rec = inner.recommend(budget_bytes)
        wall = time.perf_counter() - t0
        eps = comp.error_bound(rec.config, inner.sizes)
        true_cost = float(chunked_config_costs(
            wl_big, inner.sizes, [rec.config])[0])
        assert abs(true_cost - rec.cost) <= eps + 1e-9 * abs(true_cost), \
            f"error bound violated at budget {b}"
        curve.append({
            "budget": b,
            "n_representatives": comp.n_representatives,
            "compression_ratio": round(comp.compression_ratio, 1),
            "recommend_seconds": round(wall, 4),
            "compressed_cost": rec.cost,
            "true_full_cost": true_cost,
            "error_bound": eps,
            "bound_rel": eps / max(abs(true_cost), 1e-12),
        })
        print(f"  budget={b:>5}  reps {comp.n_representatives:>4}  "
              f"true cost {true_cost:12.2f}  bound_rel "
              f"{eps / max(abs(true_cost), 1e-12):.3f}")

    report = {
        "backend": backend,
        "schema_scale": scale,
        "budget_frac": budget_frac,
        "compression_budget": comp_budget,
        "reference_full_recommend_seconds": round(ref_wall, 4),
        "gate": {
            "n_statements": gate_n,
            "factor": gate_factor,
            "wall_ok": bool(gate_wall_ok),
            "mem_cap_mb": mem_cap_mb,
            "mem_ok": bool(gate_mem_ok),
        },
        "exact_parity_ok": parity_ok,
        "scaling": rows,
        "quality_curve": {"n_statements": n_big, "points": curve},
    }
    ok = gate_wall_ok and gate_mem_ok and parity_ok
    out_path.write_text(json.dumps(report | {"ok": ok}, indent=2) + "\n")
    print(json.dumps(report | {"ok": ok}, indent=2))
    if not gate_wall_ok:
        print(f"FAIL: {gate_n}-statement compressed recommend "
              f"{gate_row['recommend_seconds']:.2f}s exceeds "
              f"{gate_factor:.1f}x the {sizes[0]}-statement full run "
              f"({ref_wall:.2f}s)", file=sys.stderr)
    if not gate_mem_ok:
        print(f"FAIL: tracemalloc peak {gate_row['tracemalloc_peak_mb']:.0f}"
              f"MB exceeds the {mem_cap_mb}MB cap", file=sys.stderr)
    if ok:
        print(f"OK: n={gate_n} compressed recommend "
              f"{gate_row['recommend_seconds']:.2f}s <= "
              f"{gate_factor:.1f}x full-run reference {ref_wall:.2f}s, "
              f"peak {gate_row['tracemalloc_peak_mb']:.0f}MB")
    return report | {"ok": ok}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[200, 2_000, 10_000, 100_000])
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--compression-budget", type=int, default=128)
    ap.add_argument("--budget-frac", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="unified advisor backend for the scaling rows")
    ap.add_argument("--curve-budgets", type=int, nargs="+",
                    default=[32, 64, 128, 256, 512, 1024])
    ap.add_argument("--gate-factor", type=float, default=1.0,
                    help="10k compressed recommend must finish within this "
                    "times the 200-statement full-run wall time")
    ap.add_argument("--mem-cap-mb", type=float, default=1024.0)
    ap.add_argument("--out", type=Path, default=None,
                    help="output JSON path (default: BENCH_workload.json at "
                    "the repo root; smoke runs write "
                    "BENCH_workload.smoke.json so they never clobber the "
                    "committed trajectory)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI")
    args = ap.parse_args()
    enable_compile_cache()
    root = Path(__file__).resolve().parent.parent
    if args.smoke:
        args.sizes = [200, 10_000]
        args.scale = 0.2
        # at smoke scale the 200-statement uncompressed reference is very
        # cheap, so the gate needs a tighter representative budget to hold
        args.compression_budget = 64
        args.curve_budgets = [32, 128]
        args.mem_cap_mb = 512.0
    if args.out is None:
        args.out = root / ("BENCH_workload.smoke.json" if args.smoke
                           else "BENCH_workload.json")
    report = run(args.sizes, args.scale, args.compression_budget,
                 args.budget_frac, args.seed, args.curve_budgets,
                 args.gate_factor, args.mem_cap_mb, args.out, args.backend)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
