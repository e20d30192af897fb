"""Chip smoke: the advisor's jax path, end to end, on one TPU.

One process, three phases at TPC-H SF1 (`make_tpch_like(scale=100)`:
6.0M lineitem rows, `make_tpch_workload`'s 22 statements):

  (a) one cold `DesignAdvisor(backend="jax").recommend(0.25 * base size)`,
      checked against the numpy oracle: SampleCF estimates bit-identical,
      the same index set (or, where float32 ties split differently, a
      numpy-costed workload cost within 1e-4 relative);
  (b) one `AdvisorSession` delta round (reweight + add statements), checked
      against a fresh jax `DesignAdvisor` (exact) and the numpy oracle;
  (c) an `AdvisorFleetService` of 4 tenants over 2 schema seeds, each
      recommend -> delta -> recommend, every recommendation `==` a fresh
      jax `DesignAdvisor`.

Counters that must stay zero: engine backend fallbacks, codec envelope
reroutes, fleet prefetch failures, tickets resolved with an exception.

Usage (from the repository root):

    python chip_smoke.py                 # needs a TPU; SF1
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --scale 1
        # CPU rehearsal: Pallas interpret mode at a small scale; runs every
        # phase and check, but never reports ok (exit 3 when all passed)

Without a TPU and without --rehearse it exits 2 before any work.  The last
line of standard output is one JSON object; `"ok": true` only on a TPU
with every check passed.  Phase times are cold single readings: they
include compilation.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
BUDGET_FRAC = 0.25
REL_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str, code: int) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def same_rec(a, b) -> bool:
    return (a.config == b.config and a.cost == b.cost
            and a.used_bytes == b.used_bytes)


def renamed(statements, prefix):
    return tuple(dataclasses.replace(s, name=f"{prefix}{s.name}")
                 for s in statements)


def make_delta(wl, schema, prefix: str, seed: int):
    """Add three statements, double the weight of the first two queries."""
    from repro.core import WorkloadDelta, make_scaled_workload
    added = renamed(make_scaled_workload(schema, n_statements=3,
                                         seed=seed).statements, prefix)
    queries = [q.name for q in wl.queries()][:2]
    return WorkloadDelta(added=added,
                         reweighted=tuple((n, 2.0) for n in queries))


class Checks:
    def __init__(self):
        self.results = {}

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        self.results[name] = bool(ok)
        log(f"  check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip())

    @property
    def ok(self) -> bool:
        return bool(self.results) and all(self.results.values())


def check_recommendation(check, name, wl, sizes_np, rec_jx, rec_np):
    """Same index set as numpy; else the numpy-costed cost of the jax
    configuration within REL_TOL of numpy's."""
    from repro.core import CostEngine
    if rec_jx.config == rec_np.config:
        check(name, True, f"(same {len(rec_np.config.indexes)} indexes)")
        return
    cost = CostEngine(wl, sizes_np, backend="numpy").config_cost(
        rec_jx.config)
    rel = abs(cost - rec_np.cost) / max(abs(rec_np.cost), 1e-12)
    only_np = rec_np.config.indexes - rec_jx.config.indexes
    only_jx = rec_jx.config.indexes - rec_np.config.indexes
    check(name, rel <= REL_TOL,
          f"(index sets differ; numpy-costed jax {cost!r} vs numpy "
          f"{rec_np.cost!r}, rel {rel!r}; only numpy: {sorted(map(str, only_np))}"
          f"; only jax: {sorted(map(str, only_jx))})")


def _plan_sig(plan):
    if plan is None:
        return None
    return plan.f, sorted((repr(k), node.state.name)
                          for k, node in plan.nodes.items())


def check_estimates(check, tables, samples, plans):
    """SampleCF estimates of every SAMPLED plan node: jax == numpy bitwise
    (the integer contract), on the same samples."""
    from repro.core import EstimationEngine, State
    by_f = {}
    for plan in plans:
        if plan is None:
            continue
        keys = by_f.setdefault(plan.f, [])
        keys.extend(k for k, node in plan.nodes.items()
                    if node.state is State.SAMPLED and k not in keys)
    n = 0
    bad = []
    for f, keys in by_f.items():
        got = EstimationEngine(tables, samples, backend="jax").estimate_batch(
            keys, f)
        want = EstimationEngine(tables, samples).estimate_batch(keys, f)
        for k in keys:
            n += 1
            if (got[k].est_bytes != want[k].est_bytes
                    or got[k].cf != want[k].cf):
                bad.append(k)
    check("samplecf_bitwise", not bad and n > 0,
          f"({n} sampled targets, {len(bad)} differ)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=float, default=100.0,
                    help="make_tpch_like scale (100 = TPC-H SF1, 6.0M "
                    "lineitem rows)")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a non-TPU platform (Pallas interpret mode); "
                    "the run then never reports ok")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        fail(f"no TPU: jax found {device}; pass --rehearse for a CPU "
             "rehearsal", 2)
    log(f"device: {device}")

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}", 2)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import backend
    cache_dir = backend.enable_compile_cache()
    log(f"compile cache: {cache_dir}")
    interpret = backend.pallas_interpret()
    if dev.platform == "tpu" and interpret:
        fail("Pallas would run in interpret mode on a TPU", 1)
    log(f"pallas interpret mode: {interpret}")

    from repro.core import (AdvisorOptions, AdvisorSession, DesignAdvisor,
                            SizeProvider, base_configuration,
                            make_tpch_like, make_tpch_workload)
    from repro.kernels import codec_bytes, planner_score
    from repro.serve.advisor_service import AdvisorFleetService, FleetConfig

    jx = AdvisorOptions(backend="jax")
    npo = AdvisorOptions(backend="numpy")
    check = Checks()
    times = {}

    def budget_of(schema):
        sizes = SizeProvider(schema)
        return BUDGET_FRAC * sum(sizes.size(i) for i in
                                 base_configuration(schema).indexes)

    t0 = time.perf_counter()
    schema = make_tpch_like(scale=args.scale, z=0, seed=0)
    wl = make_tpch_workload(schema, insert_weight=0.1)
    budget = budget_of(schema)
    times["data"] = time.perf_counter() - t0
    log(f"scale: {args.scale} (TPC-H SF{args.scale / 100:g}); rows: "
        + ", ".join(f"{k}={t.nrows}" for k, t in schema.tables.items())
        + f"; statements: {len(wl.statements)}; budget bytes: {budget!r}; "
        f"data {times['data']:.2f}s")

    try:
        # --- (a) cold recommend -------------------------------------------
        t0 = time.perf_counter()
        adv_np = DesignAdvisor(wl, npo)
        rec_np = adv_np.recommend(budget)
        times["a_numpy"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        adv_jx = DesignAdvisor(wl, jx)
        rec_jx = adv_jx.recommend(budget)
        times["a_jax_cold"] = time.perf_counter() - t0
        log(f"phase a: numpy recommend {times['a_numpy']:.2f}s, jax "
            f"recommend {times['a_jax_cold']:.2f}s (cold: includes "
            f"compilation); cost numpy {rec_np.cost!r} jax {rec_jx.cost!r}")
        check_estimates(check, schema.tables, adv_np.samples,
                        [rec_np.estimation_plan, rec_jx.estimation_plan])
        # float32 planner scoring may pick another deduction plan; then
        # the registered sizes legitimately differ (informational only)
        log(f"  plans equal: "
            f"{_plan_sig(rec_np.estimation_plan) == _plan_sig(rec_jx.estimation_plan)}"
            f"; registered sizes equal: "
            f"{adv_np.sizes._sizes == adv_jx.sizes._sizes}")
        check_recommendation(check, "a_recommendation", wl, adv_np.sizes,
                             rec_jx, rec_np)

        # --- (b) session delta round --------------------------------------
        t0 = time.perf_counter()
        sess = AdvisorSession(wl, jx)
        sess.recommend(budget)
        delta = make_delta(wl, schema, "smoke_", seed=7)
        sess.apply(delta)
        rec_s = sess.recommend(budget)
        times["b_session"] = time.perf_counter() - t0
        wl2 = wl.apply_delta(delta)
        fresh = DesignAdvisor(wl2, jx).recommend(budget)
        adv2_np = DesignAdvisor(wl2, npo)
        rec2_np = adv2_np.recommend(budget)
        log(f"phase b: session recommend + delta + re-advise "
            f"{times['b_session']:.2f}s; statements {len(wl2.statements)}")
        check("b_session_eq_fresh_jax", same_rec(rec_s, fresh))
        check_recommendation(check, "b_recommendation", wl2, adv2_np.sizes,
                             rec_s, rec2_np)
        st = sess.stats
        check("b_session_counters",
              st["backend_fallbacks"] == 0 and st["envelope_reroutes"] == 0,
              f"(backend_fallbacks={st['backend_fallbacks']}, "
              f"envelope_reroutes={st['envelope_reroutes']})")

        # --- (c) fleet ----------------------------------------------------
        schemas = [schema, make_tpch_like(scale=args.scale, z=0, seed=1)]
        fleet = AdvisorFleetService(FleetConfig(slots=4, backend="jax"))
        tenants = {}
        for i in range(4):
            tid = f"t{i}"
            sch = schemas[i // 2]
            # per schema, one SELECT- and one INSERT-intensive tenant
            # (App. D.2 insert weights 0.1 and 20)
            twl = make_tpch_workload(sch, insert_weight=(0.1, 20.0)[i % 2])
            tenants[tid] = [sch, twl, budget_of(sch)]
            fleet.register_tenant(tid, twl, jx)
        t0 = time.perf_counter()
        tickets = []
        for tid, (sch, twl, tb) in tenants.items():
            first = fleet.submit_recommend(tid, tb)
            delta = make_delta(twl, sch, f"{tid}_", seed=100 + int(tid[1:]))
            tickets.append((tid, "delta", fleet.submit_delta(tid, delta),
                            None))
            tickets.append((tid, "recommend", first, twl))
            twl = twl.apply_delta(delta)
            tenants[tid][1] = twl
            tickets.append((tid, "recommend", fleet.submit_recommend(tid, tb),
                            twl))
        fleet.run_until_drained()
        times["c_fleet"] = time.perf_counter() - t0
        errors = [(tid, kind, tk.exception(timeout=0))
                  for tid, kind, tk, _ in tickets
                  if tk.exception(timeout=0) is not None]
        check("c_no_ticket_errors", not errors, f"({errors})")
        parity = 0
        for tid, kind, tk, twl in tickets:
            if kind != "recommend" or tk.exception(timeout=0) is not None:
                continue
            sch, _, tb = tenants[tid]
            fresh = DesignAdvisor(twl, jx).recommend(tb)
            parity += same_rec(tk.result(timeout=0), fresh)
        check("c_fleet_eq_fresh_jax", parity == 8, f"({parity}/8 equal)")
        fs = fleet.stats
        group_fallbacks = sum(g.engine.stats()["backend_fallbacks"]
                              for g in fleet.groups.values())
        check("c_fleet_counters",
              fs["prefetch_failures"] == 0 and fs["failures"] == 0
              and group_fallbacks == 0,
              f"(prefetch_failures={fs['prefetch_failures']}, "
              f"failures={fs['failures']}, groups={fs['groups']}, "
              f"prefetch_batches={fs['prefetch_batches']}, "
              f"cost_prefetch_batches={fs['cost_prefetch_batches']})")
        log(f"phase c: fleet of 4 tenants, 12 requests, "
            f"{times['c_fleet']:.2f}s over {fs['steps']} steps")
    except Exception as e:  # any phase failing fails the smoke
        import traceback
        traceback.print_exc()
        check("phases_completed", False, f"({type(e).__name__}: {e})")

    cc, pc = codec_bytes.counters(), planner_score.counters()
    check("zero_backend_fallbacks", backend.fallback_count() == 0,
          f"({backend.fallback_count()})")
    check("zero_envelope_reroutes", cc["envelope_reroutes"] == 0,
          f"({cc['envelope_reroutes']})")
    check("device_kernels_ran",
          cc["kernel_calls"] > 0 and pc["fused_calls"] > 0,
          f"(codec {cc['kernel_calls']}, planner fused {pc['fused_calls']}, "
          f"prob {pc['prob_calls']})")
    log("times (s, cold single readings): "
        + json.dumps({k: round(v, 3) for k, v in times.items()}))

    ok = check.ok and dev.platform == "tpu" and not args.rehearse
    if args.rehearse:
        log(f"rehearsal: checks {'passed' if check.ok else 'FAILED'}; "
            "a rehearsal never reports ok")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    if ok:
        return 0
    return 3 if check.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
