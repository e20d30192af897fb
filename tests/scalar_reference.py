"""The scalar reference recommend for parity tests.

`scalar_recommend` composes the advisor's scalar oracles in
`DesignAdvisor._recommend_full`'s order: the scalar §5.2 planner
(`EstimationPlanner(use_engine=False)`), per-target `sample_cf`
(`execute_scalar`), the scalar what-if optimizer for the base cost and
per-query candidate costing, and `greedy_enumerate_scalar`.  Candidates
come from `DesignAdvisor._candidate_universe`, so both sides advise on
the same candidate set.  It runs the uncompressed pipeline only.
"""
from repro.core import candidates as cand
from repro.core.advisor import (AdvisorOptions, DesignAdvisor,
                                Recommendation, pool_with_merged,
                                select_candidates)
from repro.core.enumeration import greedy_enumerate_scalar
from repro.core.estimation_graph import EstimationPlanner
from repro.core.whatif import base_configuration


def scalar_recommend(workload, budget_bytes, options=None):
    adv = DesignAdvisor(workload, options or AdvisorOptions())
    opt = adv.opt
    per_query_exp, merged_all, all_cands = adv._candidate_universe()

    tkey_to_defs = DesignAdvisor.estimation_targets(all_cands)
    plan = None
    if tkey_to_defs:
        planner = EstimationPlanner(adv.schema.tables, use_engine=False)
        targets = list(tkey_to_defs)
        plan = (planner.plan(targets, opt.e, opt.q) if opt.use_deduction
                else planner.plan_all_sampled(targets, opt.e, opt.q))
        for k, est in planner.execute_scalar(plan, adv.samples).items():
            for idx in tkey_to_defs.get(k, ()):
                adv.sizes.register(idx, est.est_bytes)

    base = base_configuration(adv.schema)
    base_cost = adv.optimizer.workload_cost(base)
    pool, n_cand = {}, 0
    for q in workload.queries():
        costed = cand.cost_candidates(q, per_query_exp[q.name], base,
                                      adv.optimizer, adv.sizes)
        n_cand += len(costed)
        for c in select_candidates(costed, opt):
            pool.setdefault(c.index.key, c.index)
    pool_with_merged(pool, merged_all)
    res = greedy_enumerate_scalar(adv.optimizer, adv.sizes,
                                  list(pool.values()), base, budget_bytes,
                                  variant=opt.enumeration)
    n_full = len(workload.statements)
    return Recommendation(
        config=res.config, base=base, base_cost=base_cost, cost=res.cost,
        used_bytes=res.used_bytes, budget_bytes=budget_bytes,
        estimation_cost_pages=plan.total_cost if plan else 0.0,
        estimation_plan=plan,
        n_sampled=plan.n_sampled() if plan else 0,
        n_deduced=plan.n_deduced() if plan else 0,
        candidate_count=n_cand, pool_size=len(pool), wall_seconds=0.0,
        steps=res.steps, n_statements_full=n_full, n_representatives=n_full)
