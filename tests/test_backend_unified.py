"""Backend plumbing: one name for every engine, and exact contracts.

Covers what the per-kernel suite (test_pallas_parity) does not:
AdvisorOptions(backend=...) overriding every per-module field, unknown
names refused, WhatIfOptimizer's engine REBUILD on backend switch, the
jax engine kernels against their numpy twins, session and fleet parity
under backend="jax", the fleet COST-phase stacked costing (bitwise equal
to per-job scoring on both backends), and the batched delta append.

Hypothesis-free so the module always runs.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (AdvisorOptions, CostEngine, DesignAdvisor,
                        WorkloadDelta, base_configuration,
                        make_scaled_workload, make_tpch_like,
                        make_tpch_workload)
from repro.core import backend as bk
from repro.core import candidates as cand
from repro.core.cost_engine import batched_candidate_costs
from repro.core.session import AdvisorSession
from repro.core.whatif import WhatIfOptimizer
from repro.serve.advisor_service import AdvisorFleetService, FleetConfig

BUDGET = 2_000_000


@pytest.fixture(scope="module")
def schema():
    return make_tpch_like(scale=0.2, z=0, seed=0)


@pytest.fixture(scope="module")
def workload(schema):
    return make_tpch_workload(schema, insert_weight=0.1)


def tenant_workload(schema, tid, n=12, seed=0):
    wl = make_scaled_workload(schema, n_statements=n, seed=seed)
    return dataclasses.replace(
        wl, statements=[dataclasses.replace(s, name=f"{tid}_{s.name}")
                        for s in wl.statements])


def identical(a, b):
    return (a.config == b.config and a.cost == b.cost
            and a.used_bytes == b.used_bytes)


class TestUnifiedKnob:
    def test_backend_overrides_per_module_knobs(self):
        opt = AdvisorOptions(backend="jax")
        assert opt.engine_backend == "jax"
        assert opt.estimation_backend == "jax"
        assert opt.planner_backend == "jax"
        opt = AdvisorOptions(backend="numpy", engine_backend="jax")
        assert opt.engine_backend == "numpy"

    def test_none_keeps_per_module_knobs(self):
        opt = AdvisorOptions(engine_backend="jax")
        assert opt.backend is None
        assert opt.engine_backend == "jax"
        assert opt.planner_backend == "numpy"

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown backend"):
            AdvisorOptions(backend="cuda")
        with pytest.raises(ValueError, match="unknown backend"):
            bk.resolve("tpu")

    def test_advisor_threads_backend_everywhere(self, workload):
        adv = DesignAdvisor(workload, AdvisorOptions(backend="jax"))
        rec = adv.recommend(BUDGET)
        assert rec.config is not None
        assert adv.build_engine().backend == "jax"
        assert adv.opt.planner_backend == "jax"
        assert adv.opt.estimation_backend == "jax"


class TestWhatIfEngineRebuild:
    def test_backend_switch_rebuilds_instead_of_raising(self, workload):
        adv = DesignAdvisor(workload)
        w = WhatIfOptimizer(workload, adv.sizes)
        e1 = w.engine("numpy")
        assert e1.backend == "numpy"
        e2 = w.engine("jax")
        assert e2.backend == "jax" and e2 is not e1
        assert w.engine() is e2            # bare call reuses, never rebuilds
        e3 = w.engine("jax")
        assert e3 is e2                    # same backend: no rebuild
        e4 = w.engine("numpy")
        assert e4.backend == "numpy" and e4 is not e2
        base = base_configuration(workload.schema)
        assert np.isfinite(e4.config_cost(base))


class TestJaxEngineKernels:
    """jax engine kernels vs the numpy float64 twins (float32 tolerance;
    the numpy backend remains the bit-parity reference)."""

    def test_candidate_query_costs_close(self, workload, schema):
        adv = DesignAdvisor(workload)
        base = base_configuration(schema)
        q = workload.queries()[0]
        raw = cand.syntactically_relevant(q, schema.tables[q.table])
        raw = cand.expand_with_compression(raw, ("NS", "LDICT"))
        adv.estimate_sizes(raw)
        e_np = CostEngine(workload, adv.sizes)
        e_jx = CostEngine(workload, adv.sizes, backend="jax")
        np.testing.assert_allclose(
            e_jx.candidate_query_costs(q, base, raw),
            e_np.candidate_query_costs(q, base, raw), rtol=2e-6)

    def test_score_replace_clustered_close(self, workload, schema):
        adv = DesignAdvisor(workload)
        base = base_configuration(schema)
        q = workload.queries()[0]
        raw = cand.syntactically_relevant(q, schema.tables[q.table])
        raw = cand.expand_with_compression(raw, ("NS", "LDICT"))
        adv.estimate_sizes(raw)
        secs = [i for i in raw if not i.clustered][:3]
        cls = [i for i in raw if i.clustered]
        if not cls:
            pytest.skip("no clustered candidates on this table")
        e_np = CostEngine(workload, adv.sizes)
        e_jx = CostEngine(workload, adv.sizes, backend="jax")
        for eng in (e_np, e_jx):
            eng.register(base.indexes)
            eng.register(raw)
        t = q.table
        sids = [e_np.blocks[t].id_of(i) for i in secs]
        cids = [e_np.blocks[t].id_of(i) for i in cls]
        qn, un = e_np.score_replace_clustered(t, sids, cids)
        qj, uj = e_jx.score_replace_clustered(t, sids, cids)
        np.testing.assert_allclose(qj, qn, rtol=2e-6)
        np.testing.assert_allclose(uj, un, rtol=2e-6)


class TestStackedCostBatch:
    """The fleet COST phase's stacked scorer vs per-job scoring."""

    def _jobs(self, workload, schema, backend):
        adv = DesignAdvisor(workload)
        base = base_configuration(schema)
        eng = CostEngine(workload, adv.sizes, backend=backend)
        jobs, per_job = [], []
        for q in workload.queries()[:4]:
            raw = cand.syntactically_relevant(q, schema.tables[q.table])
            raw = cand.expand_with_compression(raw, ("NS", "LDICT"))
            adv.estimate_sizes(raw)
            jobs.append(eng.cost_job_arrays(q, base, raw))
            per_job.append(eng.candidate_query_costs(q, base, raw))
        return jobs, per_job

    def test_numpy_stack_bitwise_equals_per_job(self, workload, schema):
        jobs, per_job = self._jobs(workload, schema, "numpy")
        costs = batched_candidate_costs(jobs, backend="numpy")
        for i, want in enumerate(per_job):
            np.testing.assert_array_equal(costs[i, :len(want)], want)

    def test_jax_stack_bitwise_equals_per_job(self, workload, schema):
        jobs, per_job = self._jobs(workload, schema, "jax")
        costs = batched_candidate_costs(jobs, backend="jax")
        for i, want in enumerate(per_job):
            np.testing.assert_array_equal(costs[i, :len(want)], want)

    def test_requires_secondary_free_base(self, workload, schema):
        adv = DesignAdvisor(workload)
        base = base_configuration(schema)
        q = workload.queries()[0]
        raw = cand.syntactically_relevant(q, schema.tables[q.table])
        eng = CostEngine(workload, adv.sizes)
        sec = next(i for i in raw if not i.clustered)
        with pytest.raises(ValueError, match="secondary-free"):
            eng.cost_job_arrays(q, base.add(sec), raw)


class TestSessionJaxParity:
    def test_session_equals_fresh_advisor_jax(self, schema):
        opt = dataclasses.replace(AdvisorOptions.dtac(), backend="jax")
        wl = make_scaled_workload(schema, n_statements=12, seed=11)
        sess = AdvisorSession(wl, opt)
        for rnd in range(3):
            extra = make_scaled_workload(schema, n_statements=2,
                                         seed=300 + rnd)
            added = [dataclasses.replace(s, name=f"r{rnd}_{s.name}")
                     for s in extra.statements]
            sess.add_statements(added)
            wl = wl.apply_delta(WorkloadDelta(added=tuple(added)))
            rec = sess.recommend(BUDGET)
            fresh = DesignAdvisor(wl, opt).recommend(BUDGET)
            assert identical(rec, fresh), rnd

    def test_peeked_cost_jobs_consumed_exactly(self, schema):
        """peek_cost_jobs + accept_cost_results with per-job engine
        values reproduces the un-peeked recommendation bitwise."""
        opt = dataclasses.replace(AdvisorOptions.dtac(), backend="jax")
        wl = make_scaled_workload(schema, n_statements=10, seed=21)
        plain = AdvisorSession(wl, opt).recommend(BUDGET)
        sess = AdvisorSession(wl, opt)
        jobs = sess.peek_cost_jobs()
        assert jobs  # fresh session: every selection is stale
        base = base_configuration(schema)
        res = {q.name: sess.engine.candidate_query_costs(q, base, cands)
               for q, cands in jobs}
        assert sess.accept_cost_results(sess.workload_version, res) == \
            len(res)
        rec = sess.recommend(BUDGET)
        assert identical(rec, plain)
        assert sess.cost_prefetch_consumed == len(res)

    def test_stale_cost_results_dropped(self, schema):
        opt = AdvisorOptions.dtac()
        wl = make_scaled_workload(schema, n_statements=8, seed=22)
        sess = AdvisorSession(wl, opt)
        ver = sess.workload_version
        sess.peek_cost_jobs()
        extra = make_scaled_workload(schema, n_statements=1, seed=400)
        sess.add_statements([dataclasses.replace(s, name=f"x_{s.name}")
                             for s in extra.statements])
        assert sess.accept_cost_results(ver, {"q": np.zeros(3)}) == 0
        rec = sess.recommend(BUDGET)
        fresh = DesignAdvisor(sess.workload, opt).recommend(BUDGET)
        assert identical(rec, fresh)
        assert sess.cost_prefetch_consumed == 0


class TestFleetCostPrefetchParity:
    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_fleet_parity_with_cost_prefetch(self, schema, backend):
        opt = dataclasses.replace(AdvisorOptions.dtac(), backend=backend)
        fleet = AdvisorFleetService(FleetConfig(slots=3))
        wls = {}
        for i in range(3):
            tid = f"t{i}"
            wls[tid] = tenant_workload(schema, tid, seed=60 + i)
            fleet.register_tenant(tid, wls[tid], opt)
        rng_seed = 500
        for rnd in range(2):
            tks = {}
            for i, tid in enumerate(list(wls)):
                extra = make_scaled_workload(
                    schema, n_statements=2, seed=rng_seed + rnd * 10 + i)
                added = [dataclasses.replace(s,
                                             name=f"{tid}_r{rnd}_{s.name}")
                         for s in extra.statements]
                d = WorkloadDelta(added=tuple(added))
                wls[tid] = wls[tid].apply_delta(d)
                fleet.submit_delta(tid, d)
                tks[tid] = fleet.submit_recommend(tid, BUDGET)
            fleet.run_until_drained()
            for tid, tk in tks.items():
                fresh = DesignAdvisor(wls[tid], opt).recommend(BUDGET)
                assert identical(tk.result(), fresh), (backend, rnd, tid)
        st = fleet.stats
        assert st["cost_prefetch_batches"] > 0
        assert st["cost_prefetch_jobs"] > 0
        consumed = sum(t.session.cost_prefetch_consumed
                       for t in fleet.tenants.values())
        assert consumed == st["cost_prefetch_jobs"]


class TestBatchedDeltaAppend:
    def test_grouped_append_bitwise_equals_sequential(self, schema):
        wl = make_scaled_workload(schema, n_statements=10, seed=31)
        adv = DesignAdvisor(wl)
        extra = make_scaled_workload(schema, n_statements=6, seed=32)
        added = tuple(dataclasses.replace(s, name=f"n_{s.name}")
                      for s in extra.statements)
        e1 = CostEngine(wl, adv.sizes)
        e2 = CostEngine(wl, adv.sizes)
        e1.apply_delta(WorkloadDelta(added=added))
        for s in added:                       # one-at-a-time reference
            e2.apply_delta(WorkloadDelta(added=(s,)))
        for t, b1 in e1.blocks.items():
            b2 = e2.blocks[t]
            assert b1.n == b2.n
            for name in ("cov", "seek", "ridr", "scanc", "upd"):
                np.testing.assert_array_equal(
                    getattr(b1, name)[:, :b1.n], getattr(b2, name)[:, :b2.n],
                    err_msg=(t, name))
            for name in ("size", "beta", "alpha", "nrows_idx"):
                np.testing.assert_array_equal(
                    getattr(b1, name)[:b1.n], getattr(b2, name)[:b2.n],
                    err_msg=(t, name))
