"""Batched cost-engine tests: scalar/batched parity on the paper workloads,
staged_recommend (Example 1), and SizeProvider.fallback_hits accounting.

Deliberately hypothesis-free so this module always runs (the property-test
modules skip when hypothesis is not installed).
"""
import numpy as np
import pytest

from repro.core import (AdvisorOptions, CostEngine, DesignAdvisor,
                        base_configuration, make_scaled_workload,
                        make_tpch_like, make_tpch_workload)
from repro.core import candidates as cand
from repro.core.advisor import (pool_with_merged, select_candidates,
                                staged_recommend)
from repro.core.enumeration import greedy_enumerate, greedy_enumerate_scalar
from repro.core.whatif import Configuration
from scalar_reference import scalar_recommend


@pytest.fixture(scope="module")
def schema():
    return make_tpch_like(scale=0.3, z=0, seed=0)


@pytest.fixture(scope="module")
def workload(schema):
    return make_tpch_workload(schema, insert_weight=0.1)


@pytest.fixture(scope="module")
def base_size(schema, workload):
    adv = DesignAdvisor(workload)
    return sum(adv.sizes.size(i) for i in base_configuration(schema).indexes)


def _rel_err(a, b):
    return abs(a - b) / max(abs(b), 1e-12)


class TestConfigCostParity:
    def test_base_config_cost_matches_scalar(self, workload):
        adv = DesignAdvisor(workload)
        base = base_configuration(workload.schema)
        engine = CostEngine(workload, adv.sizes)
        assert _rel_err(engine.config_cost(base),
                        adv.optimizer.workload_cost(base)) < 1e-12

    def test_single_index_configs_match_scalar(self, workload, schema):
        adv = DesignAdvisor(workload)
        base = base_configuration(schema)
        q = workload.queries()[0]
        raw = cand.syntactically_relevant(q, schema.tables[q.table])
        raw = cand.expand_with_compression(raw, ("NS", "LDICT"))
        adv.estimate_sizes(raw)
        engine = CostEngine(workload, adv.sizes)
        configs = []
        for idx in raw:
            if idx.clustered:
                old = base.clustered(idx.table)
                configs.append(base.replace(old, idx))
            else:
                configs.append(base.add(idx))
        batched = engine.config_costs(configs)
        scalar = [adv.optimizer.workload_cost(c) for c in configs]
        np.testing.assert_allclose(batched, scalar, rtol=1e-12)

    def test_workload_cost_batch_api(self, workload):
        adv = DesignAdvisor(workload)
        base = base_configuration(workload.schema)
        out = adv.optimizer.workload_cost_batch([base, base])
        assert out.shape == (2,)
        assert _rel_err(out[0], adv.optimizer.workload_cost(base)) < 1e-12

    def test_cost_candidates_engine_matches_scalar(self, workload, schema):
        adv = DesignAdvisor(workload)
        base = base_configuration(schema)
        engine = CostEngine(workload, adv.sizes)
        for q in workload.queries()[:6]:
            raw = cand.syntactically_relevant(q, schema.tables[q.table])
            raw = cand.expand_with_compression(raw, ("NS", "LDICT"))
            got = cand.cost_candidates(q, raw, base, adv.optimizer,
                                       adv.sizes, engine=engine)
            want = cand.cost_candidates(q, raw, base, adv.optimizer,
                                        adv.sizes)
            assert [c.index.key for c in got] == [c.index.key for c in want]
            np.testing.assert_allclose([c.cost for c in got],
                                       [c.cost for c in want], rtol=1e-12)
            np.testing.assert_allclose([c.size for c in got],
                                       [c.size for c in want], rtol=1e-12)


class TestEnumerationParity:
    @pytest.mark.parametrize("variant", ["pure", "density", "backtrack"])
    @pytest.mark.parametrize("frac", [0.0, 0.15, 0.4, 1.0])
    def test_greedy_matches_scalar(self, workload, schema, base_size,
                                   variant, frac):
        adv = DesignAdvisor(workload)
        pq, merged_all, all_cands = adv._candidate_universe()
        adv.estimate_sizes(all_cands)
        base = base_configuration(schema)
        pool = {}
        for q in workload.queries():
            for c in cand.select_skyline(cand.cost_candidates(
                    q, pq[q.name], base, adv.optimizer, adv.sizes)):
                pool.setdefault(c.index.key, c.index)
        for idx in merged_all:
            pool.setdefault(idx.key, idx)
        pool = list(pool.values())
        budget = frac * base_size
        res_s = greedy_enumerate_scalar(adv.optimizer, adv.sizes, pool,
                                        base, budget, variant=variant)
        engine = CostEngine(workload, adv.sizes)
        res_b = greedy_enumerate(adv.optimizer, adv.sizes, pool, base,
                                 budget, variant=variant, engine=engine)
        assert res_b.config == res_s.config
        assert _rel_err(res_b.cost, res_s.cost) < 1e-6
        assert _rel_err(res_b.used_bytes or 1.0,
                        res_s.used_bytes or 1.0) < 1e-6

    @pytest.mark.parametrize("frac", [0.0, 0.2, 0.6])
    def test_recommend_matches_scalar_end_to_end(self, workload, base_size,
                                                 frac):
        budget = frac * base_size
        rec_b = DesignAdvisor(workload, AdvisorOptions.dtac()).recommend(
            budget)
        rec_s = scalar_recommend(workload, budget)
        assert rec_b.config == rec_s.config
        assert _rel_err(rec_b.cost, rec_s.cost) < 1e-6
        assert _rel_err(rec_b.base_cost, rec_s.base_cost) < 1e-6

    def test_recommend_matches_scalar_scaled_workload(self, schema):
        # seed chosen to avoid degenerate equal-cost optima: some seeds
        # (e.g. 1, 3) produce two clustered orderings whose total costs
        # agree to the last ulp, where scalar/batched summation order
        # legitimately breaks the tie differently
        wl = make_scaled_workload(schema, n_statements=60, seed=5)
        adv = DesignAdvisor(wl)
        base_size = sum(adv.sizes.size(i)
                        for i in base_configuration(schema).indexes)
        rec_b = DesignAdvisor(wl, AdvisorOptions.dtac()).recommend(
            0.25 * base_size)
        rec_s = scalar_recommend(wl, 0.25 * base_size)
        assert rec_b.config == rec_s.config
        assert _rel_err(rec_b.cost, rec_s.cost) < 1e-6

    @pytest.mark.parametrize("seed", [1, 3])
    def test_recommend_matches_scalar_where_rounding_decides(self, schema,
                                                             seed):
        """Seeds whose greedy meets candidates (two clustered orderings)
        with totals one ulp apart: the batched greedy asks the scalar
        optimizer there, and so takes the scalar greedy's choice."""
        wl = make_scaled_workload(schema, n_statements=60, seed=seed)
        adv = DesignAdvisor(wl)
        base_size = sum(adv.sizes.size(i)
                        for i in base_configuration(schema).indexes)
        rec_b = DesignAdvisor(wl, AdvisorOptions.dtac()).recommend(
            0.25 * base_size)
        rec_s = scalar_recommend(wl, 0.25 * base_size)
        assert rec_b.config == rec_s.config
        assert _rel_err(rec_b.cost, rec_s.cost) < 1e-6

    def test_insert_heavy_parity(self, schema, base_size):
        wl = make_tpch_workload(schema, insert_weight=50.0)
        rec_b = DesignAdvisor(wl, AdvisorOptions.dtac()).recommend(
            0.5 * base_size)
        rec_s = scalar_recommend(wl, 0.5 * base_size)
        assert rec_b.config == rec_s.config
        assert _rel_err(rec_b.cost, rec_s.cost) < 1e-6


class TestSmokeParity:
    """Scalar vs batched advising at a 40-statement workload (scale 0.1,
    seed 2, budget 0.3 of the base design), with the cost engine of the
    hot path on each backend."""

    @staticmethod
    def _pool(adv, per_query_exp, merged_all, base, engine=None):
        pool = {}
        for q in adv.workload.queries():
            costed = cand.cost_candidates(q, per_query_exp[q.name], base,
                                          adv.optimizer, adv.sizes,
                                          engine=engine)
            for c in select_candidates(costed, adv.opt):
                pool.setdefault(c.index.key, c.index)
        return list(pool_with_merged(pool, merged_all).values())

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_scalar_vs_batched(self, backend):
        schema = make_tpch_like(scale=0.1, z=0, seed=2)
        wl = make_scaled_workload(schema, n_statements=40, seed=2)
        base = base_configuration(schema)
        budget = 0.3 * sum(DesignAdvisor(wl).sizes.size(i)
                           for i in base.indexes)

        adv = DesignAdvisor(wl, AdvisorOptions.dtac())
        per_query_exp, merged_all, all_cands = adv._candidate_universe()
        adv.estimate_sizes(all_cands)
        pool_s = self._pool(adv, per_query_exp, merged_all, base)
        res_s = greedy_enumerate_scalar(adv.optimizer, adv.sizes, pool_s,
                                        base, budget)
        # a fresh advisor: no warm scalar what-if cache
        adv_b = DesignAdvisor(wl, AdvisorOptions.dtac())
        adv_b.estimate_sizes(all_cands)
        engine = CostEngine(wl, adv_b.sizes, backend=backend)
        pool_b = self._pool(adv_b, per_query_exp, merged_all, base, engine)
        res_b = greedy_enumerate(adv_b.optimizer, adv_b.sizes, pool_b, base,
                                 budget, engine=engine)
        # the jax scoring kernels run in float32
        tol = 1e-6 if backend == "numpy" else 1e-3
        assert [p.key for p in pool_b] == [p.key for p in pool_s]
        assert res_b.config == res_s.config
        assert _rel_err(res_b.cost, res_s.cost) <= tol

        # end to end on the chip benchmark's paths: estimation and
        # planner on `backend`, costing on numpy
        rec_b = DesignAdvisor(wl, AdvisorOptions(
            estimation_backend=backend,
            planner_backend=backend)).recommend(budget)
        rec_s = scalar_recommend(wl, budget)
        assert rec_b.config == rec_s.config
        assert _rel_err(rec_b.cost, rec_s.cost) <= 1e-6


class TestJaxBackend:
    def test_jax_backend_close_to_numpy(self, workload, base_size):
        budget = 0.3 * base_size
        rec_np = DesignAdvisor(workload, AdvisorOptions.dtac()).recommend(
            budget)
        rec_jx = DesignAdvisor(workload, AdvisorOptions(
            engine_backend="jax")).recommend(budget)
        # jax defaults to f32 for the scoring kernel: loose tolerance only
        assert _rel_err(rec_jx.cost, rec_np.cost) < 1e-3
        assert rec_jx.cost <= rec_jx.base_cost


class TestStagedRecommend:
    """Example 1: select-then-compress is a valid but inferior baseline."""

    def test_staged_improves_over_base(self, workload, base_size):
        rec = staged_recommend(workload, 0.25 * base_size)
        assert rec.cost <= rec.base_cost + 1e-9
        assert rec.improvement >= 0.0

    def test_staged_never_beats_dtac(self, workload, base_size):
        for frac in (0.15, 0.3):
            b = frac * base_size
            dtac = DesignAdvisor(workload, AdvisorOptions.dtac()).recommend(b)
            staged = staged_recommend(workload, b)
            assert dtac.cost <= staged.cost + 1e-9

    def test_staged_keeps_one_clustered_per_table(self, workload, schema,
                                                  base_size):
        rec = staged_recommend(workload, 0.3 * base_size)
        for t in schema.tables:
            n = sum(1 for i in rec.config.indexes
                    if i.table == t and i.clustered)
            assert n == 1


class TestSizeProviderAccounting:
    def test_recommend_registers_all_compressed_candidates(self, workload):
        """A full recommend() must size every compressed candidate through
        the §4-§5 estimation framework — zero analytic-prior fallbacks."""
        adv = DesignAdvisor(workload, AdvisorOptions.dtac())
        all_cands = adv.generate_candidates()
        rec = adv.recommend(1e12)
        assert adv.sizes.fallback_hits == 0
        for idx in all_cands:
            if idx.compression is None or idx.predicate is not None:
                continue
            assert adv.sizes._key(idx) in adv.sizes._sizes, idx.label()
        assert adv.sizes.fallback_hits == 0
        assert rec.cost <= rec.base_cost + 1e-9

    def test_fallback_hits_counts_unregistered(self, workload, schema):
        adv = DesignAdvisor(workload)
        idx = cand.syntactically_relevant(
            workload.queries()[0],
            schema.tables[workload.queries()[0].table])[0]
        compressed = idx.with_compression("NS")
        assert adv.sizes.fallback_hits == 0
        s1 = adv.sizes.size(compressed)       # unregistered -> prior fallback
        assert adv.sizes.fallback_hits == 1
        assert s1 == pytest.approx(
            adv.sizes.analytic_uncompressed(compressed)
            * adv.sizes.DEFAULT_CF_PRIOR)
        adv.sizes.register(compressed, 123.0)
        assert adv.sizes.size(compressed) == 123.0
        assert adv.sizes.fallback_hits == 1   # no new fallback
