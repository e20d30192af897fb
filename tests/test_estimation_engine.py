"""Batched estimation engine: exact parity with scalar SampleCF, batched
kernel equality, SampleManager determinism, the planner's greedy-vs-optimal
behavior on small graphs, the "All" baseline grid scan, and the prefix
sorts' rank fold against np.lexsort.

Everything here is deterministic (no hypothesis dependency) so the parity
guarantees run in every environment; the hypothesis property twins live in
tests/test_core_compression.py and tests/test_core_estimation.py.
"""
import numpy as np
import pytest

from repro.core import (METHODS, AdvisorOptions, DesignAdvisor,
                        EstimationEngine, EstimationPlanner, IndexDef,
                        NodeKey, PlannerEngine, SampleManager, State,
                        batched_sample_cf, make_scaled_workload,
                        make_tpch_like, sample_cf)
from repro.core import compression as C
from repro.core import errors as E
from repro.core.estimation_engine import (_prefix_permutations,
                                          permute_counters)
from repro.core.estimation_graph import F_GRID, FORCE_ALL_Q, sampling_cost
from repro.core.planner_engine import assert_plan_identical
from repro.core.relation import ColumnDef, Table, rows_per_page
from repro.core.samplecf import full_index_sizes
from repro.core.workload import make_tpch_workload
from repro.core.synopses import MVDef, SynopsisManager


@pytest.fixture(scope="module")
def schema():
    return make_tpch_like(scale=0.2, z=0, seed=0)


def make_targets(method="NS", n=4):
    keys = [
        NodeKey("lineitem", ("l_shipdate",), method),
        NodeKey("lineitem", ("l_extendedprice",), method),
        NodeKey("lineitem", ("l_shipdate", "l_extendedprice"), method),
        NodeKey("lineitem", ("l_shipdate", "l_extendedprice",
                             "l_quantity"), method),
        NodeKey("orders", ("o_orderdate",), method),
        NodeKey("orders", ("o_orderdate", "o_totalprice"), method),
    ]
    return keys[:n]


class TestBatchKernelParity:
    """Exact batch-vs-scalar equality for every *_bytes_batch kernel."""

    @pytest.mark.parametrize("method", list(METHODS))
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_batch_equals_scalar(self, method, seed):
        rng = np.random.default_rng(seed)
        m = int(rng.integers(1, 6))
        n = int(rng.integers(2, 400))
        widths = rng.integers(1, 9, m)
        cols = np.stack([
            rng.integers(0, min(1 << (8 * int(w)), 1 << 62), n)
            for w in widths])
        for rpp in (1, 7, n, rows_per_page(int(widths.sum()))):
            got = C.BATCH_KERNELS[method](cols, widths, rpp)
            want = [C.METHODS[method]._fn(cols[i], int(widths[i]), rpp)
                    for i in range(m)]
            assert got.tolist() == want, (method, rpp)

    @pytest.mark.parametrize("method", list(METHODS))
    def test_batch_empty_columns(self, method):
        cols = np.zeros((3, 0), dtype=np.int64)
        got = C.BATCH_KERNELS[method](cols, np.array([1, 4, 8]), 16)
        assert got.tolist() == [0, 0, 0]

    def test_jax_dispatcher_falls_back_without_x64(self):
        # jax's default config is x64-off: backend="jax" runs the int32-
        # safe Pallas kernels, bit-identical to the numpy kernels
        rng = np.random.default_rng(0)
        cols = rng.integers(0, 1000, (2, 64))
        w = np.array([4, 4])
        a = C.batched_bytes("LDICT", cols, w, 16, backend="numpy")
        b = C.batched_bytes("LDICT", cols, w, 16, backend="jax")
        assert a.tolist() == b.tolist()
        assert EstimationEngine({}, SampleManager({}),
                                backend="jax").backend == "jax"

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError):
            EstimationEngine({}, SampleManager({}), backend="tpu")


class TestEnginePlanParity:
    """Acceptance: batched est_bytes byte-identical to scalar sample_cf."""

    def test_execute_matches_execute_scalar(self, schema):
        wl = make_scaled_workload(schema, n_statements=60, seed=0)
        adv = DesignAdvisor(wl, AdvisorOptions.dtac())
        _, _, all_cands = adv._candidate_universe()
        targets = list(DesignAdvisor.estimation_targets(all_cands))
        planner = EstimationPlanner(schema.tables)
        plan = planner.plan(targets, 0.5, 0.9)
        mgr_s = SampleManager(schema.tables, seed=0)
        mgr_b = SampleManager(schema.tables, seed=0)
        ests_s = planner.execute_scalar(plan, mgr_s)
        ests_b = planner.execute(plan, mgr_b)
        assert set(ests_s) == set(ests_b)
        assert any(n.state is State.SAMPLED for n in plan.nodes.values())
        for k, ref in ests_s.items():
            got = ests_b[k]
            assert got.est_bytes == ref.est_bytes, k.label()
            assert got.cf == ref.cf and got.cost_pages == ref.cost_pages
            assert got.method == ref.method and got.index == ref.index

    def test_all_methods_all_fractions(self, schema):
        keys = [NodeKey("lineitem", cols, m)
                for m in METHODS
                for cols in (("l_shipdate",),
                             ("l_returnflag", "l_shipdate"),
                             ("l_shipdate", "l_extendedprice",
                              "l_quantity"))]
        for f in (0.01, 0.10):
            mgr_s = SampleManager(schema.tables, seed=2)
            eng = EstimationEngine(schema.tables,
                                   SampleManager(schema.tables, seed=2))
            ests = eng.estimate_batch(keys, f)
            for k in keys:
                ref = sample_cf(mgr_s, IndexDef(k.table, k.cols, k.method),
                                f)
                assert ests[k].est_bytes == ref.est_bytes, (k.label(), f)
                assert ests[k].cf == ref.cf

    def test_estimate_sizes_batched_equals_scalar(self, schema):
        """The advisor's registered sizes equal per-target `sample_cf`
        (`execute_scalar`) on the plan it executed."""
        wl = make_scaled_workload(schema, n_statements=40, seed=1)
        adv = DesignAdvisor(wl, AdvisorOptions.dtac())
        _, _, cands = adv._candidate_universe()
        cost, plan, ns, nd = adv.estimate_sizes(cands)
        assert (cost, ns, nd) == (plan.total_cost, plan.n_sampled(),
                                  plan.n_deduced())
        ref = EstimationPlanner(schema.tables).execute_scalar(
            plan, SampleManager(schema.tables, seed=adv.opt.sample_seed))
        for k, defs in DesignAdvisor.estimation_targets(cands).items():
            for idx in defs:
                assert adv.sizes.size(idx) == ref[k].est_bytes, idx.label()
        assert adv.sizes.fallback_hits == 0

    def test_mv_index_size_matches_scalar_reference(self, schema):
        samples = SampleManager(schema.tables, seed=0)
        syn = SynopsisManager(schema, samples)
        mv = MVDef("mv_ship", "lineitem", group_by=("l_shipdate",))
        est = syn.mv_index_size(mv, ("l_shipdate",), "LDICT", 0.05)
        # scalar reference: sample_cf on the MV sample as its own table
        smv, n_est = syn.mv_sample(mv, 0.05)
        ref = sample_cf(SampleManager({smv.name: smv}),
                        IndexDef(smv.name, ("l_shipdate",), "LDICT"),
                        1.0, sample_table=smv)
        assert est.cf == ref.cf and est.cost_pages == ref.cost_pages
        w = smv.col_by_name["l_shipdate"].width
        assert est.est_bytes == ref.cf * C.uncompressed_payload_bytes(
            int(n_est), [w])

    def test_engine_counters(self, schema):
        keys = make_targets("LDICT", 6)
        eng = EstimationEngine(schema.tables,
                               SampleManager(schema.tables, seed=0))
        eng.estimate_batch(keys, 0.05)
        assert eng.batch_calls == 2          # lineitem + orders groups
        assert eng.targets_estimated == 6


class TestSampleManager:
    def test_same_seed_identical_samples(self, schema):
        a = SampleManager(schema.tables, seed=7)
        b = SampleManager(schema.tables, seed=7)
        for tname in ("lineitem", "orders"):
            sa = a.get_sample(tname, 0.05)
            sb = b.get_sample(tname, 0.05)
            assert sa.nrows == sb.nrows
            for c in sa.columns:
                assert np.array_equal(sa.values[c.name], sb.values[c.name])

    def test_different_seed_differs(self, schema):
        a = SampleManager(schema.tables, seed=0).get_sample("lineitem", 0.05)
        b = SampleManager(schema.tables, seed=1).get_sample("lineitem", 0.05)
        assert not all(np.array_equal(a.values[c.name], b.values[c.name])
                       for c in a.columns)

    def test_sampling_amortized_across_engine_targets(self, schema):
        """§4.1: sampling_calls stays flat per (table, f), however many
        targets share it — through the batched engine too."""
        mgr = SampleManager(schema.tables, seed=0)
        eng = EstimationEngine(schema.tables, mgr)
        li = [NodeKey("lineitem", cols, m)
              for m in ("NS", "LDICT", "RLE")
              for cols in (("l_shipdate",), ("l_shipdate", "l_quantity"))]
        eng.estimate_batch(li, 0.05)
        assert mgr.sampling_calls == 1
        eng.estimate_batch(li, 0.05)
        assert mgr.sampling_calls == 1       # cached sample reused
        eng.estimate_batch(li, 0.025)
        assert mgr.sampling_calls == 2       # new f => one new draw
        eng.estimate_batch(make_targets("NS", 6), 0.05)
        assert mgr.sampling_calls == 3       # orders joins in once

    @pytest.mark.parametrize("method",
                             [m for m in METHODS if m != "GDICT"])
    def test_samplecf_within_fitted_error_model(self, schema, method):
        """Ground truth (full_index_sizes) vs SampleCF on a fixed seed:
        the bias-corrected estimate's error stays within a few fitted
        standard deviations of the §5.1 error model."""
        f = 0.05
        mgr = SampleManager(schema.tables, seed=3)
        li = schema.tables["lineitem"]
        idx = IndexDef("lineitem", ("l_shipdate", "l_returnflag"),
                       compression=method)
        _, true = full_index_sizes(li, idx)
        est = sample_cf(mgr, idx, f)
        rv = E.samplecf_error(method, f)
        assert abs(est.est_bytes / true - 1) <= max(4 * rv.std, 0.03)

    def test_gdict_samplecf_overestimates(self, schema):
        """GDICT is the known exception to linear CF scaling (NDV does not
        scale with the sample); the App. B Adaptive Estimator now prices
        the full dictionary directly.  Flipped from a characterization
        test (estimate pinned between truth and the uncompressed size) to
        a tolerance assertion in the spirit of the paper's ~6% AE error
        (Table 1), across the whole f grid."""
        li = schema.tables["lineitem"]
        idx = IndexDef("lineitem", ("l_shipdate", "l_returnflag"),
                       compression="GDICT")
        _, true = full_index_sizes(li, idx)
        for f in F_GRID:
            mgr = SampleManager(schema.tables, seed=3)
            est = sample_cf(mgr, idx, f)
            assert abs(est.est_bytes / true - 1) <= 0.10, f


class TestPlannerEngine:
    """Batched §5.2 planner engine vs the scalar greedy reference."""

    def advisor_targets(self, schema, n_statements=60, seed=0):
        wl = make_scaled_workload(schema, n_statements=n_statements,
                                  seed=seed)
        adv = DesignAdvisor(wl, AdvisorOptions.dtac())
        _, _, all_cands = adv._candidate_universe()
        return list(DesignAdvisor.estimation_targets(all_cands))

    def test_greedy_batch_plan_identical_over_grid(self, schema):
        targets = self.advisor_targets(schema)
        planner = EstimationPlanner(schema.tables)
        batched = planner.engine.greedy_batch(targets, 0.5, 0.9, F_GRID)
        assert any(p.n_deduced() for p in batched)  # non-trivial plans
        for f, got in zip(F_GRID, batched):
            assert_plan_identical(
                planner.greedy_scalar(targets, f, 0.5, 0.9), got)

    def test_plan_matches_plan_scalar(self, schema):
        targets = self.advisor_targets(schema)
        planner = EstimationPlanner(schema.tables)
        for e, q in ((0.5, 0.9), (0.05, 0.99), (1.0, 0.8)):
            assert_plan_identical(planner.plan_scalar(targets, e, q),
                                  planner.plan(targets, e, q))

    def test_plan_all_sampled_matches_scalar(self, schema):
        targets = make_targets("LDICT", 4)
        planner = EstimationPlanner(schema.tables)
        for e, q in ((0.2, 0.9), (0.05, 0.99)):
            got = planner.plan_all_sampled(targets, e, q)
            planner.use_engine = False
            ref = planner.plan_all_sampled(targets, e, q)
            planner.use_engine = True
            assert_plan_identical(ref, got)

    def test_force_all_q_parity(self, schema):
        targets = make_targets("NS", 6)
        planner = EstimationPlanner(schema.tables)
        for f in F_GRID:
            got = planner.engine.greedy_batch(targets, 0.3, FORCE_ALL_Q,
                                              (f,))[0]
            assert_plan_identical(
                planner.greedy_scalar(targets, f, 0.3, FORCE_ALL_Q), got)
            assert got.n_deduced() == 0  # q > 1 forces sampling everywhere

    def test_existing_exact_nodes(self, schema):
        existing = {NodeKey("lineitem", ("l_shipdate",), "NS"): 12345.0,
                    NodeKey("lineitem",
                            ("l_shipdate", "l_extendedprice"), "NS"): 99.0}
        planner = EstimationPlanner(schema.tables, existing=existing)
        targets = make_targets("NS", 4)
        for f in (0.01, 0.05):
            got = planner.engine.greedy_batch(targets, 0.5, 0.9, (f,))[0]
            assert_plan_identical(
                planner.greedy_scalar(targets, f, 0.5, 0.9), got)
            for k, size in existing.items():
                assert got.nodes[k].state is State.EXACT
                assert got.nodes[k].exact_bytes == size

    def test_graph_built_once_across_runs(self, schema):
        targets = make_targets("NS", 6)
        eng = PlannerEngine(schema.tables)
        eng.greedy_batch(targets, 0.5, 0.9, F_GRID)
        eng.greedy_batch(targets, 0.1, 0.99, F_GRID)
        eng.plan_batch(targets, 0.5, 0.9)
        assert eng.graph_builds == 1     # shared deduction graph reused
        assert eng.batch_runs == 3

    def test_estimate_sizes_planner_toggle_parity(self, schema):
        """The advisor's plan is plan-identical to the scalar planner's,
        and its sizes equal that plan's scalar execution."""
        wl = make_scaled_workload(schema, n_statements=40, seed=1)
        adv = DesignAdvisor(wl, AdvisorOptions.dtac())
        _, _, cands = adv._candidate_universe()
        _, plan, _, _ = adv.estimate_sizes(cands)
        tkeys = DesignAdvisor.estimation_targets(cands)
        planner_s = EstimationPlanner(schema.tables, use_engine=False)
        plan_s = planner_s.plan(list(tkeys), adv.opt.e, adv.opt.q)
        assert_plan_identical(plan_s, plan)
        ref = planner_s.execute_scalar(
            plan_s, SampleManager(schema.tables, seed=adv.opt.sample_seed))
        for k, defs in tkeys.items():
            for idx in defs:
                assert adv.sizes.size(idx) == ref[k].est_bytes, idx.label()

    def test_backend_gating(self, schema):
        # both names run as asked; any other name is refused
        assert PlannerEngine(schema.tables, backend="jax").backend == "jax"
        with pytest.raises(ValueError):
            PlannerEngine(schema.tables, backend="tpu")


class TestBatchedScoring:
    """The walk scores the targets it re-decides in batches gathered ahead
    of it (one kernel launch each on jax) and decides them in order; a
    score gathered before a write to what its target reads is dropped and
    the target scored again, so the plans are those of scoring one target
    at a time."""

    # the two 3-column targets share a batch; at f = 0.075 the first
    # samples (l_shipdate) by lines 8-9, which the second reads
    STALE = ([("l_discount", "l_extendedprice"),
              ("l_discount", "l_extendedprice", "l_shipdate"),
              ("l_quantity", "l_extendedprice", "l_shipdate")], 0.2, 0.9)

    @staticmethod
    def d2_targets(schema, insert_weight=0.1):
        """The App. D.2 workload's estimation targets."""
        wl = make_tpch_workload(schema, insert_weight=insert_weight)
        adv = DesignAdvisor(wl, AdvisorOptions.dtac())
        _, _, cands = adv._candidate_universe()
        return list(DesignAdvisor.estimation_targets(cands))

    @staticmethod
    def one_per_launch(monkeypatch):
        """Batches of one target: the walk as it scored before batching."""
        from repro.kernels import planner_score as ps
        monkeypatch.setattr(ps, "fits", lambda rows, k: False)

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_child_sample_makes_a_later_score_stale(self, schema, backend,
                                                    monkeypatch):
        cols, e, q = self.STALE
        targets = [NodeKey("lineitem", c, "NS") for c in cols]
        eng = PlannerEngine(schema.tables, backend=backend)
        plans = eng.greedy_batch(targets, e, q, F_GRID)
        assert eng.stale_rescores == 1
        assert (eng.score_launches, eng.scored_records) == (3, 4)
        sampled_child = {f for p, f in zip(plans, F_GRID)
                         for k, nd in p.nodes.items()
                         if k not in targets and nd.state is State.SAMPLED}
        assert sampled_child == {0.075}
        if backend == "numpy":
            planner = EstimationPlanner(schema.tables)
            for f, got in zip(F_GRID, plans):
                assert_plan_identical(
                    planner.greedy_scalar(targets, f, e, q), got, f"f={f}")
        else:
            self.one_per_launch(monkeypatch)
            alone = PlannerEngine(schema.tables, backend=backend)
            for f, a, b in zip(F_GRID, alone.greedy_batch(targets, e, q,
                                                          F_GRID), plans):
                assert_plan_identical(a, b, f"f={f}")
            assert alone.score_launches == alone.scored_records == 3

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_d2_batches_decide_as_one_at_a_time(self, schema, backend,
                                                monkeypatch):
        """On the App. D.2 workload's targets a launch holds several
        targets, and the plans are bitwise those of one target a launch
        (numpy: of the scalar greedy)."""
        targets = self.d2_targets(schema)
        eng = PlannerEngine(schema.tables, backend=backend)
        plans = eng.greedy_batch(targets, 0.5, 0.9, F_GRID)
        assert 0 < eng.score_launches < eng.scored_records
        assert eng.scored_records >= 4 * eng.score_launches
        if backend == "numpy":
            planner = EstimationPlanner(schema.tables)
            for f, got in zip(F_GRID, plans):
                assert_plan_identical(
                    planner.greedy_scalar(targets, f, 0.5, 0.9), got,
                    f"f={f}")
        else:
            self.one_per_launch(monkeypatch)
            alone = PlannerEngine(schema.tables, backend=backend)
            for f, a, b in zip(F_GRID, alone.greedy_batch(
                    targets, 0.5, 0.9, F_GRID), plans):
                assert_plan_identical(a, b, f"f={f}")
            assert alone.score_launches == alone.scored_records

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_session_after_delta_equals_fresh(self, schema, backend):
        """A recording engine replays what a delta left alone and batches
        what it re-decides among the replays; its plans after the delta
        equal a fresh engine's."""
        before = self.d2_targets(schema, 0.1)
        after = TestPlannerEngine().advisor_targets(schema, seed=1)
        eng = PlannerEngine(schema.tables, backend=backend)
        eng.greedy_batch(before, 0.5, 0.9, F_GRID)
        launches = eng.score_launches
        got = eng.greedy_batch(after, 0.5, 0.9, F_GRID)
        fresh = PlannerEngine(schema.tables, backend=backend)
        for f, a, b in zip(F_GRID, fresh.greedy_batch(after, 0.5, 0.9,
                                                      F_GRID), got):
            assert_plan_identical(a, b, f"f={f}")
        assert eng.replay_hits + eng.replay_verified > 0
        assert eng.score_launches > launches

    def test_jax_walk_warms_its_programs(self, schema, monkeypatch):
        """The jax walk asks for every program a batch can launch before
        its first decision, once per accuracy target (a no-op where the
        kernels are interpreted)."""
        from repro.kernels import planner_score as ps
        asked = []
        monkeypatch.setattr(ps, "warm_once",
                            lambda e, q: asked.append((e, q)) or 0)
        targets = make_targets("NS", 4)
        PlannerEngine(schema.tables, backend="jax").greedy_batch(
            targets, 0.5, 0.9, F_GRID)
        PlannerEngine(schema.tables).greedy_batch(targets, 0.5, 0.9,
                                                  F_GRID)
        assert asked == [(0.5, 0.9)]


class TestSmokeParity:
    """Plan-identical planning and byte-identical SampleCF at a 40-
    statement workload (scale 0.1, seed 0), with SampleCF on each
    backend; the planner runs its numpy parity backend."""

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_engines_match_oracles(self, backend):
        schema = make_tpch_like(scale=0.1, z=0, seed=0)
        wl = make_scaled_workload(schema, n_statements=40, seed=0)
        adv = DesignAdvisor(wl, AdvisorOptions(estimation_backend=backend))
        e, q = adv.opt.e, adv.opt.q
        _, _, cands = adv._candidate_universe()
        tkeys = DesignAdvisor.estimation_targets(cands)
        targets = list(tkeys)
        planner = EstimationPlanner(schema.tables)
        plan = planner.plan(targets, e, q)
        assert_plan_identical(planner.plan_scalar(targets, e, q), plan,
                              "plan()")
        for f, got in zip(F_GRID, planner.engine.greedy_batch(
                targets, e, q, F_GRID)):
            assert_plan_identical(planner.greedy_scalar(targets, f, e, q),
                                  got, f"greedy(f={f})")

        mgr_s = SampleManager(schema.tables, seed=adv.opt.sample_seed)
        mgr_b = SampleManager(schema.tables, seed=adv.opt.sample_seed)
        ests_s = planner.execute_scalar(plan, mgr_s)
        engine = EstimationEngine(schema.tables, mgr_b, backend=backend)
        ests_b = planner.execute(plan, mgr_b, engine=engine)
        assert engine.targets_estimated > 0
        assert set(ests_s) == set(ests_b)
        for k, ref in ests_s.items():
            got = ests_b[k]
            assert (got.est_bytes == ref.est_bytes and got.cf == ref.cf
                    and got.cost_pages == ref.cost_pages
                    and got.method == ref.method), k.label()

        adv.estimate_sizes(cands)
        for k, defs in tkeys.items():
            for idx in defs:
                assert adv.sizes.size(idx) == ests_s[k].est_bytes, \
                    idx.label()


class TestGreedyVsOptimal:
    """Small graphs (<= 6 targets): greedy within the paper's bound,
    (e, q) satisfied whenever a plan is feasible, infeasibility flagged."""

    CASES = [
        ("NS", 0.8, 0.85), ("NS", 0.3, 0.9), ("LDICT", 1.0, 0.8),
        ("LDICT", 0.5, 0.9),
    ]

    @pytest.mark.parametrize("method,e,q", CASES)
    def test_optimal_not_worse_and_bounded_by_all_sampled(
            self, schema, method, e, q):
        planner = EstimationPlanner(schema.tables)
        targets = make_targets(method, 6)
        for f in (0.05, 0.10):
            g = planner.greedy(targets, f, e, q)
            o = planner.optimal(targets, f, e, q)
            all_cost = sum(sampling_cost(schema.tables[t.table], t, f)
                           for t in targets)
            assert o.total_cost <= g.total_cost + 1e-9
            assert g.total_cost <= all_cost + 1e-9   # §5.2 greedy bound
            if o.feasible:
                for t in targets:
                    assert E.satisfies(o.nodes[t].rv, e, q)
            if g.feasible:
                for t in targets:
                    assert E.satisfies(g.nodes[t].rv, e, q)

    def test_feasible_case_agrees(self, schema):
        planner = EstimationPlanner(schema.tables)
        targets = make_targets("NS", 4)
        g = planner.greedy(targets, 0.05, 0.8, 0.85)
        o = planner.optimal(targets, 0.05, 0.8, 0.85)
        assert g.feasible and o.feasible

    @pytest.mark.parametrize("method", ["NS", "LDICT"])
    def test_optimal_plan_executes_through_batched_engine(self, schema,
                                                          method):
        """App. D plans run through the batched EstimationEngine exactly
        like greedy plans: byte-identical to the scalar execute path."""
        planner = EstimationPlanner(schema.tables)
        targets = make_targets(method, 6)
        plan = planner.optimal(targets, 0.05, 0.8, 0.85)
        mgr_s = SampleManager(schema.tables, seed=0)
        mgr_b = SampleManager(schema.tables, seed=0)
        ests_s = planner.execute_scalar(plan, mgr_s)
        ests_b = planner.execute(plan, mgr_b)
        assert set(ests_s) == set(ests_b)
        assert any(n.state is State.SAMPLED for n in plan.nodes.values())
        for k, ref in ests_s.items():
            got = ests_b[k]
            assert (got.est_bytes == ref.est_bytes and got.cf == ref.cf
                    and got.cost_pages == ref.cost_pages
                    and got.method == ref.method), k.label()

    def test_optimal_execute_cached_matches_scalar(self, schema):
        """The session's (NodeKey, f)-cached executor resolves optimal
        plans byte-identically too, and repeated calls hit the cache."""
        planner = EstimationPlanner(schema.tables)
        targets = make_targets("NS", 5)
        plan = planner.optimal(targets, 0.05, 0.8, 0.85)
        mgr_s = SampleManager(schema.tables, seed=0)
        mgr_c = SampleManager(schema.tables, seed=0)
        cache = {}
        ests_s = planner.execute_scalar(plan, mgr_s)
        ests_c = planner.execute_cached(plan, mgr_c, cache)
        n_cached = len(cache)
        assert n_cached == sum(1 for n in plan.nodes.values()
                               if n.state is State.SAMPLED)
        for k, ref in ests_s.items():
            assert ests_c[k].est_bytes == ref.est_bytes
        # second execution: all sampled estimates come from the cache
        ests_c2 = planner.execute_cached(plan, mgr_c, cache)
        assert len(cache) == n_cached
        for k, ref in ests_c.items():
            assert ests_c2[k].est_bytes == ref.est_bytes

    def test_infeasible_flagged_by_both(self, schema):
        """e/q so tight that even SampleCF cannot meet the bound for
        ORD-DEP methods: every plan must be flagged infeasible."""
        planner = EstimationPlanner(schema.tables)
        targets = make_targets("LDICT", 4)
        assert not E.satisfies(E.samplecf_error("LDICT", 0.10), 0.05, 0.99)
        g = planner.greedy(targets, 0.10, 0.05, 0.99)
        assert not g.feasible
        o = planner.optimal(targets, 0.10, 0.05, 0.99)
        assert not o.feasible
        p = planner.plan(targets, 0.05, 0.99)
        assert not p.feasible                # grid scan can't rescue it


class TestAllSampledBaseline:
    """Regression for the estimate_sizes "All" loop: the f grid must
    actually be scanned against the caller's (e, q) — the old code broke
    on F_GRID[0] unconditionally (q>1 plans are never feasible)."""

    def test_scans_grid_to_satisfy_constraint(self, schema):
        e, q = 0.2, 0.9
        # LDICT sampling error at the smallest fractions violates (e, q):
        # the intended behavior picks the first f on the grid that works
        expected_f = next(f for f in F_GRID
                          if E.satisfies(E.samplecf_error("LDICT", f), e, q))
        assert expected_f > F_GRID[0]        # the scan is non-trivial
        planner = EstimationPlanner(schema.tables)
        plan = planner.plan_all_sampled(make_targets("LDICT", 4), e, q)
        assert plan.f == expected_f
        assert plan.feasible
        assert plan.n_deduced() == 0
        assert plan.n_sampled() == 4

    def test_infeasible_falls_back_to_cheapest(self, schema):
        planner = EstimationPlanner(schema.tables)
        plan = planner.plan_all_sampled(make_targets("LDICT", 4), 0.05, 0.99)
        assert not plan.feasible
        assert plan.f == F_GRID[0]           # cheapest all-sampled plan
        assert plan.n_deduced() == 0

    def test_advisor_all_baseline_uses_grid(self, schema):
        wl = make_scaled_workload(schema, n_statements=30, seed=0)
        adv = DesignAdvisor(wl, AdvisorOptions(use_deduction=False,
                                               e=0.2, q=0.9))
        _, _, cands = adv._candidate_universe()
        cost, plan, n_s, n_d = adv.estimate_sizes(cands)
        assert n_d == 0                      # "All" never deduces
        assert plan.f > F_GRID[0]            # grid actually scanned
        assert plan.feasible
        assert cost > 0

    def test_forced_sampling_matches_manual_greedy(self, schema):
        """plan_all_sampled(f) states match greedy under q>1 at the same
        f (the forcing trick), with feasibility re-judged honestly."""
        from repro.core.estimation_graph import FORCE_ALL_Q
        planner = EstimationPlanner(schema.tables)
        targets = make_targets("LDICT", 4)
        plan = planner.plan_all_sampled(targets, 0.2, 0.9)
        manual = planner.greedy(targets, plan.f, 0.2, FORCE_ALL_Q)
        assert plan.states() == manual.states()
        assert not manual.feasible           # q>1 is unsatisfiable...
        assert plan.feasible                 # ...but the real q holds


class TestReplacedFractionBatch:
    def test_bit_identical_to_scalar(self, schema):
        """The batched F(I_X, Y) stats equal the scalar ones exactly —
        both fill the same per-table cache, so any drift would leak
        between the scalar and batched ColExt deduction paths."""
        import copy

        from repro.core import deduction as D
        table = schema.tables["lineitem"]
        cols = [c.name for c in table.columns]
        for w in (1, 2, 3):
            for start in range(len(cols) - w + 1):
                ic = tuple(cols[start:start + w])
                fresh = copy.copy(table)
                fresh._stats_cache = {
                    k: v for k, v in table._stats_cache.items()
                    if k[0] != "ded_rf"}
                got = D.replaced_fraction_batch(fresh, ic, list(ic)).tolist()
                want = [D.replaced_fraction(table, ic, c) for c in ic]
                assert got == want, ic


class TestSinglePageClosedForms:
    """The engine's single-page LDICT/PREFIX closed forms vs the kernels."""

    def test_single_page_matches_kernel(self):
        rng = np.random.default_rng(0)
        n = 50
        t = Table("t", [ColumnDef("a", 4), ColumnDef("b", 2)], {
            "a": rng.integers(0, 9, n), "b": rng.integers(0, 500, n)})
        mgr = SampleManager({"t": t}, seed=0)
        specs = [(("a", "b"), m) for m in ("LDICT", "PREFIX", "RLE")]
        # f=1.0 -> sample is the table; rpp >> n -> single page everywhere
        got = batched_sample_cf(t, t, specs, 1.0)
        for (cols, m), est in zip(specs, got):
            ref = sample_cf(mgr, IndexDef("t", cols, m), 1.0,
                            sample_table=t)
            assert est.est_bytes == ref.est_bytes, m
            assert est.cf == ref.cf

    def test_multi_page_boundary(self):
        """n just above/below rows-per-page crosses the closed-form
        boundary; both sides must match the scalar path exactly."""
        rng = np.random.default_rng(1)
        rpp = rows_per_page(8 + 8)   # two 8-byte columns
        for n in (rpp - 1, rpp, rpp + 1, 3 * rpp + 5):
            t = Table("t", [ColumnDef("a", 8), ColumnDef("b", 8)], {
                "a": rng.integers(0, 7, n), "b": rng.integers(0, 1 << 40, n)})
            mgr = SampleManager({"t": t}, seed=0)
            for m in METHODS:
                est = batched_sample_cf(t, t, [(("a", "b"), m)], 1.0)[0]
                ref = sample_cf(mgr, IndexDef("t", ("a", "b"), m), 1.0,
                                sample_table=t)
                assert est.est_bytes == ref.est_bytes, (m, n)


# ---------------------------------------------------------------------------
# Prefix sorts: the rank fold of _prefix_permutations against np.lexsort
# ---------------------------------------------------------------------------

def _heads(*keys):
    """Every needed prefix of the given key column tuples."""
    return sorted({k[:j + 1] for k in keys for j in range(len(k))})


def _lineitem_like(n, seed=0):
    """24 lineitem columns at TPC-H widths (text as 8-byte payload
    columns, as bench/gen/tpch.py lays them out), with TPC-H's NDVs."""
    rng = np.random.default_rng(seed)
    top = (1 << 63) - 1
    instruct = rng.integers(0, top, (4, 4))[rng.integers(0, 4, n)]
    cols = {
        "l_returnflag": (1, rng.integers(0, 3, n)),
        "l_linestatus": (1, rng.integers(0, 2, n)),
        "l_shipmode": (1, rng.integers(0, 7, n)),
        "l_discount": (1, rng.integers(0, 11, n)),
        "l_tax": (1, rng.integers(0, 9, n)),
        "l_quantity": (1, 1 + rng.integers(0, 50, n)),
        "l_linenumber": (4, 1 + rng.integers(0, 7, n)),
        "l_shipdate": (4, 728_000 + rng.integers(0, 2_526, n)),
        "l_commitdate": (4, 728_000 + rng.integers(0, 2_526, n)),
        "l_receiptdate": (4, 728_000 + rng.integers(0, 2_526, n)),
        "l_suppkey": (4, rng.integers(0, 10_000, n)),
        "l_partkey": (4, rng.integers(0, 200_000, n)),
        "l_orderkey": (4, rng.integers(0, 6_000_000, n)),
        "l_extendedprice": (4, rng.integers(100, 10_500_000, n)),
    }
    for i in range(4):
        cols[f"l_shipinstruct{i}"] = (8, instruct[:, i])
    for i in range(6):
        cols[f"l_comment{i}"] = (8, rng.integers(0, top, n))
    return Table("lineitem", [ColumnDef(c, w) for c, (w, _) in cols.items()],
                 {c: v for c, (_, v) in cols.items()})


# low-NDV columns first, so the fold passes 63 bits before it turns
# all-distinct, with the wide columns left to skip
LINEITEM_COVER = (
    "l_returnflag", "l_linestatus", "l_shipmode", "l_shipinstruct0",
    "l_shipinstruct1", "l_shipinstruct2", "l_shipinstruct3", "l_discount",
    "l_tax", "l_quantity", "l_linenumber", "l_shipdate", "l_commitdate",
    "l_receiptdate", "l_suppkey", "l_partkey", "l_orderkey",
    "l_extendedprice") + tuple(f"l_comment{i}" for i in range(6))


def _perm_case(case):
    """(table, needed prefixes, counters that must engage) of one case."""
    rng = np.random.default_rng(7)
    if case == "wide24":
        # 24 columns, ~14 bits each at 3000 rows: far past 63 bits
        t = _lineitem_like(3000)
        keys = [LINEITEM_COVER, LINEITEM_COVER[11:] + LINEITEM_COVER[:11],
                tuple(rng.permutation(LINEITEM_COVER))]
        return t, _heads(*keys), ("densify", "cut_short", "columns_skipped")
    if case == "distinct_lead":
        n = 500
        vals = {"id": rng.permutation(n), "a": rng.integers(0, 5, n),
                "b": rng.integers(0, 3, n)}
        t = Table("t", [ColumnDef(c, 4) for c in vals], vals)
        return t, _heads(("id", "a", "b"), ("a", "id", "b")), \
            ("cut_short", "scattered")
    if case == "low_ndv_ties":
        # 60 two- and three-valued columns, 90 bits: the first 45 repeat
        # 20 row patterns, so the key is full of ties when it passes 63
        # bits (at 42 columns) and only the last 15 break them
        n = 400
        rows = rng.integers(0, 20, n)
        vals = {f"c{i}": rng.integers(0, 2 + i % 2, 20)[rows] if i < 45
                else rng.integers(0, 2 + i % 2, n) for i in range(60)}
        t = Table("t", [ColumnDef(c, 1) for c in vals], vals)
        key = tuple(vals)
        # the third shares the first's re-densified head
        return t, _heads(key, key[::-1], key[:42] + key[50:]), ("densify",)
    if case == "near_int64_max":
        n = 300
        top = (1 << 63) - 1
        vals = {"a": top - rng.integers(0, 4, n), "b": top - rng.integers(
            0, 1 << 40, n), "c": rng.choice([0, top, top - 1], n)}
        t = Table("t", [ColumnDef(c, 8) for c in vals], vals)
        return t, _heads(("a", "b", "c"), ("c", "a")), ()
    if case == "shared_heads":
        # eight maximal prefixes behind one shared head, cut short there
        t = _lineitem_like(2000, seed=1)
        head = ("l_shipdate", "l_orderkey")
        rest = [c.name for c in t.columns if c.name not in head]
        keys = [head + tuple(rng.permutation(rest)[:6]) for _ in range(8)]
        return t, _heads(*keys), ("cut_short", "columns_skipped")
    n = int(case[1:])
    vals = {"a": rng.integers(0, 3, n), "b": rng.integers(0, 1 << 60, n)}
    t = Table("t", [ColumnDef("a", 1), ColumnDef("b", 8)], vals)
    return t, _heads(("a", "b"), ("b",)), ()


@pytest.mark.parametrize("case", ["wide24", "distinct_lead", "low_ndv_ties",
                                  "near_int64_max", "shared_heads", "n0",
                                  "n1", "n2"])
def test_prefix_permutations_match_lexsort(case):
    """Each maximal prefix's permutation is exactly the stable
    lexicographic order np.lexsort gives; a shorter prefix reuses the
    permutation of a maximal prefix it heads, which lays its columns out
    as np.lexsort of the shorter prefix does."""
    t, needed, engaged = _perm_case(case)
    before = permute_counters()
    got = _prefix_permutations(t, needed)
    delta = {k: v - before[k] for k, v in permute_counters().items()}
    assert set(got) == set(needed)
    for p in needed:
        ref = np.lexsort([t.values[c] for c in reversed(p)])
        longer = [q for q in needed if len(q) > len(p) and q[:len(p)] == p]
        if not longer:
            np.testing.assert_array_equal(got[p], ref)
            continue
        assert any(got[p] is got[q] for q in longer)
        for c in p:
            np.testing.assert_array_equal(t.values[c][got[p]],
                                          t.values[c][ref])
    maximal = [p for p in needed
               if not any(q[:-1] == p for q in needed)]
    assert delta["prefixes"] == len(maximal)
    for k in engaged:
        assert delta[k] > 0, (k, delta)


def test_batched_24col_covering_key_equals_scalar():
    """A 24-column covering key on a lineitem-like table, multi-page, is
    byte-identical to the scalar sample_cf under every order-dependent
    method, and the fold re-densifies and stops early on it."""
    t = _lineitem_like(3000)
    cols = LINEITEM_COVER
    assert len(cols) == 24 and rows_per_page(sum(
        t.col_by_name[c].width for c in cols)) < t.nrows
    methods = ("LDICT", "PREFIX", "RLE")
    mgr = SampleManager({"lineitem": t}, seed=0)
    eng = EstimationEngine({"lineitem": t}, mgr)
    keys = [NodeKey("lineitem", cols, m) for m in methods]
    got = eng.estimate_batch(keys, 1.0)
    before = permute_counters()
    direct = batched_sample_cf(t, t, [(cols, m) for m in methods], 1.0)
    delta = {k: v - before[k] for k, v in permute_counters().items()}
    for key, est in zip(keys, direct):
        ref = sample_cf(mgr, IndexDef("lineitem", cols, key.method), 1.0)
        assert got[key].est_bytes == ref.est_bytes, key.method
        assert got[key].cf == ref.cf
        ref_t = sample_cf(mgr, IndexDef("lineitem", cols, key.method), 1.0,
                          sample_table=t)
        assert est.est_bytes == ref_t.est_bytes, key.method
        assert est.cf == ref_t.cf
    assert delta["densify"] > 0 and delta["cut_short"] > 0 \
        and delta["columns_skipped"] > 0, delta
    st = eng.stats()
    assert st["permute_prefixes"] == 1
    assert st["permute_densify"] > 0 and st["permute_cut_short"] > 0 \
        and st["permute_columns_skipped"] > 0, st
