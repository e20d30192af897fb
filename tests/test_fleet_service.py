"""Fleet advisor service: multi-tenant continuous batching invariants.

The load-bearing assertion is exact parity: whatever the interleaving of
tenant deltas and recommends through the shared slots, every tenant's
recommendation equals — config, cost, used_bytes — a fresh
`DesignAdvisor` on that tenant's current workload.  The rest pins the
amortization machinery (schema-fingerprint grouping, shared SampleCF
cache, cross-tenant prefetch) and the isolation surface (admission
control, per-tenant budgets, failure containment).

Kept free of hypothesis/zstandard imports so the fleet regressions run
in every environment.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (AdvisorOptions, DesignAdvisor, FaultError,
                        FaultInjector, FaultSpec, WorkloadDelta,
                        make_scaled_workload, make_tpch_like)
from repro.core.samplecf import schema_fingerprint
from repro.serve.advisor_service import (AdvisorFleetService, DrainStalled,
                                         FleetConfig, TenantBudget,
                                         TenantBudgetExceeded,
                                         TenantQuarantined, TicketTimeout)
from repro.serve.engine import QueueFull

BUDGET = 2_000_000


def tenant_workload(schema, tid: str, n: int = 14, seed: int = 0):
    """A per-tenant workload with tenant-prefixed statement names."""
    wl = make_scaled_workload(schema, n_statements=n, seed=seed)
    return dataclasses.replace(
        wl, statements=[dataclasses.replace(s, name=f"{tid}_{s.name}")
                        for s in wl.statements])


def identical(a, b) -> bool:
    return (a.config == b.config and a.cost == b.cost
            and a.used_bytes == b.used_bytes)


@pytest.fixture(scope="module")
def schema():
    return make_tpch_like(scale=0.1, seed=0)


def make_fleet(schema, n_tenants, opt=None, fc=None):
    fleet = AdvisorFleetService(fc or FleetConfig(slots=3))
    wls = {}
    for i in range(n_tenants):
        tid = f"t{i}"
        wls[tid] = tenant_workload(schema, tid, seed=50 + i)
        fleet.register_tenant(tid, wls[tid], opt or AdvisorOptions.dtac())
    return fleet, wls


class TestFleetParity:
    def test_batched_recommends_match_fresh_advisor(self, schema):
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 5, opt)
        tickets = {tid: fleet.submit_recommend(tid, BUDGET) for tid in wls}
        fleet.run_until_drained()
        for tid, tk in tickets.items():
            fresh = DesignAdvisor(wls[tid], opt).recommend(BUDGET)
            assert identical(tk.result(), fresh), tid
        assert fleet.stats["groups"] == 1  # same schema: one share group

    def test_interleaved_delta_storm_parity(self, schema):
        """THE fleet contract: exact per-tenant parity under interleaved
        per-tenant deltas and recommends sharing slots and caches."""
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 4, opt)
        rng = np.random.default_rng(3)
        for rnd in range(3):
            tks = {}
            for i, tid in enumerate(list(wls)):
                wl = wls[tid]
                names = [s.name for s in wl.statements]
                removed = tuple(rng.choice(names, size=2, replace=False))
                pool = make_scaled_workload(
                    schema, n_statements=2,
                    seed=900 + rnd * 10 + i).statements
                added = tuple(
                    dataclasses.replace(s, name=f"{tid}_r{rnd}_{j}")
                    for j, s in enumerate(pool))
                rw = tuple((n, float(rng.uniform(0.5, 2.0)))
                           for n in rng.choice(
                               [n for n in names if n not in removed],
                               size=3, replace=False))
                delta = WorkloadDelta(added=added, removed=removed,
                                      reweighted=rw)
                fleet.submit_delta(tid, delta)
                wls[tid] = wl.apply_delta(delta)
                tks[tid] = fleet.submit_recommend(tid, BUDGET)
            fleet.run_until_drained()
            for tid, tk in tks.items():
                fresh = DesignAdvisor(wls[tid], opt).recommend(BUDGET)
                assert identical(tk.result(), fresh), (rnd, tid)

    def test_per_tenant_fifo(self, schema):
        """A tenant's requests execute in its submission order: a
        recommend submitted after a delta sees the post-delta workload
        even though both were queued before the loop ran."""
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 1, opt)
        wl = wls["t0"]
        delta = WorkloadDelta(
            removed=(wl.statements[0].name, wl.statements[1].name))
        fleet.submit_delta("t0", delta)
        tk = fleet.submit_recommend("t0", BUDGET)
        fleet.run_until_drained()
        fresh = DesignAdvisor(wl.apply_delta(delta), opt).recommend(BUDGET)
        assert identical(tk.result(), fresh)


class TestSharing:
    def test_fingerprint_grouping(self, schema):
        """Tenants group by schema CONTENT + seed, not by object
        identity; different content lands in different groups."""
        other = make_tpch_like(scale=0.1, seed=1)
        assert schema_fingerprint(schema, 0) == \
            schema_fingerprint(make_tpch_like(scale=0.1, seed=0), 0)
        assert schema_fingerprint(schema, 0) != schema_fingerprint(other, 0)
        assert schema_fingerprint(schema, 0) != schema_fingerprint(schema, 1)

        opt = AdvisorOptions.dtac()
        fleet = AdvisorFleetService(FleetConfig(slots=2))
        fleet.register_tenant("a", tenant_workload(schema, "a"), opt)
        fleet.register_tenant(
            "b", tenant_workload(make_tpch_like(scale=0.1, seed=0), "b",
                                 seed=9), opt)
        fleet.register_tenant("c", tenant_workload(other, "c"), opt)
        assert fleet.stats["groups"] == 2
        assert fleet.tenants["a"].group is fleet.tenants["b"].group
        assert fleet.tenants["a"].group is not fleet.tenants["c"].group

    def test_shared_cache_amortizes_sampling(self, schema):
        """Evidence the sharing pays: co-scheduled tenants on one schema
        are served almost entirely from the cross-tenant prefetch (zero
        per-session SampleCF misses), and the group's sampling cost is
        paid once, not per tenant."""
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 4, opt,
                                fc=FleetConfig(slots=4))
        for tid in wls:
            fleet.submit_recommend(tid, BUDGET)
        fleet.run_until_drained()
        s = fleet.stats
        assert s["groups"] == 1
        assert s["prefetch_targets"] > 0
        for tid in wls:
            ts = fleet.tenant_stats(tid)
            # every sampled estimate came from the shared prefetched cache
            assert ts["samplecf_cache_misses"] == 0
        # one SampleManager: the shared fleet draws strictly fewer
        # samples than the same tenants run in isolated fleets (tenants'
        # plans may pick different fractions f, so the shared count is
        # bounded by distinct (table, f) pairs, not by one tenant's)
        separate = 0
        for tid, wl in wls.items():
            solo = AdvisorFleetService(FleetConfig(slots=1))
            solo.register_tenant(tid, wl, opt)
            solo.submit_recommend(tid, BUDGET)
            solo.run_until_drained()
            separate += solo.stats["sampling_calls"]
        assert s["sampling_calls"] < separate

    def test_prefetch_off_still_exact(self, schema):
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 2, opt,
                                fc=FleetConfig(slots=2, prefetch=False))
        tks = {tid: fleet.submit_recommend(tid, BUDGET) for tid in wls}
        fleet.run_until_drained()
        for tid, tk in tks.items():
            fresh = DesignAdvisor(wls[tid], AdvisorOptions.dtac()
                                  ).recommend(BUDGET)
            assert identical(tk.result(), fresh)


class TestIsolation:
    def test_queue_admission_control(self, schema):
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 2, opt,
                                fc=FleetConfig(slots=1, max_queue=2))
        fleet.submit_recommend("t0", BUDGET)
        fleet.submit_recommend("t1", BUDGET)
        with pytest.raises(QueueFull):
            fleet.submit_recommend("t0", BUDGET)
        fleet.run_until_drained()
        fleet.submit_recommend("t0", BUDGET)  # capacity freed

    def test_per_tenant_pending_cap(self, schema):
        opt = AdvisorOptions.dtac()
        fleet = AdvisorFleetService(FleetConfig(slots=1))
        fleet.register_tenant("a", tenant_workload(schema, "a"), opt,
                              TenantBudget(max_pending=1))
        fleet.register_tenant("b", tenant_workload(schema, "b", seed=9),
                              opt)
        fleet.submit_recommend("a", BUDGET)
        with pytest.raises(QueueFull):
            fleet.submit_recommend("a", BUDGET)
        fleet.submit_recommend("b", BUDGET)  # other tenants unaffected
        fleet.run_until_drained()

    def test_statement_budget_enforced_before_apply(self, schema):
        opt = AdvisorOptions.dtac()
        fleet = AdvisorFleetService(FleetConfig(slots=1))
        wl = tenant_workload(schema, "a")
        fleet.register_tenant("a", wl, opt,
                              TenantBudget(max_statements=len(
                                  wl.statements) + 1))
        added = tuple(
            dataclasses.replace(s, name=f"a_x{j}") for j, s in enumerate(
                make_scaled_workload(schema, n_statements=3,
                                     seed=7).statements))
        tk = fleet.submit_delta("a", WorkloadDelta(added=added))
        fleet.run_until_drained()
        assert isinstance(tk.exception(), TenantBudgetExceeded)
        # the violating delta never touched the session
        assert len(fleet.tenants["a"].session.workload.statements) == \
            len(wl.statements)
        tk2 = fleet.submit_recommend("a", BUDGET)
        fleet.run_until_drained()
        fresh = DesignAdvisor(wl, opt).recommend(BUDGET)
        assert identical(tk2.result(), fresh)

    def test_failed_delta_isolated_to_tenant(self, schema):
        """An invalid delta resolves ONE ticket with the error; the
        tenant's workload is unchanged and co-batched tenants are
        untouched."""
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 2, opt,
                                fc=FleetConfig(slots=2))
        bad = fleet.submit_delta(
            "t0", WorkloadDelta(removed=("no_such_statement",)))
        ok = fleet.submit_recommend("t1", BUDGET)
        fleet.run_until_drained()
        assert isinstance(bad.exception(), KeyError)
        fresh = DesignAdvisor(wls["t1"], opt).recommend(BUDGET)
        assert identical(ok.result(), fresh)
        tk = fleet.submit_recommend("t0", BUDGET)
        fleet.run_until_drained()
        fresh0 = DesignAdvisor(wls["t0"], opt).recommend(BUDGET)
        assert identical(tk.result(), fresh0)

    def test_duplicate_tenant_rejected(self, schema):
        fleet = AdvisorFleetService(FleetConfig(slots=1))
        fleet.register_tenant("a", tenant_workload(schema, "a"))
        with pytest.raises(ValueError):
            fleet.register_tenant("a", tenant_workload(schema, "a"))


class TestDurability:
    """Deadlines, retries, quarantine/restore, bounded caches — the
    parity contract through the failure surface."""

    def test_transient_fault_retried_to_success(self, schema):
        """A delta failing with a transient FaultError is requeued with
        step backoff and retried bit-exactly."""
        opt = AdvisorOptions.dtac()
        inj = FaultInjector(specs={"apply_delta": FaultSpec(at=(0,))})
        fleet = AdvisorFleetService(FleetConfig(slots=2), faults=inj)
        wl = tenant_workload(schema, "t0", seed=50)
        fleet.register_tenant("t0", wl, opt)
        delta = WorkloadDelta(removed=(wl.statements[0].name,))
        tk = fleet.submit_delta("t0", delta)
        rk = fleet.submit_recommend("t0", BUDGET)
        fleet.run_until_drained()
        assert tk.result()["applied"] is True
        assert tk.attempts == 2                  # one fault, one success
        assert fleet.stats["retries"] == 1
        assert fleet.stats["failures"] == 0
        fresh = DesignAdvisor(wl.apply_delta(delta), opt).recommend(BUDGET)
        assert identical(rk.result(), fresh)

    def test_retry_exhaustion_quarantines_then_restore(self, schema):
        """A persistent fault exhausts the bounded retries, trips the
        circuit breaker, flushes the tenant's queue with
        TenantQuarantined and rejects submits; checkpoint readmission
        brings the tenant back `==` a fresh advisor."""
        opt = AdvisorOptions.dtac()
        inj = FaultInjector(specs={"apply_delta": 1.0})  # always fires
        fc = FleetConfig(slots=1, retry_backoff=(1, 2),
                         quarantine_after=1)
        fleet = AdvisorFleetService(fc, faults=inj)
        wl = tenant_workload(schema, "t0", seed=50)
        fleet.register_tenant("t0", wl, opt)
        tk = fleet.submit_delta(
            "t0", WorkloadDelta(removed=(wl.statements[0].name,)))
        queued = fleet.submit_recommend("t0", BUDGET)
        fleet.run_until_drained()
        assert isinstance(tk.exception(), FaultError)
        assert tk.attempts == 3                 # 1 + len(retry_backoff)
        assert isinstance(queued.exception(), TenantQuarantined)
        s = fleet.stats
        assert s["quarantines"] == 1 and s["quarantined_tenants"] == 1
        with pytest.raises(TenantQuarantined):
            fleet.submit_recommend("t0", BUDGET)
        fleet.readmit_tenant("t0")
        assert fleet.stats["restores"] == 1
        rk = fleet.submit_recommend("t0", BUDGET)
        fleet.run_until_drained()
        # the faulted delta never applied: parity vs the ORIGINAL workload
        fresh = DesignAdvisor(wl, opt).recommend(BUDGET)
        assert identical(rk.result(), fresh)

    def test_crash_then_auto_readmit_parity(self, schema):
        """crash_tenant drops the session; the quarantine_steps cooldown
        restores it from the post-delta checkpoint, so the recovered
        tenant recommends against its CURRENT workload."""
        opt = AdvisorOptions.dtac()
        fc = FleetConfig(slots=2, quarantine_steps=2)
        fleet = AdvisorFleetService(fc)
        wl = tenant_workload(schema, "t0", seed=50)
        fleet.register_tenant("t0", wl, opt)
        delta = WorkloadDelta(removed=(wl.statements[0].name,
                                       wl.statements[1].name))
        fleet.submit_delta("t0", delta)
        fleet.run_until_drained()
        wl = wl.apply_delta(delta)
        fleet.crash_tenant("t0")
        assert fleet.tenants["t0"].session is None
        for _ in range(10):                     # idle ticks drive cooldown
            if fleet.tenants["t0"].quarantined_at is None:
                break
            fleet.step()
        assert fleet.tenants["t0"].quarantined_at is None
        ts = fleet.tenant_stats("t0")
        assert ts["restores"] == 1 and ts["n_statements"] == \
            len(wl.statements)
        rk = fleet.submit_recommend("t0", BUDGET)
        fleet.run_until_drained()
        assert identical(rk.result(),
                         DesignAdvisor(wl, opt).recommend(BUDGET))
        assert len(fleet.restore_seconds) == 1

    def test_deadline_expires_queued_request(self, schema):
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 1, opt, fc=FleetConfig(slots=1))
        first = fleet.submit_recommend("t0", BUDGET)
        late = fleet.submit_recommend("t0", BUDGET, deadline_steps=1)
        fleet.run_until_drained()
        assert identical(first.result(),
                         DesignAdvisor(wls["t0"], opt).recommend(BUDGET))
        with pytest.raises(TicketTimeout, match="t0.*deadline"):
            late.result()
        assert fleet.stats["timeouts"] == 1

    def test_deadline_pressure_degrades_recommend(self, schema):
        """With degraded_budget set, an expiring recommend is served NOW
        at the smaller workload-compression budget — exact for that
        budget, certificate attached — instead of failing."""
        opt = AdvisorOptions.dtac()
        fc = FleetConfig(slots=1, degraded_budget=6)
        fleet = AdvisorFleetService(fc)
        wl0 = tenant_workload(schema, "t0", seed=50)
        wl1 = tenant_workload(schema, "t1", seed=51)
        fleet.register_tenant("t0", wl0, opt)
        fleet.register_tenant("t1", wl1, opt)
        fleet.submit_recommend("t0", BUDGET)      # occupies the one slot
        tk = fleet.submit_recommend("t1", BUDGET, deadline_steps=1)
        fleet.run_until_drained()
        assert tk.degraded is True
        assert fleet.stats["degraded_recommends"] == 1
        dopt = dataclasses.replace(opt, compression_budget=6)
        fresh = DesignAdvisor(wl1, dopt).recommend(BUDGET)
        rec = tk.result()
        assert identical(rec, fresh)
        # the certificate rides along: the degraded answer is an exact
        # advisor run on <= 6 representatives, error bound included
        assert 0 < rec.n_representatives <= 6
        assert rec.compression_error_bound >= 0.0

    def test_drain_stall_raises_with_pending_counts(self, schema):
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 1, opt, fc=FleetConfig(slots=1))
        tk = fleet.submit_recommend("t0", BUDGET)
        with pytest.raises(DrainStalled) as ei:
            fleet.run_until_drained(max_steps=0)
        assert ei.value.queued == 1
        assert ei.value.pending_by_tenant == {"t0": 1}
        fleet.run_until_drained()                 # work was NOT lost
        assert identical(tk.result(),
                         DesignAdvisor(wls["t0"], opt).recommend(BUDGET))

    def test_prefetch_failure_counted_not_fatal(self, schema):
        """A failing prefetch batch is counted, attached to the affected
        tickets, and the recommends still resolve bit-exactly (the warm-
        up is pure optimization)."""
        opt = AdvisorOptions.dtac()
        inj = FaultInjector(specs={"prefetch": 1.0})
        fleet = AdvisorFleetService(FleetConfig(slots=2), faults=inj)
        wls = {}
        for i in range(2):
            tid = f"t{i}"
            wls[tid] = tenant_workload(schema, tid, seed=50 + i)
            fleet.register_tenant(tid, wls[tid], opt)
        tks = {tid: fleet.submit_recommend(tid, BUDGET) for tid in wls}
        fleet.run_until_drained()
        s = fleet.stats
        assert s["prefetch_failures"] >= 1
        assert s["prefetch_batches"] == 0         # every batch faulted
        assert any(isinstance(tk.prefetch_error, FaultError)
                   for tk in tks.values())
        for tid, tk in tks.items():
            assert identical(tk.result(),
                             DesignAdvisor(wls[tid], opt).recommend(BUDGET))

    def test_result_default_timeout_names_tenant_and_kind(self, schema):
        """A ticket awaited while the loop is not running fails fast
        with a message saying WHOSE request is stuck, not a silent
        forever-block."""
        opt = AdvisorOptions.dtac()
        fleet, _ = make_fleet(schema, 1, opt)
        tk = fleet.submit_recommend("t0", BUDGET)
        with pytest.raises(TicketTimeout, match="'t0' recommend"):
            tk.result(timeout=0.01)
        fleet.run_until_drained()
        tk.result()                               # resolves normally now

    def test_bounded_group_cache_keeps_parity(self, schema):
        """A tight share-group LRU forces evictions across drift rounds;
        every recommendation stays `==` the fresh advisor."""
        opt = AdvisorOptions.dtac()
        fleet, wls = make_fleet(schema, 2, opt,
                                fc=FleetConfig(slots=2, cache_entries=8))
        for rnd in range(2):
            tks = {}
            for i, tid in enumerate(list(wls)):
                added = tuple(dataclasses.replace(s, name=f"{tid}_b{rnd}{j}")
                              for j, s in enumerate(make_scaled_workload(
                                  schema, n_statements=2,
                                  seed=700 + rnd * 10 + i).statements))
                delta = WorkloadDelta(added=added)
                fleet.submit_delta(tid, delta)
                wls[tid] = wls[tid].apply_delta(delta)
                tks[tid] = fleet.submit_recommend(tid, BUDGET)
            fleet.run_until_drained()
            for tid, tk in tks.items():
                fresh = DesignAdvisor(wls[tid], opt).recommend(BUDGET)
                assert identical(tk.result(), fresh), (rnd, tid)
        s = fleet.stats
        assert s["shared_cache_entries"] <= 8
        assert s["shared_cache_evictions"] > 0


class TestDurableStoreWiring:
    """The fleet x DurableStore integration surface (the store's own
    semantics and the crash-point harness live in test_durability.py):
    journal-before-apply ordering, budget metadata round-tripping, and
    store-backed fleets behaving identically to store-less ones."""

    def test_store_backed_fleet_same_answers_as_storeless(self, schema,
                                                          tmp_path):
        from repro.core import DurableStore
        opt = AdvisorOptions.dtac()
        plain, wls = make_fleet(schema, 2, opt)
        store = DurableStore(tmp_path, compact_after=2)
        durable = AdvisorFleetService(FleetConfig(slots=3), store=store)
        for tid, wl in wls.items():
            durable.register_tenant(tid, wl, opt)
        added = tuple(dataclasses.replace(s, name=f"d{j}")
                      for j, s in enumerate(make_scaled_workload(
                          schema, n_statements=2, seed=900).statements))
        results = {}
        for fleet in (plain, durable):
            fleet.submit_delta("t0", WorkloadDelta(added=added))
            tk = fleet.submit_recommend("t0", BUDGET)
            fleet.run_until_drained()
            results[fleet] = tk.result()
        assert identical(results[plain], results[durable])
        assert durable.stats["wal_appends"] == 1

    def test_budget_metadata_survives_recovery(self, schema, tmp_path):
        from repro.core import DurableStore
        store = DurableStore(tmp_path)
        fleet = AdvisorFleetService(FleetConfig(slots=1), store=store)
        wl = tenant_workload(schema, "t0", seed=50)
        budget = TenantBudget(max_statements=len(wl.statements) + 1,
                              max_pending=7)
        fleet.register_tenant("t0", wl, AdvisorOptions.dtac(),
                              budget=budget)
        store.close()
        f2 = AdvisorFleetService.recover(tmp_path)
        got = f2.tenants["t0"].budget
        assert got.max_statements == budget.max_statements
        assert got.max_pending == budget.max_pending
        # and the cap is live: the oversize delta is rejected before it
        # is ever journaled, so the next recovery replays nothing
        added = tuple(dataclasses.replace(s, name=f"x{j}")
                      for j, s in enumerate(make_scaled_workload(
                          schema, n_statements=3, seed=901).statements))
        tk = f2.submit_delta("t0", WorkloadDelta(added=added))
        f2.run_until_drained()
        assert isinstance(tk.exception(30), TenantBudgetExceeded)
        f2.store.close()
        f3 = AdvisorFleetService.recover(tmp_path)
        assert len(f3.tenants["t0"].session.workload.statements) \
            == len(wl.statements)


def drift_delta(rng, schema, tid: str, rnd: int, wl) -> WorkloadDelta:
    """Two statements replaced by fresh ad-hoc queries, three survivors
    reweighted (the fleet's drift round)."""
    names = [s.name for s in wl.statements]
    removed = tuple(rng.choice(names, size=2, replace=False))
    added = tuple(dataclasses.replace(s, name=f"{tid}_r{rnd}_{j}")
                  for j, s in enumerate(make_scaled_workload(
                      schema, n_statements=2,
                      seed=700 + 10 * rnd + int(tid[1:])).statements))
    rw = tuple((n, float(rng.uniform(0.5, 2.0))) for n in rng.choice(
        [n for n in names if n not in removed], size=3, replace=False))
    return WorkloadDelta(added=added, removed=removed, reweighted=rw)


class TestShareGroupBackend:
    """The share group's batched prefetch runs on the tenants' own
    `estimation_backend` (the group's key); the fleet has no backend
    knob of its own."""

    JAX = dataclasses.replace(AdvisorOptions.dtac(),
                              estimation_backend="jax",
                              planner_backend="jax")

    def test_group_engine_takes_the_tenants_estimation_backend(self,
                                                               schema):
        assert "backend" not in {f.name for f in
                                 dataclasses.fields(FleetConfig)}
        fleet = AdvisorFleetService(FleetConfig(slots=2))
        fleet.register_tenant("a", tenant_workload(schema, "a"), self.JAX)
        fleet.register_tenant("b", tenant_workload(schema, "b", seed=3),
                              AdvisorOptions.dtac())
        ga, gb = fleet.tenants["a"].group, fleet.tenants["b"].group
        assert ga is not gb
        assert (ga.key[1], ga.engine.backend) == ("jax", "jax")
        assert (gb.key[1], gb.engine.backend) == ("numpy", "numpy")

    def test_jax_prefetch_drift_parity(self, schema):
        """Drift rounds of several tenants on the jax backend: the
        prefetch sizes the targets through the codec kernels, and every
        resolved recommend is `==` a fresh advisor on the tenant's
        workload."""
        from repro.kernels import codec_bytes
        fleet, wls = make_fleet(schema, 3, self.JAX)
        rng = np.random.default_rng(11)
        for rnd in range(2):
            tks = {}
            for tid in wls:
                d = drift_delta(rng, schema, tid, rnd, wls[tid])
                fleet.submit_delta(tid, d)
                wls[tid] = wls[tid].apply_delta(d)
                tks[tid] = fleet.submit_recommend(tid, BUDGET)
            launches = codec_bytes.counters()["kernel_calls"]
            targets = fleet.prefetch_targets
            fleet.run_until_drained()
            assert fleet.prefetch_targets > targets
            assert codec_bytes.counters()["kernel_calls"] > launches
            for tid, tk in tks.items():
                fresh = DesignAdvisor(wls[tid], self.JAX).recommend(BUDGET)
                assert identical(tk.result(), fresh), (rnd, tid)
        assert fleet.stats["recommends"] == 6

    def test_planner_batches_in_stats(self, schema):
        """The tenants' planners' scoring batches show in the fleet's
        `stats` as the sums of the tenants' own."""
        fleet, wls = make_fleet(schema, 2)
        for tid in wls:
            fleet.submit_recommend(tid, BUDGET)
        fleet.run_until_drained()
        st = fleet.stats
        for k in ("score_launches", "scored_records", "stale_rescores"):
            assert st[k] == sum(fleet.tenant_stats(t)[k] for t in wls)
        assert 0 < st["score_launches"] < st["scored_records"]

    def test_warm_up_leaves_nothing_to_lower(self, schema):
        """`warm_up` before any request, the tenants' first recommends,
        and `warm_up` again (for any fraction those sampled at besides the
        planner's cheapest): drift rounds then lower no codec or planner
        program, and a call runs nothing an earlier one ran."""
        from repro.kernels import codec_bytes, planner_score
        fleet, wls = make_fleet(schema, 2, self.JAX)
        assert fleet.warm_up() > 0
        for tid in wls:
            fleet.submit_recommend(tid, BUDGET)
        fleet.run_until_drained()
        fleet.warm_up()
        programs = (codec_bytes._codec_call._cache_size(),
                    planner_score._fused_call._cache_size(),
                    planner_score._prob_call._cache_size())
        rng = np.random.default_rng(12)
        for rnd in range(2):
            for tid in wls:
                d = drift_delta(rng, schema, tid, rnd, wls[tid])
                fleet.submit_delta(tid, d)
                wls[tid] = wls[tid].apply_delta(d)
                fleet.submit_recommend(tid, BUDGET)
            fleet.run_until_drained()
        assert (codec_bytes._codec_call._cache_size(),
                planner_score._fused_call._cache_size(),
                planner_score._prob_call._cache_size()) == programs
        assert fleet.warm_up() == 0
