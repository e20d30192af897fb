"""Program spans (`repro.core.tracing`): nesting and request ids, the
disabled no-op, draining, the spans of one recommend on the jax path
(Pallas in interpret mode here), and their agreement with the profiler's
own host events."""
import glob

import jax
import pytest

from repro.core import (AdvisorOptions, DesignAdvisor, make_tpch_like,
                        make_tpch_workload, tracing)

# every span a recommend on the jax path opens, as PERF.md names them
RECOMMEND_SPANS = {
    "advisor.recommend", "advisor.candidates", "advisor.estimate",
    "estimate.plan", "kernel.planner", "estimate.sample",
    "estimate.samplecf", "samplecf.permute", "kernel.codec",
    "estimate.resolve", "advisor.cost", "advisor.enumerate"}
ESTIMATE_CHILDREN = {"estimate.plan", "estimate.sample", "estimate.samplecf",
                     "estimate.resolve"}


@pytest.fixture
def recording():
    tracing.drain()
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()
        tracing.drain()


def test_spans_nest_and_share_the_request_id(recording):
    with tracing.span("loose"):
        pass
    for _ in range(2):
        with tracing.span("advisor.recommend", request=True):
            with tracing.span("advisor.estimate"):
                with tracing.span("estimate.plan"):
                    pass
            with tracing.span("advisor.cost"):
                pass
    spans = tracing.drain()
    by_id = {s.span_id: s for s in spans}
    assert [s.name for s in spans] == [
        "loose", "estimate.plan", "advisor.estimate", "advisor.cost",
        "advisor.recommend"] + ["estimate.plan", "advisor.estimate",
                                "advisor.cost", "advisor.recommend"]
    assert spans[0].parent_id is None and spans[0].request_id is None
    roots = [s for s in spans if s.name == "advisor.recommend"]
    assert len({r.request_id for r in roots}) == 2
    for r in roots:
        assert r.parent_id is None and r.request_id == r.span_id
    for s in spans:
        if s.name in ("advisor.estimate", "advisor.cost"):
            assert by_id[s.parent_id].name == "advisor.recommend"
        if s.name == "estimate.plan":
            assert by_id[s.parent_id].name == "advisor.estimate"
        if s.parent_id is not None:
            parent = by_id[s.parent_id]
            assert s.request_id == parent.request_id
            assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns


def test_disabled_tracing_records_nothing():
    tracing.drain()
    assert tracing.span("advisor.estimate") is tracing.NO_SPAN
    assert tracing.span("other", request=True) is tracing.NO_SPAN
    with tracing.span("advisor.estimate") as s:
        assert s is None
    assert tracing.drain() == []


def test_drain_clears_the_list(recording):
    with tracing.span("a"):
        pass
    assert [s.name for s in tracing.drain()] == ["a"]
    assert tracing.drain() == []
    tracing.disable()
    with tracing.span("b"):
        pass
    assert tracing.drain() == []


def test_a_span_is_recorded_when_its_body_raises(recording):
    with pytest.raises(ValueError):
        with tracing.span("raises"):
            raise ValueError("boom")
    assert [s.name for s in tracing.drain()] == ["raises"]


@tracing.traced("traced.inner")
def _inner(x):
    """Doubles x."""
    return 2 * x


@tracing.traced("traced.root", request=True)
def _root(x):
    return _inner(x) + 1


def test_traced_keeps_the_function_and_records_when_enabled(recording):
    assert _inner.__name__ == "_inner" and _inner.__doc__ == "Doubles x."
    assert _root(3) == 7
    inner, root = tracing.drain()
    assert (inner.name, root.name) == ("traced.inner", "traced.root")
    assert root.request_id == root.span_id and root.parent_id is None
    assert inner.parent_id == root.span_id
    assert inner.request_id == root.span_id


def test_traced_calls_straight_through_when_disabled():
    tracing.drain()
    assert _root(3) == 7
    assert tracing.drain() == []


def test_traced_records_a_call_that_raises(recording):
    @tracing.traced("traced.raises")
    def boom():
        raise ValueError("boom")

    with pytest.raises(ValueError):
        boom()
    assert [s.name for s in tracing.drain()] == ["traced.raises"]


def _host_events(log_dir):
    """The trace's start on the real-time clock, and its repro.* host
    events: [(name, start_ns, end_ns)] after that start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    pd = ProfileData.from_file(path)
    t0 = None
    events = []
    for plane in pd.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                t0 = value
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events
                              if e.name.startswith("repro."))
    assert t0 is not None
    return t0, sorted(events)


@pytest.fixture(scope="module")
def traced_recommend(tmp_path_factory):
    """One recommend at a size where every estimation layer runs (the
    lineitem sample spans several pages, so the prefix sorts run), after
    a first one that compiles, under the profiler."""
    schema = make_tpch_like(scale=0.5, z=0, seed=0)
    workload = make_tpch_workload(schema, insert_weight=0.1)
    options = AdvisorOptions(backend="jax")
    DesignAdvisor(workload, options).recommend(1e6)
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tracing.drain()
    tracing.enable()
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        DesignAdvisor(workload, options).recommend(1e6)
    finally:
        jax.profiler.stop_trace()
        tracing.disable()
    return tracing.drain(), _host_events(log_dir)


def test_a_recommend_records_every_span(traced_recommend):
    spans, _ = traced_recommend
    assert {s.name for s in spans} == RECOMMEND_SPANS
    (root,) = [s for s in spans if s.name == "advisor.recommend"]
    assert {s.request_id for s in spans} == {root.span_id}
    (est,) = [s for s in spans if s.name == "advisor.estimate"]
    children = [s for s in spans if s.parent_id == est.span_id]
    assert {s.name for s in children} == ESTIMATE_CHILDREN
    assert sum(s.end_ns - s.start_ns for s in children) >= \
        0.95 * (est.end_ns - est.start_ns)
    by_id = {s.span_id: s for s in spans}
    for name, parent in (("kernel.planner", "estimate.plan"),
                         ("samplecf.permute", "estimate.samplecf"),
                         ("kernel.codec", "estimate.samplecf")):
        assert {by_id[s.parent_id].name for s in spans
                if s.name == name} == {parent}


def test_spans_match_their_profiler_events(traced_recommend):
    spans, (t0, events) = traced_recommend
    recorded = sorted(("repro." + s.name, s.start_ns - t0, s.end_ns - t0)
                      for s in spans)
    assert [e[0] for e in events] == [r[0] for r in recorded]
    for (name, s0, e0), (_, s1, e1) in zip(recorded, events):
        assert abs(s1 - s0) <= 50e3, name
        assert abs(e1 - e0) <= 50e3, name


# every span a fleet step opens around its sessions' own spans
FLEET_PHASES = {"fleet.admit", "fleet.prefetch", "fleet.cost_prefetch",
                "fleet.execute"}


def _fleet_with_two_recommends():
    import dataclasses

    from repro.serve.advisor_service import AdvisorFleetService, FleetConfig
    schema = make_tpch_like(scale=0.1, z=0, seed=0)
    options = AdvisorOptions(estimation_backend="jax", planner_backend="jax")
    fleet = AdvisorFleetService(FleetConfig(slots=2))
    for tid, iw in (("t0", 0.1), ("t1", 20.0)):
        wl = make_tpch_workload(schema, insert_weight=iw)
        wl = dataclasses.replace(wl, statements=[
            dataclasses.replace(s, name=f"{tid}_{s.name}")
            for s in wl.statements])
        fleet.register_tenant(tid, wl, options)
        fleet.submit_recommend(tid, 1e6)
    return fleet


def test_a_fleet_step_nests_its_phases_and_the_sessions_spans(recording):
    fleet = _fleet_with_two_recommends()
    fleet.step()
    spans = tracing.drain()
    by_id = {s.span_id: s for s in spans}
    (root,) = [s for s in spans if s.name == "fleet.step"]
    assert root.parent_id is None and root.request_id == root.span_id
    assert {s.request_id for s in spans} == {root.span_id}
    phases = [s for s in spans if s.parent_id == root.span_id]
    assert {s.name for s in phases} == FLEET_PHASES
    assert sum(s.name == "fleet.execute" for s in phases) == 2
    for name in ("fleet.admit", "fleet.prefetch", "fleet.cost_prefetch"):
        assert sum(s.name == name for s in phases) == 1

    def phase_of(s):
        while s.parent_id != root.span_id:
            s = by_id[s.parent_id]
        return s.name

    inner = [s for s in spans if s.name not in FLEET_PHASES | {"fleet.step"}]
    assert {phase_of(s) for s in inner} <= {"fleet.prefetch",
                                             "fleet.cost_prefetch",
                                             "fleet.execute"}
    # the prefetch plans the recommends and sizes their targets through
    # the codec kernels; the slots then find every target in the cache
    assert {phase_of(s) for s in inner if s.name == "kernel.codec"} \
        == {"fleet.prefetch"}
    assert "fleet.prefetch" in {phase_of(s) for s in inner
                                if s.name == "estimate.plan"}
    for s in spans:
        if s.parent_id is not None:
            parent = by_id[s.parent_id]
            assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns


def test_a_fleet_step_records_nothing_with_tracing_off():
    fleet = _fleet_with_two_recommends()
    tracing.drain()
    fleet.step()
    assert fleet.stats["recommends"] == 2
    assert tracing.drain() == []
