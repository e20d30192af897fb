"""The program's spans in a traced window: the readers that take them and
the transfer counters, self time, idle gaps by the innermost span (one
sweep, the same result as the scan), the clock check, and the whole
measurement rehearsed on the CPU."""
from collections import namedtuple
from pathlib import Path

import numpy as np
import pytest

from bench import program_spans as ps
from bench import run
from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace

ROOT = Path(__file__).resolve().parents[2]
RECORDED = ROOT / "bench" / "testdata" / "tpu_small.xplane.pb"

Span = namedtuple("Span", "name start_ns end_ns span_id parent_id "
                          "request_id")


def _reader(name):
    return run.metric_reader(ROOT, name)


def _window():
    """Two recommends, in ns: the second plans with one kernel call and
    runs SampleCF with a prefix sort and two codec calls."""
    return [
        Span("advisor.recommend", 0, 1000, 1, None, 1),
        Span("advisor.estimate", 100, 900, 2, 1, 1),
        Span("estimate.plan", 100, 400, 3, 2, 1),
        Span("advisor.recommend", 2000, 4000, 4, None, 4),
        Span("advisor.estimate", 2000, 3800, 5, 4, 4),
        Span("estimate.plan", 2000, 2600, 6, 5, 4),
        Span("kernel.planner", 2100, 2300, 7, 6, 4),
        Span("estimate.sample", 2600, 2700, 8, 5, 4),
        Span("estimate.samplecf", 2700, 3700, 9, 5, 4),
        Span("samplecf.permute", 2700, 3000, 10, 9, 4),
        Span("kernel.codec", 3100, 3300, 11, 9, 4),
        Span("kernel.codec", 3250, 3500, 12, 9, 4),
        Span("estimate.resolve", 3700, 3800, 13, 5, 4),
    ]


@pytest.mark.parametrize("name,ms", [
    ("plan_ms.recommend", (300 + 600) / 2e6),
    ("planner_call_ms.recommend", 200 / 2e6),
    ("sample_ms.recommend", 100 / 2e6),
    ("permute_ms.recommend", 300 / 2e6),
    ("codec_call_ms.recommend", (200 + 250) / 2e6),
    # 1000 ns less the sort (300) and the union of the codec calls (400)
    ("samplecf_self_ms.recommend", 300 / 2e6)])
def test_span_readers(name, ms):
    read = _reader(name)
    assert read(run.Context(completed=2, spans=_window())) == \
        pytest.approx(ms)
    assert read(run.Context(completed=0, spans=_window())) is None
    # a program without spans, or a window that recorded none
    assert read(run.Context(completed=2)) is None
    assert read(run.Context(completed=2, spans=[])) is None


def test_a_span_reader_reads_nothing_of_a_name_never_opened():
    spans = [s for s in _window() if s.name != "samplecf.permute"]
    ctx = run.Context(completed=2, spans=spans)
    assert _reader("permute_ms.recommend")(ctx) is None
    assert _reader("plan_ms.recommend")(ctx) == pytest.approx(9e-4 / 2)


@pytest.mark.parametrize("name,mb", [("h2d_mb.recommend", 8.0),
                                     ("d2h_mb.recommend", 0.25)])
def test_transfer_reader(name, mb):
    read = _reader(name)
    ctx = run.Context(completed=4,
                      codec={"kernel_calls": 40, "h2d_bytes": 30_000_000,
                             "d2h_bytes": 600_000},
                      planner={"fused_calls": 30, "h2d_bytes": 2_000_000,
                               "d2h_bytes": 400_000})
    assert read(ctx) == pytest.approx(mb)
    ctx.completed = 0
    assert read(ctx) is None
    # the counters of a program that does not count transfers
    old = run.Context(completed=4, codec={"kernel_calls": 40},
                      planner={"fused_calls": 30})
    assert read(old) is None


def test_self_time_is_duration_less_the_union_of_children():
    total, own = ps.totals_ns(_window()), ps.self_ns(_window())
    assert total["advisor.estimate"] == 800 + 1800
    # 800 - 300 (plan); 1800 - (600 + 100 + 1000 + 100)
    assert own["advisor.estimate"] == 500 + 0
    assert own["estimate.samplecf"] == 1000 - 300 - 400
    assert own["estimate.plan"] == 300 + 400
    assert own["kernel.codec"] == total["kernel.codec"] == 450
    assert own["advisor.recommend"] == 200 + 200


def _nested() -> Trace:
    """Device gaps inside program spans nested in harness spans."""
    ops = [Event("a", 0, 100), Event("b", 300, 100), Event("c", 600, 100),
           Event("d", 900, 100), Event("e", 1500, 100)]
    spans = [Event("bench.recommend_s", 0, 1400),
             Event("bench.estimate", 50, 1000),
             Event("repro.advisor.estimate", 60, 980),
             Event("repro.estimate.plan", 150, 300),
             Event("repro.kernel.planner", 160, 20),
             Event("repro.samplecf.permute", 700, 250)]
    return Trace({"/device:TPU:0": ops}, {}, spans)


def test_idle_gaps_name_the_innermost_program_span():
    t = _nested()
    # gaps 100..300 (mid 200), 400..600 (500), 700..900 (800),
    # 1000..1500 (1250)
    want = [["bench.recommend_s", pytest.approx(500e-9)],
            ["repro.estimate.plan", pytest.approx(200e-9)],
            ["repro.advisor.estimate", pytest.approx(200e-9)],
            ["repro.samplecf.permute", pytest.approx(200e-9)]]
    assert ps.idle_gaps(t) == want
    assert tr.idle_gaps(t) == want


def _random_trace(seed: int) -> Trace:
    rng = np.random.default_rng(seed)

    def events(n, span, prefix, planes=1):
        starts = rng.integers(0, 10_000, size=(planes, n))
        durs = rng.integers(1, span, size=(planes, n))
        return [[Event(f"{prefix}{i % 7}", float(s), float(d))
                 for i, (s, d) in enumerate(zip(ps_, ds))]
                for ps_, ds in zip(starts, durs)]

    ops = events(300, 40, "op", planes=2)
    (spans,) = events(120, 3000, "repro.s")
    # equal durations and shared starts, to pin the order of ties
    spans += [Event("repro.tie", 500.0, 400.0), Event("repro.tie2", 500.0,
                                                      400.0)]
    return Trace({"/device:TPU:0": ops[0], "/device:TPU:1": ops[1]}, {},
                 spans)


@pytest.mark.parametrize("seed", range(6))
def test_idle_gaps_sweep_equals_the_scan(seed):
    t = _random_trace(seed)
    assert ps.idle_gaps(t, n=1000) == tr.idle_gaps(t, n=1000)


def test_idle_gaps_sweep_on_the_recorded_trace():
    t = tr.load(str(RECORDED))
    assert ps.idle_gaps(t) == tr.idle_gaps(t)
    start, events = ps.program_events(str(RECORDED))
    assert start > 0 and events == []


def test_clock_offsets():
    spans = [Span("a", 1_000_000, 1_005_000, 1, None, None),
             Span("b", 1_001_000, 1_002_000, 2, 1, None)]
    events = [Event("repro.b", 1_003.0, 1_000.0),
              Event("repro.a", 0.0, 5_007.0)]
    got = ps.clock_offsets_us(spans, 1_000_000, events)
    assert got == {"spans": 2, "start_p50_us": pytest.approx(0.003),
                   "start_p99_us": pytest.approx(0.003),
                   "start_max_us": pytest.approx(0.003),
                   "end_p50_us": pytest.approx(0.007),
                   "end_p99_us": pytest.approx(0.007),
                   "end_max_us": pytest.approx(0.007), "over_10us": 0,
                   "far": []}
    late = [Event("repro.b", 1_003.0, 12_000.0), events[1]]
    got = ps.clock_offsets_us(spans, 1_000_000, late)
    assert got["over_10us"] == 1
    assert got["far"] == [["repro.b", "end", pytest.approx(11.003), 1]]
    with pytest.raises(ValueError):
        ps.clock_offsets_us(spans, 1_000_000, events[:1])


def test_measure_rehearsed_on_the_cpu():
    """The whole measurement at a tiny scale, Pallas in interpret mode:
    every span reader reads, the estimation stage is covered and agrees
    with the harness's stage timer.  Times here say nothing of a chip."""
    r = ps.measure(ROOT, "sf1.recommend", seed=2 ** 31 + 17, seconds=1.0,
                   require_tpu=False, overrides={"scale": 0.2})
    assert r["correct"] is True and r["completed"] >= 1
    for name in ("plan_ms", "planner_call_ms", "sample_ms", "permute_ms",
                 "codec_call_ms", "samplecf_self_ms", "h2d_mb", "d2h_mb",
                 "estimate_ms", "planner_launches"):
        assert r["metrics"][f"{name}.recommend"] > 0, name
    assert r["metrics"]["planner_launches.recommend"] == 141
    assert r["estimate_covered"] >= 0.95
    assert r["estimate_over_stage_timer"] == pytest.approx(1, abs=0.02)
    assert r["clock"]["spans"] == sum(
        v["count"] for v in r["span_ms_per_recommend"].values())
