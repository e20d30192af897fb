"""Per-layer metric readers: what each reads, and nothing where there is
nothing to read."""
from collections import namedtuple
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import run
from bench.gen import tpch
from bench.trace_reduce import Event, Trace

ROOT = Path(__file__).resolve().parents[2]


def _reader(name):
    return run.metric_reader(ROOT, name)


Key = namedtuple("Key", "table cols")


def _plan(f, nodes):
    return NS(f=f, nodes={Key(t, c): NS(state=NS(name=s))
                          for t, c, s in nodes})


def test_codec_roofline_counts_the_algorithms_bytes():
    schema = tpch.make_tpch_like(scale=1.0)          # 60,000 lineitem rows
    plan = _plan(0.01, [("lineitem", ("a", "b"), "SAMPLED"),
                        ("lineitem", ("a",), "DEDUCED")])
    trace = Trace({}, {"/device:TPU:0": [
        Event("jit__codec_call(1)", 0, 1000.0)]}, [])
    ctx = run.Context(trace=trace, schema=schema, device_kind="TPU v5 lite",
                      records=[NS(rec=NS(estimation_plan=plan))])
    # 600 sample rows x 2 columns x 4 B over 819 GB/s, in 1 us of device
    want = 100.0 * (600 * 2 * 4 / 819e9) / 1e-6
    assert _reader("codec_roofline.recommend")(ctx) == pytest.approx(want)
    ctx.trace = Trace({}, {}, [])
    assert _reader("codec_roofline.recommend")(ctx) is None


@pytest.mark.parametrize("name", ["device_idle.recommend"])
def test_device_idle(name):
    read = _reader(name)
    assert read(run.Context(window_s=10.0, busy_s=0.5)) == pytest.approx(95)
    assert read(run.Context(window_s=10.0, busy_s=0.0)) is None


def test_per_request_readers():
    ctx = run.Context(completed=4, stages={"estimate": 2.0, "costenum": 1.0},
                      codec={"kernel_calls": 40, "envelope_reroutes": 0},
                      planner={"fused_calls": 30, "prob_calls": 10})
    assert _reader("estimate_ms.recommend")(ctx) == 500.0
    assert _reader("costenum_ms.recommend")(ctx) == 250.0
    assert _reader("codec_launches.recommend")(ctx) == 10.0
    assert _reader("planner_launches.recommend")(ctx) == 10.0
    ctx.completed = 0
    assert _reader("estimate_ms.recommend")(ctx) is None
