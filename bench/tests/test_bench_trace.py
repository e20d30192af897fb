"""Trace reduction: busy time, module time, top operations and idle gaps,
on a hand-made trace and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / \
    "tpu_small.xplane.pb"


def _hand_made() -> Trace:
    ops = [Event("sort.1", 0, 100), Event("kernel", 50, 100),
           Event("fusion.2", 400, 100), Event("kernel", 700, 300)]
    modules = [Event("jit__codec_call(7)", 0, 150),
               Event("jit_score(3)", 400, 100),
               Event("jit__codec_call(7)", 700, 300)]
    spans = [Event("bench.recommend_s", 0, 2000),
             Event("bench.costenum", 160, 300)]
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, spans)


def test_busy_is_the_union_of_operation_intervals():
    assert tr.busy_s(_hand_made()) == pytest.approx(550e-9)
    assert tr.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_module_time_by_name():
    assert tr.module_s(_hand_made(), r"_codec_call") == pytest.approx(450e-9)


def test_top_ops_and_idle_gaps_by_span():
    t = _hand_made()
    assert tr.top_ops(t, 2) == [["kernel", pytest.approx(400e-9)],
                                ["sort.1", pytest.approx(100e-9)]]
    # gap 150..400 has its midpoint in bench.costenum, 500..700 does not
    assert tr.idle_gaps(t) == [["bench.costenum", pytest.approx(250e-9)],
                               ["bench.recommend_s", pytest.approx(200e-9)]]


def test_no_device_reads_zero_busy():
    assert tr.busy_s(Trace({}, {}, [])) == 0.0


def test_recorded_tpu_trace():
    """Three `bench.step` spans, each running a jitted sort and a Pallas
    kernel on a TPU v5e (recorded with the harness's profiler options)."""
    t = tr.load(str(RECORDED))
    assert list(t.ops) == ["/device:TPU:0"]
    assert tr.busy_s(t) == pytest.approx(21.316e-6)
    assert tr.module_s(t, r"small_kernel") == pytest.approx(2.323e-6)
    assert tr.module_s(t, r"small_op") == pytest.approx(19.041e-6)
    assert [s.name for s in t.spans] == ["bench.step"] * 3
    assert tr.top_ops(t, 2) == [
        ["%sort.6 f32[8,1024]", pytest.approx(16.65e-6)],
        ["%small_kernel.1 f32[8,1024]", pytest.approx(2.313e-6)]]
    assert tr.idle_gaps(t) == [
        ["bench.step", pytest.approx(2.404929e-3)],
        ["outside any span", pytest.approx(0.746106e-3)]]


def test_op_label_keeps_name_and_shape():
    assert tr.op_label("%sort.8 = (s32[28160,273]{0,1:T(8,128)}, s32[28160,"
                       "273]{0,1:T(8,128)}) sort(...)") == \
        "%sort.8 s32[28160,273]"
