"""The trace reduction, on a trace recorded on one v5e
(tests/data/small.xplane.pb, made by record_trace.py: two harness-style
sessions of four small jitted programs each) and on hand-made intervals."""

import os

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def test_recorded_trace_planes_and_spans():
    planes, spans = devtrace.read(DATA)
    assert list(planes) == ["/device:TPU:0"]
    ops = planes["/device:TPU:0"]
    assert len(ops) == 24
    assert {name for name, _, _ in ops} == {
        "%fusion", "%copy-start", "%copy-done"}
    assert [name for name, _, _ in spans] == [
        "bench.session", "bench.open", "bench.actions", "bench.close"] * 2


def test_recorded_trace_busy_and_breakdown():
    planes, spans = devtrace.read(DATA)
    per = devtrace.session_busy(planes, spans)
    assert per == [(2863040, 47425), (2416810, 23715)]
    lo, hi = devtrace.traced_window(spans)
    assert (lo, hi) == (47368985, 52650755)
    busy = devtrace.window_busy(planes, lo, hi)
    assert busy == 7.114e-05
    bd = devtrace.breakdown(planes, spans, lo, hi)
    assert bd["device_ops"][0] == ["%fusion", 7.1044e-05]
    assert len(bd["idle_gaps"]) == 10
    assert bd["idle_gaps"][0] == ["actions", 0.00166777]
    assert {name for name, _ in bd["idle_gaps"]} == {"open", "actions"}
    # the gaps and the busy time tile the window
    gaps = devtrace.breakdown(planes, spans, lo, hi, top=10 ** 6)["idle_gaps"]
    assert abs(sum(s for _, s in gaps) + busy - (hi - lo) / 1e9) < 1e-9


def test_union_of_overlapping_intervals():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 40, 45)]
    merged = devtrace.merge(ops)
    assert merged == [(0, 20), (30, 45)]
    assert devtrace.busy_ns(merged, 10, 35) == 15


def test_no_device_plane_gives_no_numbers():
    spans = [("bench.session", 0, 100)]
    assert devtrace.session_busy({}, spans) is None
    assert devtrace.window_busy({}, 0, 100) is None
    assert devtrace.breakdown({}, spans, 0, 100) is None


def test_op_name_keeps_the_instruction_name():
    assert devtrace.op_name(
        "%while.151 = (pred[65536]{0}, s32[]) while(%tuple), body=%b") \
        == "%while.151"
    assert devtrace.op_name("jit_f") == "jit_f"


def test_idle_device_plane_reads_zero_busy():
    """A device plane on which nothing ran in a session is kept: the
    session reads 0 busy (100% idle), not nothing."""
    planes = {"/device:TPU:0": [("%fusion", 500, 600)]}
    spans = [("bench.probe", 400, 700), ("bench.session", 1000, 2000),
             ("bench.open", 1000, 1500)]
    assert devtrace.session_busy(planes, spans) == [(1000, 0)]
    lo, hi = devtrace.traced_window(spans)
    assert (lo, hi) == (400, 2000)
    assert devtrace.window_busy(planes, lo, hi) == 100 / 1e9
    empty = {"/device:TPU:0": []}
    assert devtrace.session_busy(empty, spans) == [(1000, 0)]
    assert devtrace.window_busy(empty, lo, hi) == 0.0
