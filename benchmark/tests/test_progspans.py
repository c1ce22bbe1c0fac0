"""The program-span metrics (progspans.py): hand-made spans and sessions,
then each cell's traced run at a tiny scale on the CPU."""

import json

import pytest

import progspans
from conftest import run_cell

CELLS = ["cfg5.backlog", "cfg4.preempt", "cfg5.steady"]
SPAN_METRICS = {"snapshot_ms", "enqueue_ms", "backfill_ms", "device_wait_ms",
                "predicate_ms", "prioritize_ms", "mirror_flush_ms",
                "job_update_ms"}


def _session(t0, t1, t2, t3):
    return {"t0": t0, "t1": t1, "t2": t2, "t3": t3}


def test_covered_counts_nested_repeats_once():
    spans = [("vt.a", 1.0, 4.0), ("vt.a", 2.0, 3.0), ("vt.a", 3.5, 6.0),
             ("vt.b", 0.0, 10.0)]
    assert progspans.covered(spans, "vt.a", 0.0, 10.0) == 5.0
    # clipped to the window
    assert progspans.covered(spans, "vt.a", 2.5, 5.0) == 2.5
    assert progspans.covered(spans, "vt.c", 0.0, 10.0) == 0.0


def test_span_ms_is_a_mean_over_traced_sessions():
    sessions = [_session(0.0, 1.0, 3.0, 4.0), _session(10.0, 11.0, 13.0, 14.0),
                _session(20.0, 21.0, 23.0, 24.0)]
    spans = [("vt.open.snapshot", 0.1, 0.6), ("vt.device.wait", 1.5, 2.0),
             ("vt.open.snapshot", 10.1, 10.4), ("vt.device.wait", 13.2, 13.5)]
    # the third session holds no span: it was not traced
    assert progspans.traced(sessions, spans) == sessions[:2]
    assert progspans.span_ms(sessions, spans, "vt.open.snapshot") == \
        pytest.approx((0.5 + 0.3) / 2 * 1e3)
    # limited to the actions: the second session's wait lies in its close
    assert progspans.span_ms(sessions, spans, "vt.device.wait",
                             "actions") == pytest.approx(0.5 / 2 * 1e3)
    # a span that ran in one traced session only still averages over both
    assert progspans.span_ms(sessions, spans, "vt.device.wait") == \
        pytest.approx((0.5 + 0.3) / 2 * 1e3)


def test_nothing_recorded_gives_no_number():
    sessions = [_session(0.0, 1.0, 3.0, 4.0)]
    assert progspans.span_ms(sessions, None, "vt.open.snapshot") is None
    assert progspans.span_ms(sessions, [], "vt.open.snapshot") is None
    spans = [("vt.open.snapshot", 0.1, 0.6)]
    assert progspans.span_ms(sessions, spans, "vt.close.job_updater") is None
    assert progspans.span_ms(sessions, spans, "vt.open.snapshot",
                             "actions") is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_program_spans(tiny_root, capsys, cell):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"]
            if m["name"] in SPAN_METRICS and cell in m["workloads"]}
    assert want
    res = run_cell(tiny_root, cell, trace=1, capsys=capsys)
    assert res["correct"], res["checks"]
    assert want <= set(res["metrics"]), set(res["metrics"])
    for name in want:
        assert res["metrics"][name]["value"] >= 0.0, name
