"""The reduction from a profiler trace to busy time, idle gaps and the
breakdown: on known intervals, and on a small trace recorded on a TPU v5e
(``data/tpu_tiny.xplane.pb``: one window of the ``power`` stream at
SF 0.002)."""

from pathlib import Path

import pytest

import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "tpu_tiny.xplane.pb"
STREAM = ["q14", "q6", "q4", "q1", "q19", "q12"]


def test_union_merges_overlapping_intervals():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def known():
    spans = [tr.Span("query", "q6", 0.0, 10.0), tr.Span("call", "q6", 1.0, 9.0),
             tr.Span("query", "q1", 10.0, 20.0), tr.Span("fetch", "q1", 18.0, 20.0)]
    ops = {"/device:TPU:0": [("a", 2.0, 4.0), ("b", 3.0, 6.0), ("sort", 12.0, 18.0)],
           "/device:TPU:1": [("a", 2.0, 12.0)]}
    return tr.Trace(spans, ops)


@pytest.mark.parametrize("lo,hi,seconds", [
    (0.0, 20.0, (4.0 + 6.0 + 10.0) / 2), (3.0, 13.0, (3.0 + 1.0 + 9.0) / 2),
    (6.5, 11.5, (0.0 + 5.0) / 2), (20.0, 30.0, 0.0)])
def test_busy_is_the_union_inside_the_interval_averaged_over_devices(lo, hi, seconds):
    assert known().busy(lo, hi) == pytest.approx(seconds)


def test_breakdown_names_ops_and_gaps_by_the_open_span():
    b = tr.breakdown(known())
    assert b["device_ops"][0] == ["q6/call/a", 12.0]
    assert ["q1/query/sort", 6.0] in b["device_ops"]
    assert b["idle_gaps"][0] == ["q1/query", 8.0]  # device 1, 12 s to 20 s
    assert ["q6/call", 6.0] in b["idle_gaps"]  # device 0, 6 s to 12 s
    assert ["q1/fetch", 2.0] in b["idle_gaps"]
    assert len(b["device_ops"]) <= tr.TOP and len(b["idle_gaps"]) <= tr.TOP


def test_span_index_finds_the_innermost_open_span():
    index = tr.SpanIndex(known().spans)
    assert index.at(0.5) == "q6/query"
    assert index.at(5.0) == "q6/call"
    assert index.at(9.5) == "q6/query"
    assert index.at(19.0) == "q1/fetch"
    assert index.at(25.0) == "outside"


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.is_file():
        pytest.fail(f"missing {RECORDED}")
    return tr.load(str(RECORDED))


def test_recorded_trace_has_the_benchmarks_spans(recorded):
    names = [s.query for s in recorded.queries()]
    assert names and names[:6] == STREAM and len(names) % 6 == 0
    assert {s.phase for s in recorded.spans} == {"query", "compile", "call", "fetch"}


def test_recorded_trace_has_device_ops_inside_the_window(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    lo, hi = recorded.window()
    busy = recorded.busy(lo, hi)
    assert 0 < busy < hi - lo
    # every query drives the device, inside its own span
    assert all(recorded.busy(q.start, q.end) > 0 for q in recorded.queries())
    b = tr.breakdown(recorded)
    assert b["device_ops"] and all(n.split("/")[0] in STREAM for n, _ in b["device_ops"])
    assert sum(s for _, s in b["idle_gaps"]) <= (hi - lo) - busy + 1e-9
