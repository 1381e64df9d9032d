"""The trace reduction: busy time as a union of op intervals, idle gaps
named by host spans, exposed collectives, and a trace recorded on a v5e
chip read back."""
from pathlib import Path

import pytest

from benchmarks.chip import trace

PROBE = Path(trace.__file__).parent / "testdata" / "probe.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert merged.tolist() == [[0, 3], [5, 9], [10, 11]]
    assert trace.length(merged) == 3 + 4 + 1
    assert trace.gaps(merged, 0, 12) == [(3, 5), (9, 10), (11, 12)]


def test_idle_share_is_one_minus_union_over_window():
    # ops overlap and spill out of the window [10, 30]: busy is the
    # union clipped to it, 10..20, 22..28 and 29.5..30: 16.5 of 20
    tr = trace.Trace(
        ops={0: [("a", 5, 14), ("b", 12, 20), ("c", 13, 18),
                 ("d", 22, 28), ("e", 29.5, 40)]},
        spans=[("window", 10, 30)])
    s = trace.summarize(tr, "window", [0])
    assert s["window_s"] == pytest.approx(20e-9)
    assert s["busy_s"] == pytest.approx(16.5e-9)


def test_exposed_collective_counts_only_time_without_other_ops():
    tr = trace.Trace(
        ops={0: [("f", 0, 10), ("all-reduce.1 all-reduce f32[8]", 5, 15),
                 ("g", 12, 13)],
             1: [("all-gather.2 all-gather f32[8]", 0, 4)]},
        spans=[("window", 0, 20)])
    s = trace.summarize(tr, "window", [0, 1])
    # device 0: 10..12 and 13..15 = 4; device 1: 0..4 = 4
    assert s["exposed_collective_s"] == pytest.approx(4e-9)


def test_gaps_are_named_by_the_innermost_host_span():
    tr = trace.Trace(
        ops={0: [("f", 0, 10), ("g", 20, 30), ("h", 32, 40)]},
        spans=[("window", 0, 40), ("stage_epoch", 9, 25),
               ("dispatch_epoch", 12, 14)])
    s = trace.summarize(tr, "window", [0])
    assert s["idle_gaps"] == [["stage_epoch", pytest.approx(10e-9)],
                              ["window", pytest.approx(2e-9)]]


def test_container_ops_are_not_busy_time():
    text = ("%while.7 = (s32[], f32[256]{0}) while((s32[], f32[256]{0}) "
            "%tuple), condition=%cond, body=%body")
    assert trace.op_kind(text) == "while"
    assert trace.op_name("%fusion.3 = f32[8,128]{1,0:T(8,128)} "
                         "fusion(f32[8]{0} %p), kind=kLoop") == \
        "fusion.3 fusion f32[8,128]"


def test_recorded_chip_trace():
    """Three rounds of (dispatch, stage, sort) recorded on one v5e chip:
    the device ops, the benchmark's spans, and gaps named by them."""
    tr = trace.load(str(PROBE), ["window", "dispatch_epoch", "stage_epoch"])
    assert sorted(tr.ops) == [0]
    assert [n for n, _, _ in tr.spans].count("stage_epoch") == 3
    s = trace.summarize(tr, "window", [0])
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"][0][0].startswith("sort.6 sort")
    assert "stage_epoch" in [n for n, _ in s["idle_gaps"]]
    assert s["exposed_collective_s"] == 0
