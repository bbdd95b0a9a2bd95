"""The reduction from a profiler trace to device metrics, on synthetic
intervals and on a small trace recorded on a TPU v5e with
``chipbench/tools/record_trace.py``."""

import gzip
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "tail_240s_n16.xplane.pb.gz"


def plain_union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def plain_inside(intervals, lo, hi):
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e in plain_union(intervals))


def test_interval_helpers():
    s, e = trace.union(np.array([3.0, 0.0, 0.5]), np.array([4.0, 1.0, 2.0]))
    assert s.tolist() == [0.0, 3.0] and e.tolist() == [2.0, 4.0]
    assert trace.clip_total((s, e), [(1.0, 3.5), (1.5, 2.5)]) == 1.5


def test_op_text():
    text = "%while.58 = (s32[], f32[2]) while((s32[], f32[2]) %tuple.3)"
    assert trace.op_kind(text) == "while"
    assert trace.op_name(text) == "while.58"
    assert trace.op_kind("%fusion.1 = f32[12]{0} fusion(f32[12]{0} %p)") \
        == "fusion"


def test_summary_of_a_synthetic_unit():
    ev = trace.TraceEvents(
        leaf=[[(1.0, 2.0, "a"), (2.5, 3.0, "b"), (6.0, 7.0, "a")]],
        enclosing=[[(1.0, 3.0)]],
        spans=[("unit", 0.0, 10.0), ("scan", 0.5, 3.5),
               ("lower", 4.0, 5.5)])
    s = trace.summarize(ev, "unit")
    assert s.window_s == 10.0 and s.chips == 1
    assert s.busy_s == pytest.approx(2.5)
    assert s.idle_pct == pytest.approx(75.0)
    assert s.device_s["scan"] == pytest.approx(2.0)  # the loop, 1.0..3.0
    assert s.op_seconds == {"a": 2.0, "b": 0.5}
    # idle stretches split at span boundaries, each to its innermost span
    assert s.idle_by_label == pytest.approx(
        {"scan": 1.5, "lower": 1.5, "unit": 4.5})
    assert s.breakdown()["device_ops"][0] == ["a", 2.0]


@pytest.fixture(scope="module")
def recorded():
    return trace.read_xspace(gzip.decompress(DATA.read_bytes()))


def test_recorded_trace_holds_what_the_reduction_reads(recorded):
    assert len(recorded.leaf) == 1 and recorded.leaf[0]
    assert recorded.enclosing[0], "the tick loop's while op"
    assert {"ensemble", "lower", "scan", "assemble"} <= {
        label for label, _, _ in recorded.spans}


def test_reduction_of_the_recorded_trace(recorded):
    s = trace.summarize(recorded, "ensemble")
    (_, lo, hi), = [x for x in recorded.spans if x[0] == "ensemble"]
    leaf = [(a, b) for a, b, _ in recorded.leaf[0]]
    assert s.window_s == pytest.approx(hi - lo)
    assert s.busy_s == pytest.approx(plain_inside(leaf, lo, hi), rel=1e-9)
    assert 0.0 < s.busy_s < s.window_s
    scan = [(a, b) for label, a, b in recorded.spans if label == "scan"]
    allops = leaf + list(recorded.enclosing[0])
    want = sum(plain_inside(allops, a, b) for a, b in plain_union(scan))
    assert s.device_s["scan"] == pytest.approx(want, rel=1e-9)
    assert s.device_s["scan"] <= sum(b - a for a, b in scan)
    assert sum(s.idle_by_label.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert set(s.idle_by_label) <= {"ensemble", "lower", "scan", "assemble",
                                    "other"}
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
