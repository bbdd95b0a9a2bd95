"""The program's own spans and counters against a profiler trace
(``chipbench.program_trace``): their reduction against the device ops, on
synthetic intervals and on small traces recorded on a TPU v5e; the means
over recorder snapshots; and ``tools/program_split.py`` on the host CPU."""

import dataclasses
import gzip
import json
from pathlib import Path

import pytest

from chipbench import harness, trace
from chipbench import program_trace as pt
from repro.obs.metrics import MetricsSnapshot, SpanStats

DATA = Path(__file__).parent / "data"
BENCH = Path(__file__).resolve().parents[1]
SPLIT = harness.load_module(BENCH / "tools" / "program_split.py")
BATCHED = ("operands", "h2d", "run", "d2h", "unpack")


def test_program_split_of_a_synthetic_unit():
    """A ``batched/run`` span holds two ops and a gap; an enclosing op (the
    loop) covers both ops and the gap between them, not the dispatch before
    them. Two chips, the second idle all through."""
    ev = trace.TraceEvents(
        leaf=[[(1.0, 2.0, "a"), (2.5, 3.0, "b"), (6.0, 7.0, "a")], []],
        enclosing=[[(1.0, 3.0)], []],
        spans=[("unit", 0.0, 10.0)])
    program = [("batched/h2d", 0.2, 0.5), ("batched/run", 0.5, 3.5),
               ("mc/run_batched", 0.1, 3.8),
               ("batched/run", 11.0, 12.0)]  # after the unit: left out
    split = pt.program_split(ev, program, "unit")
    assert split.chips == 2
    assert split.span_s == pytest.approx(
        {"batched/h2d": 0.3, "batched/run": 3.0, "mc/run_batched": 3.7})
    # chip 0: run idle 0.5..1, 2..2.5, 3..3.5; chip 1: all 3 s
    assert split.idle_s == pytest.approx(
        {"batched/h2d": 0.3, "batched/run": (1.5 + 3.0) / 2,
         "mc/run_batched": (2.2 + 3.7) / 2})
    # inside the loop, only the gap 2..2.5, and only chip 0 has a loop
    assert split.loop_idle_s == pytest.approx(
        {"batched/h2d": 0.0, "batched/run": 0.25, "mc/run_batched": 0.25})
    with pytest.raises(ValueError):
        pt.program_split(ev, program, "no-such-unit")


def test_committed_trace_reduces_as_before():
    """The recorded trace without the program's spans gives the summary it
    gave before they existed, key for key, and no program spans."""
    raw = gzip.decompress((DATA / "tail_240s_n16.xplane.pb.gz").read_bytes())
    got = dataclasses.asdict(trace.summarize(trace.read_xspace(raw),
                                             "ensemble"))
    want = json.loads((DATA / "tail_240s_n16.summary.json").read_text())
    assert set(got) == set(want)
    for key, value in want.items():
        if isinstance(value, dict):
            assert set(got[key]) == set(value), key
            assert got[key] == pytest.approx(value, rel=1e-12), key
        else:
            assert got[key] == pytest.approx(value, rel=1e-12), key
    assert pt.read_program_spans(raw) == []


@pytest.fixture(scope="module")
def recorded_obs():
    raw = gzip.decompress(
        (DATA / "tail_240s_n16_obs.xplane.pb.gz").read_bytes())
    return trace.read_xspace(raw), pt.read_program_spans(raw)


def test_recorded_program_spans_sit_inside_the_scan(recorded_obs):
    """With the program's recorder on, each ``polca/batched/*`` span of the
    traced unit lies inside the benchmark's ``chipbench/scan`` span, once
    each and in order, all inside ``polca/mc/run_batched``."""
    ev, program = recorded_obs
    (_, lo, hi), = [x for x in ev.spans if x[0] == "scan"]
    (_, ulo, uhi), = [x for x in ev.spans if x[0] == "ensemble"]
    unit = sorted((s, n) for n, s, e in program if ulo <= s <= uhi)
    batched = [(n, s, e) for n, s, e in program
               if n.startswith("batched/") and ulo <= s <= uhi]
    assert [n for n, _, _ in sorted(batched, key=lambda x: x[1])] == \
        ["batched/" + b for b in BATCHED]
    assert all(lo <= s <= e <= hi for _, s, e in batched)
    (_, rlo, rhi), = [x for x in program
                      if x[0] == "mc/run_batched" and ulo <= x[1] <= uhi]
    assert all(rlo <= s <= e <= rhi for _, s, e in batched)
    assert unit[0][1] == "mc/run_batched"


def test_reduction_of_the_recorded_program_spans(recorded_obs):
    ev, program = recorded_obs
    split = pt.program_split(ev, program, "ensemble")
    assert split.chips == 1
    run = split.span_s["batched/run"]
    assert 0.0 < split.idle_s["batched/run"] < run
    assert 0.0 <= split.loop_idle_s["batched/run"] \
        <= split.idle_s["batched/run"]
    # the scan's idle, as summarize puts it, holds the runner's gaps
    s = trace.summarize(ev, "ensemble")
    assert split.idle_s["batched/run"] <= s.idle_by_label["scan"] + 1e-9
    for name, seconds in split.span_s.items():
        assert 0.0 <= split.idle_s[name] <= seconds + 1e-9, name




def _snapshot(spans=None, counters=None) -> MetricsSnapshot:
    snap = MetricsSnapshot()
    for name, seconds in (spans or {}).items():
        snap.spans[(name, ())] = SpanStats(1, seconds, seconds, seconds)
    for name, value in (counters or {}).items():
        snap.counters[(name, ())] = value
    return snap


def test_means_read_nothing_where_there_is_nothing():
    """No snapshots; a program without the batched spans and counters; a
    trace without a TPU, or without ``batched/run``: each reads None."""
    bare = [_snapshot(spans={"mc/run_batched": 1.0, "planner/probe": 1.2},
                      counters={"planner_probes_total": 3.0})]
    for snaps in ([], bare):
        assert pt.span_mean(snaps, pt.TRANSFER_SPANS) is None
        assert pt.span_mean(snaps, pt.HOST_SPANS) is None
        assert pt.counter_mean(snaps, pt.BYTE_COUNTERS) is None
    no_chip = pt.ProgramSplit(chips=0, span_s={"batched/run": 1.0},
                              idle_s={"batched/run": 0.0},
                              loop_idle_s={"batched/run": 0.0})
    for split in (None, no_chip,
                  dataclasses.replace(no_chip, chips=1, idle_s={})):
        assert pt.idle_s(split, pt.RUN_SPAN) is None


def test_means_over_the_units_snapshots():
    snaps = [_snapshot(spans={"batched/operands": 0.1, "batched/h2d": 0.02,
                              "batched/run": 1.0, "batched/d2h": 0.03,
                              "batched/unpack": 0.2},
                       counters={"batched_h2d_bytes_total": 3e6,
                                 "batched_d2h_bytes_total": 15e6}),
             _snapshot(spans={"batched/operands": 0.3, "batched/h2d": 0.04,
                              "batched/run": 1.0, "batched/d2h": 0.01,
                              "batched/unpack": 0.0},
                       counters={"batched_h2d_bytes_total": 1e6,
                                 "batched_d2h_bytes_total": 1e6})]
    assert pt.span_mean(snaps, pt.TRANSFER_SPANS) == pytest.approx(0.05)
    assert pt.span_mean(snaps, pt.HOST_SPANS) == pytest.approx(0.3)
    assert pt.counter_mean(snaps, pt.BYTE_COUNTERS) == pytest.approx(10e6)
    split = pt.ProgramSplit(chips=1, span_s={"batched/run": 1.2},
                            idle_s={"batched/run": 0.25},
                            loop_idle_s={"batched/run": 0.2})
    assert pt.idle_s(split, pt.RUN_SPAN) == 0.25


@pytest.mark.parametrize("kind", ["plan", "tail"])
def test_program_split_tool_on_the_host_cpu(kind):
    """The tool's pairs agree bit for bit with the recorder on and off; the
    recorded units give the transfer and host seconds and the bytes; the
    host CPU has no TPU plane, so the scan's gaps are left out."""
    from chipbench.tests.test_drivers import small_cell
    from repro.obs.metrics import get_recorder

    out = SPLIT.split(small_cell(kind), 2**31 + 977, 2, require_tpu=False)
    assert not get_recorder().enabled
    assert out["bit_identical"]
    assert len(out["unit_s"]["off"]) == len(out["unit_s"]["on"]) == 2
    assert out["transfer_mb"] > 0.0
    assert out["transfer_s"] > 0.0 and out["scan_host_s"] > 0.0
    assert out["scan_gap_s"] is None and out["explained_pct"] is None
    assert out["split"]["chips"] == 0
    assert {"batched/" + b for b in BATCHED} <= set(out["split"]["span_s"])
    json.dumps(out)


def test_answer_bits_tell_a_changed_member_apart():
    """The tool's parity check sees one member's power change by one ulp."""
    import numpy as np

    from chipbench.tests.test_drivers import small_cell

    cell = small_cell("tail")
    state = cell.driver.setup(cell.config, cell.traffic, 5, 6)
    state.pop("kept")
    record = cell.driver.unit(state, 7)
    before = SPLIT.answer_bits(record)
    ens = record["ensemble"]
    peak = ens.peak_fracs.copy()
    peak[0] = np.nextafter(peak[0], 2.0)
    record["ensemble"] = dataclasses.replace(ens, peak_fracs=peak)
    assert SPLIT.answer_bits(record) != before
