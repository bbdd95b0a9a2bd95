"""The scan's idle split by the program's own spans, for one cell.

    python3 chipbench/tools/program_split.py --workload <cell> --seed <n> \
        --units <k>

In one process: the cell's set-up, then ``k`` pairs of units, each pair
one unit seed run once on the program's ``NullRecorder`` and once under a
live ``MetricsRecorder`` (which goes first alternates), then one more unit
under the benchmark's wrappers, a recorder and the profiler, as a traced
run has it (``chipbench.program_trace`` reduces it). Nothing is compared
with the reference. Prints one JSON line:

- ``bit_identical``: each pair's answers agree bit for bit;
- ``unit_s``: the units' seconds with the recorder off and on, and the cost
  of the recorder, from the medians and paired by seed;
- ``transfer_s``, ``transfer_mb``, ``scan_host_s``: per unit, from the
  recorded units' snapshots; ``scan_gap_s``: the traced unit's idle inside
  ``batched/run``, None without a TPU;
- ``scan_idle_s``: the ``scan`` idle as ``breakdown`` puts it, and the
  share of it the program's spans explain;
- ``split``: every program span of the traced unit.
"""

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def ensembles(record: dict) -> list:
    """The ensembles of one unit: the tail's one, or each probe's."""
    if "ensemble" in record:
        return [record["ensemble"]]
    return [p.ensemble for p in record["probes"]]


def answer_bits(record: dict) -> list:
    """A unit's answers as bytes: every member's, and the plan's path."""
    import numpy as np

    from chipbench.compare import member_answers

    bits = [[(k, np.asarray(v).tobytes()) for k, v in sorted(a.items())]
            for ens in ensembles(record)
            for a in member_answers(ens, range(len(ens.brake_counts)))]
    if "result" in record:
        r = record["result"]
        bits.append([r.safe_added_servers] + [
            (p.added_servers, p.feasible, p.brake_prob,
             p.slo_violation_prob) for p in r.probes])
    return bits


def split(cell, seed: int, units: int, *, require_tpu: bool = True) -> dict:
    from chipbench import harness, trace
    from chipbench import program_trace as pt
    from repro.obs.metrics import MetricsRecorder, recording

    harness.device_info(cell.chips, require_tpu=require_tpu)
    drv = cell.driver
    state = drv.setup(cell.config, cell.traffic, seed,
                      harness.unit_seed(seed, harness.WARMUP_UNIT))
    # the tail's reservoir keeps units for a comparison this tool skips
    state.pop("kept", None)

    def timed(u, rec):
        t = time.perf_counter()
        if rec is None:
            record = drv.unit(state, harness.unit_seed(seed, u))
        else:
            with recording(rec):
                record = drv.unit(state, harness.unit_seed(seed, u))
        return answer_bits(record), time.perf_counter() - t

    off_s, on_s, snapshots, same = [], [], [], True
    for u in range(units):
        rec = MetricsRecorder()
        if u % 2:
            on, t_on = timed(u, rec)
            off, t_off = timed(u, None)
        else:
            off, t_off = timed(u, None)
            on, t_on = timed(u, rec)
        same = same and on == off
        off_s.append(t_off)
        on_s.append(t_on)
        snapshots.append(rec.snapshot())

    spans = harness.Spans()
    drv.wrap(spans)
    tracer = trace.Tracer()
    try:
        tracer.start()
        with spans.span(drv.UNIT_SPAN), recording(MetricsRecorder()):
            drv.unit(state, harness.unit_seed(seed, units))
        tracer.stop()
    finally:
        spans.close()
    ev = trace.read_xspace(tracer.xspace)
    summary = trace.summarize(ev, drv.UNIT_SPAN)
    program = pt.program_split(ev, pt.read_program_spans(tracer.xspace),
                               drv.UNIT_SPAN)

    scan_idle = summary.idle_by_label.get("scan")
    parts = sum(program.idle_s.get(n, 0.0) for n in
                pt.TRANSFER_SPANS + pt.HOST_SPANS + (pt.RUN_SPAN,))
    mb = pt.counter_mean(snapshots, pt.BYTE_COUNTERS)
    med_off, med_on = statistics.median(off_s), statistics.median(on_s)
    return dict(
        workload=cell.name, seed=seed, units=units, bit_identical=same,
        unit_s=dict(off=off_s, on=on_s, median_off=med_off,
                    median_on=med_on,
                    cost_pct=100.0 * (med_on / med_off - 1.0),
                    paired_cost_pct=100.0 * (statistics.median(
                        [b / a for a, b in zip(off_s, on_s)]) - 1.0)),
        transfer_s=pt.span_mean(snapshots, pt.TRANSFER_SPANS),
        transfer_mb=None if mb is None else mb / 1e6,
        scan_host_s=pt.span_mean(snapshots, pt.HOST_SPANS),
        scan_gap_s=pt.idle_s(program, pt.RUN_SPAN),
        scan_idle_s=scan_idle,
        explained_pct=(100.0 * parts / scan_idle
                       if program.chips and scan_idle else None),
        split=dataclasses.asdict(program),
        breakdown=summary.breakdown())


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--units", type=int, required=True)
    args = ap.parse_args(argv)

    from chipbench import harness
    from repro.launch.compile_cache import enable_compile_cache

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve_cell(manifest, args.workload)
    enable_compile_cache()
    try:
        out = split(cell, args.seed, args.units)
    except harness.NoChip as e:
        print(f"program_split: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
