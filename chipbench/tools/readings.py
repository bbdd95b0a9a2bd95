"""The readings that the limits of ``correct`` are set from.

    python3 chipbench/tools/readings.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>]

For each seed, in one process (the program is compiled once): the cell's
set-up and the units of one measured window at the cell's own load, then
the numbers the cell compares for the program and for the control, the
float32 reference in the program's place (on the first ``k`` seeds
only, where ``--control-seeds`` is given). Beside each, the members'
parts (``chipbench.compare.gap_parts``) by quantile, so that an outlier
among the members shows. One JSON line per seed.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench import compare, harness
    from repro.launch.compile_cache import enable_compile_cache

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve_cell(manifest, args.workload)
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    drv = cell.driver
    parts = []  # every member's parts, from the driver's comparison
    orig = compare.gap_parts

    def spy(got, want):
        parts.append(orig(got, want))
        return parts[-1]
    compare.gap_parts = spy

    def quantiles():
        out = {k: dict(zip(("p50", "p90", "p99", "max"), np.percentile(
            [p[k] for p in parts], [50, 90, 99, 100]).tolist()))
            for k in parts[0]}
        out["members"] = len(parts)
        parts.clear()
        return out

    n_control = len(args.seeds) if args.control_seeds is None \
        else args.control_seeds
    for i, seed in enumerate(args.seeds):
        state = drv.setup(cell.config, cell.traffic, seed,
                          harness.unit_seed(seed, harness.WARMUP_UNIT))
        records, t0 = [], time.perf_counter()
        while not records or time.perf_counter() - t0 < args.seconds:
            records.append(drv.unit(state, harness.unit_seed(seed,
                                                             len(records))))
        t1 = time.perf_counter()
        del state
        program = drv.compare(cell.config, cell.traffic, records, seed)
        program_parts = quantiles()
        t2 = time.perf_counter()
        line = dict(seed=seed, units=len(records), program=program,
                    program_parts=program_parts, window_s=t1 - t0,
                    reference_s=t2 - t1)
        if i < n_control:
            line["control"] = drv.compare(cell.config, cell.traffic, records,
                                          seed, control=True)
            line["control_parts"] = quantiles()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
