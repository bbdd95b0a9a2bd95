"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived[,PASS|FAIL]`` CSV rows; rows carrying a
validation flag assert the corresponding paper claim (DESIGN.md §7).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig13,table2]
      [--artifacts out/]

``--only`` takes a comma-separated list of module basenames; a token
selects the modules it names exactly or prefixes at an underscore boundary
(``fig13`` selects ``fig13_threshold_search``; ``fig1`` matches nothing
and errors instead of silently selecting fig13-fig19). ``--artifacts DIR``
records the whole run through the observability stack (``repro.obs``) and
writes a run manifest, Prometheus metrics, the JSONL event trace, and one
``BENCH_<module>.json`` per module — the perf-trajectory artifact pipeline
``tools/report.py`` renders and diffs. The CSV on stdout is byte-identical
either way: recording is write-only.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback
from typing import List, Optional

MODULES = [
    "benchmarks.fig04_phase_timeseries",
    "benchmarks.fig05_config_sweeps",
    "benchmarks.fig06_07_capping",
    "benchmarks.fig08_09_training",
    "benchmarks.table2_cluster_stats",
    "benchmarks.fig13_threshold_search",
    "benchmarks.fig14_15_throughput_sweeps",
    "benchmarks.fig16_six_week",
    "benchmarks.fig17_18_policy_comparison",
    "benchmarks.fig19_beyond_llm",
    "benchmarks.capacity_planning",
    "benchmarks.fleet_routing",
    "benchmarks.fleet_rebalance",
    "benchmarks.site_hierarchy",
    "benchmarks.chaos_resilience",
    "benchmarks.phase_aware_savings",
    "benchmarks.kernel_micro",
    "benchmarks.roofline_table",
    "benchmarks.observability",
    "benchmarks.alerting",
    "benchmarks.batched_engine",
]


def select_modules(only: Optional[str]) -> List[str]:
    """Resolve ``--only`` to a subset of MODULES, original order, deduped.

    Each comma-separated token must match at least one module basename —
    exactly, or as a prefix ending at an underscore boundary — otherwise
    the run aborts naming the known basenames (a typo must not silently
    run the wrong figures)."""
    if not only:
        return list(MODULES)
    basenames = {m.rsplit(".", 1)[-1]: m for m in MODULES}
    chosen = set()
    for token in (t.strip() for t in only.split(",")):
        if not token:
            continue
        matches = [b for b in basenames
                   if b == token or b.startswith(token + "_")]
        if not matches:
            known = ", ".join(sorted(basenames))
            raise SystemExit(
                f"--only: {token!r} matches no benchmark module "
                f"(known: {known})")
        chosen.update(matches)
    return [m for b, m in basenames.items() if b in chosen]


def main() -> None:
    from benchmarks import common

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module basenames (exact or "
                         "underscore-boundary prefix match)")
    ap.add_argument("--seed", type=int, default=None,
                    help="override every scenario's seed (reproducible runs)")
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="record the run and write manifest + metrics + "
                         "events + BENCH_<module>.json under DIR")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    common.set_seed(args.seed)
    modules = select_modules(args.only)

    rec = None
    if args.artifacts:
        from repro.obs.metrics import MetricsRecorder, set_recorder
        rec = MetricsRecorder()
        set_recorder(rec)

    # progress to stderr via the shared repro logger (REPRO_LOG_LEVEL
    # gates it); stdout stays pure CSV
    from repro.obs.log import get_logger
    log = get_logger("benchmarks.run")

    t0 = time.perf_counter()
    print("name,us_per_call,derived[,validation]")
    n_fail = 0
    for i, modname in enumerate(modules, 1):
        basename = modname.rsplit(".", 1)[-1]
        log.info("[%d/%d] %s ...", i, len(modules), basename)
        t_mod = time.perf_counter()
        try:
            mod = importlib.import_module(modname)
            if rec is not None:
                with rec.span("bench/module", module=basename):
                    bench = mod.run(quick=args.quick)
            else:
                bench = mod.run(quick=args.quick)
            for row in bench.rows:
                print(row.csv())
                if row.ok is False:
                    n_fail += 1
            if args.artifacts:
                common.write_bench_json(args.artifacts, basename, bench.rows)
            mod_fail = sum(1 for r in bench.rows if r.ok is False)
            log.info("[%d/%d] %s: %d rows, %d failing, %.1fs",
                     i, len(modules), basename, len(bench.rows), mod_fail,
                     time.perf_counter() - t_mod)
        except Exception:
            print(f"{modname},0.0,EXCEPTION,FAIL")
            traceback.print_exc()
            n_fail += 1
            log.error("[%d/%d] %s: raised after %.1fs", i, len(modules),
                      basename, time.perf_counter() - t_mod)
            if args.artifacts:
                common.write_bench_json(args.artifacts, basename, None)
        sys.stdout.flush()
    print(f"# validation_failures={n_fail}")
    if rec is not None:
        from repro.obs.export import run_manifest, write_artifacts
        from repro.obs.metrics import set_recorder
        set_recorder(None)
        manifest = run_manifest(seed=common.BENCH_SEED, extra={
            "kind": "benchmarks.run",
            "quick": bool(args.quick),
            "modules": [m.rsplit(".", 1)[-1] for m in modules],
            "validation_failures": n_fail,
            "wall_clock_s": round(time.perf_counter() - t0, 3),
        })
        write_artifacts(args.artifacts, rec.snapshot(), manifest)
    if n_fail:
        sys.exit(1)


if __name__ == "__main__":
    main()
