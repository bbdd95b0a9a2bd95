"""Record the small chip trace that the trace-reduction tests read.

    python3 chipbench/tools/record_trace.py <out.xplane.pb.gz>

Runs a 16-member, 4-minute row40 ensemble under the benchmark's own spans
and profiler, as a traced run does, and writes the trace gzipped.
"""

import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> int:
    import jax

    from chipbench import batched_entry, harness, trace
    from repro.experiments.scenario import Scenario
    from repro.provisioning.batched import run_batched_ensemble
    from repro.provisioning.montecarlo import EnsembleSpec

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    cfg = json.loads((ROOT / "chipbench/configs/row40.json").read_text())
    cfg["scenario"]["duration_s"] = 240.0
    sc = Scenario.from_dict(cfg["scenario"])
    spans = harness.Spans()
    batched_entry.wrap(spans)

    def unit(seed0):
        with spans.span("ensemble"):
            run_batched_ensemble(EnsembleSpec(sc, n_seeds=16, seed0=seed0),
                                 budget_w=cfg["budget_w"])

    unit(1)
    tracer = trace.Tracer()
    tracer.start()
    unit(2)
    tracer.stop()
    spans.close()
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    Path(out).write_bytes(gzip.compress(tracer.xspace))
    s = tracer.reduce("ensemble")
    print(json.dumps(dict(window_s=s.window_s, busy_s=s.busy_s,
                          device_s=s.device_s, idle=s.idle_by_label,
                          breakdown=s.breakdown())))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
