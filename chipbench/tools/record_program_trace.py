"""Record the small chip trace that holds the program's own spans.

    python3 chipbench/tools/record_program_trace.py <out.xplane.pb.gz>

Runs ``record_trace.py``'s 16-member, 4-minute row40 ensemble with a
``repro.obs`` recorder live, so that the trace also holds the program's
spans (``polca/<name>``), writes it gzipped and prints their reduction.
``tests/data/tail_240s_n16_obs.xplane.pb.gz`` was recorded so;
``tests/data/tail_240s_n16.xplane.pb.gz`` by ``record_trace.py``.
"""

import dataclasses
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> int:
    from chipbench import harness, trace
    from chipbench.program_trace import program_split, read_program_spans
    from repro.obs.metrics import MetricsRecorder, recording

    record_trace = harness.load_module(Path(__file__).parent
                                       / "record_trace.py")
    with recording(MetricsRecorder()):
        rc = record_trace.main(out)
    if rc:
        return rc
    raw = gzip.decompress(Path(out).read_bytes())
    split = program_split(trace.read_xspace(raw), read_program_spans(raw),
                          "ensemble")
    print(json.dumps(dict(program=dataclasses.asdict(split))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
