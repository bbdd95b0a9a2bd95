"""Run one cell of the chip benchmark on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their metrics and bounds are in BENCHMARK.json at the root of the
checkout; see chipbench/harness.py for what a run does.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu would otherwise log under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START, root=ROOT))
