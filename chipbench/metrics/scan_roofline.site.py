"""The site scan's share of the HBM roofline, in percent: the bytes
``chipbench/roofline.py`` counts for the rows, plus the interior nodes'
ratings read and each member's node peak (float64) and count of ticks over
the rating (int32) written, over the scans' device time at peak bandwidth.
The tree is the configuration's (``scenario.hierarchy.shape``)."""

import math

from chipbench.batched_entry import scan_calls
from chipbench.readers import scan_device_s
from chipbench.roofline import F64, I32, roofline_pct, scan_bytes


def n_nodes(config) -> int:
    """Interior nodes of the configuration's regular tree."""
    shape = config["scenario"]["hierarchy"]["shape"]
    return sum(math.prod(shape[:d]) for d in range(len(shape)))


def read(run):
    device_s = scan_device_s(run)
    calls = scan_calls(run.trace_calls)
    if device_s is None or not calls or "hbm_bytes_per_s" not in run.peaks:
        return None
    k = n_nodes(run.cell.config)
    total = sum(scan_bytes(**{f: v for f, v in c.items() if f != "label"})
                + k * F64 + c["N"] * k * (F64 + I32) for c in calls)
    return roofline_pct(total, device_s, run.peaks["hbm_bytes_per_s"])
