"""Readings shared by the metric files under ``metrics/``. Each returns
``None`` where the run has nothing to read, and the metric is then left
out of the result line."""

from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench.batched_entry import scan_calls
from chipbench.roofline import roofline_pct, scan_bytes


def span_mean(run, label: str) -> Optional[float]:
    """Host seconds per unit in the benchmark's span ``label``."""
    if not run.spans:
        return None
    return float(np.mean([s.get(label, 0.0) for s in run.spans]))


def scan_device_s(run) -> Optional[float]:
    """Device seconds of the traced unit's scans."""
    if run.trace is None or not run.trace.chips:
        return None
    return run.trace.device_s.get("scan") or None


def scan_roofline(run) -> Optional[float]:
    """The traced unit's scans' share of the HBM roofline, in percent."""
    device_s = scan_device_s(run)
    calls = scan_calls(run.trace_calls)
    if device_s is None or not calls or "hbm_bytes_per_s" not in run.peaks:
        return None
    total = sum(scan_bytes(**{k: v for k, v in c.items() if k != "label"})
                for c in calls)
    return roofline_pct(total, device_s, run.peaks["hbm_bytes_per_s"])


def idle_pct(run) -> Optional[float]:
    """Share of the traced unit in which no leaf operation ran."""
    if run.trace is None or not run.trace.chips or run.trace.window_s <= 0:
        return None
    return run.trace.idle_pct
