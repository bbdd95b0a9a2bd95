"""Probes the planner ran per decision (len(PlanResult.probes))."""

import numpy as np


def read(run):
    if not run.records:
        return None
    return float(np.mean([len(r["probes"]) for r in run.records]))
