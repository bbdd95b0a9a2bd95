"""Simulated member-ticks of the completed ensembles per window second,
host lowering and result assembly included."""


def read(run):
    if not run.records:
        return None
    return sum(r["member_ticks"] for r in run.records) / run.window_s
