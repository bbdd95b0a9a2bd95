"""Entry kind ``tail_site``: one unit is one dense risk tail of a site,
``run_batched_ensemble(engine="jax")`` with the user-facing automatic
memory flags, over ``n_seeds`` fresh members. Every member runs every row
of the configuration's rated power tree, and the ensemble reports, per
member and interior node, the node's peak watts and its ticks over its
rating.

Traffic parameters: ``n_seeds`` members per ensemble, ``check_units``
ensembles of the run whose every member is compared with
``chipbench/reference/sitesim.py`` after the window (drawn from the seed),
and ``limits`` on four numbers: ``brake_mismatch`` and ``power_gap`` as the
``tail`` kind has them; ``node_over_mismatch``, the count of members whose
ticks over any node's rating differ from the reference's; and
``node_peak_gap``, the largest relative gap of any member's peak at any
node. A node missing, added or out of order makes every member a mismatch
and the peak gap infinite.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from chipbench import batched_entry
from chipbench.compare import Gaps, member_answers, reference_answers
from chipbench.drivers import tail
from chipbench.reference import pool, sitesim

UNIT_SPAN = "ensemble"
wrap = batched_entry.wrap
trace_count = batched_entry.trace_count
setup = tail.setup
unit = tail.unit


def node_gaps(names, peak, over, want: Dict[str, np.ndarray],
              want_names: Tuple[str, ...]) -> Tuple[float, float]:
    """(members whose over-rating counts differ, largest relative gap of a
    node peak) of one ensemble."""
    n = len(want["node_peak"])
    if (peak is None or tuple(names) != want_names
            or peak.shape != want["node_peak"].shape):
        return float(n), float("inf")
    gap = np.abs(peak / want["node_peak"] - 1.0)
    return (float(np.any(over != want["node_over"], axis=1).sum()),
            float(np.max(gap, initial=0.0)))


def compare(config: dict, traffic: dict, records: list, seed: int, *,
            control: bool = False) -> dict:
    """Every member of each kept ensemble against the float64 reference.
    With ``control`` the float32 reference takes the program's place."""
    names = sitesim.tree_from_config(config).names
    n = int(traffic["n_seeds"])
    gaps, over_mismatch, peak_gaps = Gaps(), 0.0, [0.0]
    for r in records:
        if r["ensemble"] is None:
            continue
        seeds = range(r["seed0"], r["seed0"] + n)
        want = pool.map_members(sitesim.simulate_seeds, seeds, config)
        if control:
            low = pool.map_members(sitesim.simulate_seeds, seeds, config,
                                   "float32")
            got = reference_answers(low)
            nodes = node_gaps(names, low["node_peak"], low["node_over"],
                              want, names)
        else:
            ens = r["ensemble"]
            got = member_answers(ens, range(len(ens.brake_counts)))
            nodes = node_gaps(ens.node_names, ens.node_peak_w,
                              ens.node_over_ticks, want, names)
        gaps.add(got, reference_answers(want))
        over_mismatch += nodes[0]
        peak_gaps.append(nodes[1])
    return dict(**gaps.numbers(), node_over_mismatch=over_mismatch,
                node_peak_gap=float(np.max(peak_gaps)))
