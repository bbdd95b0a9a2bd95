"""How ensemble members' answers are held against the reference's.

Two numbers are compared over a set of members: ``brake_mismatch``, the
count of members whose powerbrake count differs from the reference's in
either place the ensemble reports it (exact, limit 0), and ``power_gap``,
the largest relative gap of a member's mean or peak power. A member's
latency-impact gap is read beside them (``tools/readings.py``) but not
compared: on the chip the program's impacts sit one float32 rounding from
the reference's, as close as the float32 control's, so no limit parts the
two."""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def member_answers(ens, idx) -> List[Dict[str, object]]:
    """The answers of members ``idx`` as an EnsembleResult holds them: its
    arrays, and each member's SimResult and latency samples."""
    return [dict(n_brakes=int(ens.brake_counts[i]),
                 member_brakes=int(ens.members[i].result.n_brakes),
                 peak=float(ens.peak_fracs[i]), mean=float(ens.mean_fracs[i]),
                 hp=np.asarray(ens.members[i].stats.hp_impacts, np.float64),
                 lp=np.asarray(ens.members[i].stats.lp_impacts, np.float64))
            for i in idx]


def reference_answers(out: Dict[str, np.ndarray]) -> List[Dict[str, object]]:
    """The same answers from ``ticksim.simulate``'s output."""
    return [dict(n_brakes=int(out["n_brakes"][j]),
                 member_brakes=int(out["n_brakes"][j]),
                 peak=float(out["peak"][j]), mean=float(out["mean"][j]),
                 hp=out["imp_hp"][j].ravel(), lp=out["imp_lp"][j].ravel())
            for j in range(len(out["n_brakes"]))]


def _abs_gap(got: np.ndarray, want: np.ndarray) -> float:
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want))) if want.size else 0.0


def gap_parts(got: dict, want: dict) -> Dict[str, float]:
    """One member's parts: whether its brake counts differ (0 or 1), the
    larger relative gap of its mean and peak power, the larger absolute gap
    of its HP and LP impact samples."""
    return dict(
        brakes=float(got["n_brakes"] != want["n_brakes"]
                     or got["member_brakes"] != want["n_brakes"]),
        power=max(abs(got["mean"] / want["mean"] - 1.0),
                  abs(got["peak"] / want["peak"] - 1.0)),
        impact=max(_abs_gap(got["hp"], want["hp"]),
                   _abs_gap(got["lp"], want["lp"])))


class Gaps:
    """The compared numbers, gathered member by member."""

    def __init__(self):
        self.parts: List[Dict[str, float]] = []

    def add(self, got: List[dict], want: List[dict]) -> None:
        if len(got) != len(want):  # a member missing is a member wrong
            self.parts.append(dict(brakes=float(abs(len(got) - len(want))),
                                   power=float("inf"), impact=float("inf")))
        self.parts += [gap_parts(g, w) for g, w in zip(got, want)]

    def numbers(self) -> Dict[str, float]:
        p = self.parts
        return dict(brake_mismatch=float(sum(x["brakes"] for x in p)),
                    power_gap=float(max(x["power"] for x in p)))
