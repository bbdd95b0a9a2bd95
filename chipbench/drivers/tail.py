"""Entry kind ``tail``: one unit is one dense risk tail,
``run_batched_ensemble(engine="jax")`` with the user-facing automatic
memory flags, over ``n_seeds`` fresh members.

Traffic parameters: ``n_seeds`` members per ensemble, ``check_units``
ensembles of the run whose every member is compared with the reference
after the window (drawn from the seed), and ``limits``.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import batched_entry
from chipbench.compare import Gaps, member_answers, reference_answers
from chipbench.reference import ticksim

UNIT_SPAN = "ensemble"
wrap = batched_entry.wrap
trace_count = batched_entry.trace_count


class Reservoir:
    """Keeps the results of ``k`` units drawn uniformly, from the seed, out
    of however many the run completes; the others are let go as they are
    replaced, so a run holds at most ``k + 1`` ensembles."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen, self.kept = k, 0, []
        self.rng = np.random.default_rng([int(seed), 29])

    def offer(self, record: dict) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(record)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.kept[j]["ensemble"] = None
            self.kept[j] = record
        else:
            record["ensemble"] = None


def setup(config: dict, traffic: dict, seed: int, warm_seed: int) -> dict:
    """Build the scenario and run one ensemble, which compiles its only
    program; the budget is the one the configuration states."""
    from repro.experiments.scenario import Scenario

    state = dict(scenario=Scenario.from_dict(config["scenario"]),
                 budget_w=float(config["budget_w"]),
                 n_seeds=int(traffic["n_seeds"]))
    unit(state, warm_seed)
    state["kept"] = Reservoir(int(traffic["check_units"]), seed)
    return state


def unit(state: dict, seed0: int) -> dict:
    from repro.provisioning.batched import run_batched_ensemble
    from repro.provisioning.montecarlo import EnsembleSpec

    sc = state["scenario"]
    n = state["n_seeds"]
    ens = run_batched_ensemble(EnsembleSpec(sc, n_seeds=n, seed0=seed0),
                               budget_w=state["budget_w"], engine="jax")
    ticks = math.floor(sc.duration_s / sc.telemetry.telemetry_s)
    record = dict(seed0=seed0, member_ticks=n * ticks, ensemble=ens)
    if "kept" in state:
        state["kept"].offer(record)
    return record


def compare(config: dict, traffic: dict, records: list, seed: int, *,
            control: bool = False) -> dict:
    """Every member of each kept ensemble against the float64 reference
    (``chipbench.compare``). With ``control`` the float32 reference takes
    the program's place."""
    plane = ticksim.plane_from_config(config)
    n_servers = ticksim.n_servers_at(
        plane, config["scenario"]["fleet"]["added_frac"])
    n = int(traffic["n_seeds"])
    gaps = Gaps()
    for r in records:
        if r["ensemble"] is None:
            continue
        occ = ticksim.member_occupancy(plane, range(r["seed0"],
                                                    r["seed0"] + n), n_servers)
        want = reference_answers(ticksim.simulate(plane, occ, n_servers))
        if control:
            got = reference_answers(ticksim.simulate(plane, occ, n_servers,
                                                     np.float32))
        else:
            got = member_answers(r["ensemble"], range(len(r["ensemble"]
                                                          .brake_counts)))
        gaps.add(got, want)
    return gaps.numbers()
