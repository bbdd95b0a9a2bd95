"""Entry kind ``plan``: one unit is one capacity decision,
``plan_capacity(engine="jax")``, on fresh member seeds.

Traffic parameters: ``n_seeds`` members per probe, ``max_added_frac`` of
the bisection, ``check_units`` decisions compared with the reference after
the window (one with the most probes among them), and ``limits``. Where
``n_provisioned * max_added_frac`` is a power of two, and the top of the
range fails and 0 passes, every decision runs the same number of probes.
"""

from __future__ import annotations

import numpy as np

from chipbench import batched_entry
from chipbench.compare import Gaps, member_answers, reference_answers
from chipbench.reference import ticksim

UNIT_SPAN = "planner"
wrap = batched_entry.wrap
trace_count = batched_entry.trace_count


def setup(config: dict, traffic: dict, seed: int, warm_seed: int) -> dict:
    """Build the scenario and run one decision: probes differ only in
    traced operands, so its first probe compiles the one program every
    probe runs, and the planner's own caches fill as in the window."""
    from repro.experiments.scenario import Scenario

    state = dict(base=Scenario.from_dict(config["scenario"]),
                 n_seeds=int(traffic["n_seeds"]),
                 max_added_frac=float(traffic["max_added_frac"]))
    unit(state, warm_seed)
    return state


def unit(state: dict, seed0: int) -> dict:
    """One decision. ``keep_ensembles`` only holds on to each probe's
    result, which the comparison after the window reads."""
    from repro.provisioning.planner import plan_capacity

    r = plan_capacity(state["base"], engine="jax", n_seeds=state["n_seeds"],
                      seed0=seed0, max_added_frac=state["max_added_frac"],
                      keep_ensembles=True)
    return dict(seed0=seed0, result=r, probes=r.probes)


def sample_units(records: list, k: int, seed: int) -> list:
    """``k`` decisions drawn from the seed, one of the longest among them."""
    rng = np.random.default_rng([int(seed), 23])
    n_probes = [len(r["probes"]) for r in records]
    longest = int(rng.choice(np.flatnonzero(np.equal(n_probes,
                                                     max(n_probes)))))
    rest = [i for i in range(len(records)) if i != longest]
    pick = rng.choice(rest, size=min(k - 1, len(rest)), replace=False)
    return [longest] + sorted(int(i) for i in pick)


def compare(config: dict, traffic: dict, records: list, seed: int, *,
            control: bool = False) -> dict:
    """Numbers compared with the float64 reference planner.

    ``plan_mismatch`` counts sampled decisions whose decision, probe path,
    or any probe's brake or SLO share differs from the reference's. Every
    member of every probe of those decisions is held to the reference's by
    ``chipbench.compare``. With ``control`` the float32 reference takes the
    program's place."""
    plane = ticksim.plane_from_config(config)
    slo = config["scenario"]["slo"]
    kw = dict(n_seeds=int(traffic["n_seeds"]),
              max_added_frac=float(traffic["max_added_frac"]))
    mismatch, gaps = 0, Gaps()
    for i in sample_units(records, int(traffic["check_units"]), seed):
        seed0 = records[i]["seed0"]
        want_safe, want = ticksim.plan(plane, slo, seed0=seed0, **kw)
        if control:
            safe, low = ticksim.plan(plane, slo, seed0=seed0,
                                     dtype=np.float32, **kw)
            got = [(p["added"], p["feasible"], p["brake_prob"],
                    p["slo_prob"], reference_answers(p["members"]))
                   for p in low]
        else:
            r = records[i]["result"]
            safe = r.safe_added_servers
            got = [(p.added_servers, p.feasible, p.brake_prob,
                    p.slo_violation_prob,
                    member_answers(p.ensemble,
                                   range(len(p.ensemble.brake_counts))))
                   for p in r.probes]
        path = [g[:4] for g in got]
        mismatch += (safe != want_safe or path != [
            (p["added"], p["feasible"], p["brake_prob"], p["slo_prob"])
            for p in want])
        for g, p in zip(got, want):
            gaps.add(g[4], reference_answers(p["members"]))
    return dict(plan_mismatch=float(mismatch), **gaps.numbers())
