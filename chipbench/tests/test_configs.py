"""Each configuration file states its deployment as the system derives it:
the reference reads these numbers, so a change to the system's power plane,
budgets or traffic shows here before it shows as an incorrect run."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.reference import ticksim

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = sorted((ROOT / "chipbench" / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_config_matches_the_lowered_model(path):
    from repro.experiments.scenario import Scenario
    from repro.provisioning.batched import lower_ensemble
    from repro.provisioning.montecarlo import EnsembleSpec

    cfg = json.loads(path.read_text())
    cfg["scenario"]["duration_s"] = 1800.0
    sc = Scenario.from_dict(cfg["scenario"])
    seeds = [2**31 + 11, 2**31 + 12]
    model, _, budget = lower_ensemble(EnsembleSpec(sc, n_seeds=2,
                                                   seed0=seeds[0]),
                                      budget_w=cfg["budget_w"])
    plane = ticksim.plane_from_config(cfg)
    for name in ("p0_srv_w", "k_lp_w", "k_hp_w", "lp_share", "gamma",
                 "a_hp", "a_lp", "svc_hp", "svc_lp", "t1", "t2",
                 "t1_buffer", "t2_buffer", "lp_freq_t1", "lp_freq_t2",
                 "hp_freq_t2", "brake_freq", "escalation_ticks", "n_ticks",
                 "stride", "n_slots", "oob_ticks", "brake_ticks",
                 "power_scale"):
        assert getattr(plane, name) == pytest.approx(getattr(model, name),
                                                     rel=1e-15), name
    assert plane.ring == model.ring_depth
    assert not model.predictive
    np.testing.assert_allclose(plane.row_budget, model.row_budget_w,
                               rtol=1e-15)
    occ = ticksim.member_occupancy(
        plane, seeds, ticksim.n_servers_at(plane, sc.fleet.added_frac))
    np.testing.assert_array_equal(occ, model.occ60)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_stated_budget_is_the_resolved_one(path):
    from repro.experiments import get_scenario
    from repro.provisioning.montecarlo import resolve_ensemble_budget

    cfg = json.loads(path.read_text())
    registered = {"row40": "fig14-plus30"}
    sc = get_scenario(registered[cfg["name"]])
    assert cfg["budget_w"] == resolve_ensemble_budget(sc)
