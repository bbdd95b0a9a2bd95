"""The site configuration and its cell, beside the row cells' tests: the
configuration states the tree, ratings and per-row constants the program
derives; the ``tail_site`` driver at a size a test run holds agrees with
the reference, its float32 control does not, and a run broken underneath
(the row faults of ``test_drivers`` and two of the fold's own) comes out
not correct; the site's reference is held to the row reference it builds
on; the manifest gives the cell its own metrics and limits."""

import dataclasses
import functools
import json
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import pool, sitesim, ticksim
from chipbench.tests import test_configs, test_drivers

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
SITE56 = BENCH / "configs" / "site56.json"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = test_drivers.SEED
SMALL = dict(n_seeds=8, check_units=1)


@pytest.fixture(autouse=True)
def two_workers(monkeypatch):
    """The site's reference runs in two worker processes here."""
    monkeypatch.setattr(pool, "workers", lambda: 2)


def test_site56_matches_the_lowered_model():
    test_configs.test_config_matches_the_lowered_model(SITE56)


def test_site56_budget_is_the_resolved_one():
    from repro.experiments import get_scenario
    from repro.provisioning.montecarlo import resolve_ensemble_budget

    cfg = json.loads(SITE56.read_text())
    assert cfg["budget_w"] == resolve_ensemble_budget(get_scenario("site56"))


def test_site56_states_the_tree_the_program_derives():
    from repro.experiments import get_scenario
    from repro.experiments.scenario import Scenario
    from repro.provisioning.batched import lower_ensemble
    from repro.provisioning.montecarlo import (EnsembleSpec,
                                               resolve_ensemble_budget)

    cfg = json.loads(SITE56.read_text())
    sc = Scenario.from_dict(cfg["scenario"])
    assert sc == get_scenario("site56")
    assert cfg["budget_w"] == resolve_ensemble_budget(
        get_scenario("fig14-plus30"))
    # per-row constants as row40 states them
    row40 = json.loads((BENCH / "configs" / "row40.json").read_text())
    for key in ("budget_w", "traffic_curve", "server", "workload_mix",
                "policy_constants"):
        assert cfg[key] == row40[key], key
    model, _, _ = lower_ensemble(
        EnsembleSpec(sc.with_(duration_s=1800.0), n_seeds=1, seed0=1),
        budget_w=cfg["budget_w"])
    tree = sitesim.tree_from_config(cfg)
    assert model.node_shape == (4, 2, 7) and model.n_rows == 56
    assert model.node_names[model.n_rows:] == tree.names
    np.testing.assert_array_equal(model.node_capacity_w, tree.rating_w)
    assert [lo for lo, _ in tree.spans[:8]] == list(range(0, 56, 7))
    plane = ticksim.plane_from_config(cfg)
    assert plane.n_ticks == 43_200 and plane.n_rows == 56
    np.testing.assert_array_equal(plane.row_budget, model.row_budget_w)


def site_config(ratings=None) -> dict:
    """``site56`` cut to a (2, 2, 3) tree of 12 rows and one hour; the
    ratings default to the median of the members' node peaks per level, so
    that they bind for some members and not for others."""
    cfg = json.loads(SITE56.read_text())
    sc = cfg["scenario"]
    sc["duration_s"] = test_drivers.DURATION_S
    sc["fleet"]["n_rows"] = 12
    sc["hierarchy"] = dict(shape=[2, 2, 3], level_names=["site", "pdu",
                                                         "rack"],
                           budget_fracs={},
                           level_capacity_w=list(ratings or _ratings()))
    return cfg


@functools.lru_cache(maxsize=None)
def _ratings() -> tuple:
    seed0 = harness.unit_seed(SEED, 0)
    peak = sitesim.simulate_seeds(range(seed0, seed0 + SMALL["n_seeds"]),
                                  site_config([1e9] * 3))["node_peak"]
    return (float(np.median(peak[:, -1])), float(np.median(peak[:, 4:6])),
            float(np.median(peak[:, :4])))


def small_cell() -> harness.Cell:
    traffic = json.loads((BENCH / "traffic" / "tail_site_n256.json")
                         .read_text())
    traffic.update(SMALL)
    return harness.Cell(
        name="tail_site.small", chips=1, config=site_config(),
        traffic=traffic,
        driver=harness.load_module(BENCH / "drivers" / "tail_site.py"),
        end_to_end=[dict(name="setup_s", unit="s")], per_layer=[],
        readers={"setup_s": lambda run: run.setup_s})


def one_unit(cell, **kw) -> dict:
    drv = cell.driver
    state = drv.setup(cell.config, cell.traffic, SEED, SEED + 1)
    records = [drv.unit(state, SEED + 2)]
    return drv.compare(cell.config, cell.traffic, records, SEED, **kw)


def measure(cell) -> dict:
    return harness.measure(cell, SEED, 0.01, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           say=lambda line: None)


def test_unit_agrees_with_the_reference():
    cell = small_cell()
    values = one_unit(cell)
    assert set(values) == set(cell.traffic["limits"])
    assert test_drivers.within_limits(cell, values), values


def test_float32_control_is_not_correct():
    cell = small_cell()
    values = one_unit(cell, control=True)
    assert not test_drivers.within_limits(cell, values), values


def _dropped_node(monkeypatch, cell):
    """The root left out of every member's node results."""
    from repro.provisioning import batched

    orig = batched.run_tick_model

    def dropped(*a, **k):
        run = orig(*a, **k)
        return dataclasses.replace(
            run, node_peak_w=run.node_peak_w[:, :-1],
            node_over_ticks=run.node_over_ticks[:, :-1])
    monkeypatch.setattr(batched, "run_tick_model", dropped)


def _rating_off_by_one_watt(monkeypatch, cell):
    """The racks rated 0.5 W above one member's rack load at one tick;
    the program reads its ratings 1 W low."""
    from repro.experiments.scenario import Scenario
    from repro.provisioning import batched
    from repro.provisioning.montecarlo import EnsembleSpec

    cfg = cell.config
    model, members, _ = batched.lower_ensemble(EnsembleSpec(
        Scenario.from_dict(cfg["scenario"]), n_seeds=SMALL["n_seeds"],
        seed0=harness.unit_seed(SEED, 0)), budget_w=cfg["budget_w"])
    node_w = batched.run_tick_model(model, members, engine="numpy",
                                    keep_series=True).node_w
    cfg["scenario"]["hierarchy"]["level_capacity_w"][2] = \
        float(node_w[0, :, model.n_rows].max()) + 0.5
    orig = batched.lower_ensemble

    def low(*a, **k):
        m, mem, b = orig(*a, **k)
        return dataclasses.replace(
            m, node_capacity_w=m.node_capacity_w - 1.0), mem, b
    monkeypatch.setattr(batched, "lower_ensemble", low)


def _row_fault(name):
    def fault(monkeypatch, cell):
        test_drivers._break_scan(monkeypatch, test_drivers.FAULTS[name])
    return fault


def _altered(monkeypatch, cell):
    """A brake count altered in the ensemble, as in the row tail."""
    test_drivers._alter_answer(monkeypatch, "tail")


FAULTS = {**{name: _row_fault(name) for name in test_drivers.FAULTS},
          "altered": _altered, "dropped_node": _dropped_node,
          "rating_off_by_one_watt": _rating_off_by_one_watt}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_site_path_is_not_correct(fault, monkeypatch):
    cell = small_cell()
    FAULTS[fault](monkeypatch, cell)
    out = measure(cell)
    assert out["correct"] is False, out["checks"]


def test_small_site_ratings_bind():
    """The compared members cross their ratings, some nodes and not all."""
    seed0 = harness.unit_seed(SEED, 0)
    over = sitesim.simulate_seeds(range(seed0, seed0 + SMALL["n_seeds"]),
                                  site_config())["node_over"]
    assert 0 < np.count_nonzero(over) < over.size


def test_sitesim_rows_are_ticksims_bit_for_bit():
    cfg = site_config([1e6, 5e5, 2.5e5])
    plane = ticksim.plane_from_config(cfg)
    occ = ticksim.member_occupancy(plane, range(3), 52)
    for dtype in (np.float64, np.float32):
        rows = ticksim.simulate(plane, occ, 52, dtype)
        site = sitesim.simulate(plane, sitesim.tree_from_config(cfg), occ,
                                52, dtype)
        for k, v in rows.items():
            np.testing.assert_array_equal(site[k], v, err_msg=k)


def test_the_pool_joins_slices_in_member_order():
    cfg = site_config([1e6, 5e5, 2.5e5])
    whole = sitesim.simulate_seeds(range(10, 15), cfg)
    split = pool.map_members(sitesim.simulate_seeds, range(10, 15), cfg)
    for k, v in whole.items():
        np.testing.assert_array_equal(split[k], v, err_msg=k)


def test_four_chip_cells_are_at_most_half():
    cells = MANIFEST["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)


def test_site_cell_reports_the_tail_metrics_of_its_own():
    c = harness.resolve_cell(MANIFEST, "tail.site56.n256")
    assert c.chips == 1 and c.config["name"] == "site56"
    assert {m["name"] for m in c.end_to_end} == {"member_ticks_per_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        f"{m}.site" for m in ("lower_s", "scan_device_s", "scan_roofline",
                              "assemble_s", "idle_pct", "peak_hbm_gb")}
    assert c.traffic["limits"] == {"brake_mismatch": 0, "power_gap": 5e-7,
                                   "node_over_mismatch": 0,
                                   "node_peak_gap": 5e-7}
