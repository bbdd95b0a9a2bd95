"""A rated power tree on the batched engine: a small site, a (2, 2, 3) tree
of 12 POLCA rows for 6 h, whose rack, PDU and site ratings bind for some
members and not for others. The jax engine folds the rows into the tree
inside its tick loop; the numpy oracle and the benchmark's plain reference
(``chipbench/reference/sitesim.py``) fold them on the host."""

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# rack, PDU and site ratings inside the range the members' node peaks span
RATINGS = (2.010e6, 1.009e6, 0.506e6)
SEED0, N_SEEDS = 2**31 + 101, 4


def site_config(ratings=RATINGS) -> dict:
    """``site56``'s configuration cut to a 12-row tree and 6 h."""
    cfg = json.loads((ROOT / "chipbench" / "configs" / "site56.json")
                     .read_text())
    sc = cfg["scenario"]
    sc["duration_s"] = 6 * 3600.0
    sc["fleet"]["n_rows"] = 12
    sc["hierarchy"] = dict(shape=[2, 2, 3],
                           level_names=["site", "pdu", "rack"],
                           budget_fracs={},
                           level_capacity_w=list(ratings))
    return cfg


def site_scenario(ratings=RATINGS):
    from repro.experiments.scenario import Scenario

    return Scenario.from_dict(site_config(ratings)["scenario"])


def _spec(sc, n=N_SEEDS):
    from repro.provisioning.montecarlo import EnsembleSpec

    return EnsembleSpec(sc, n_seeds=n, seed0=SEED0)


@pytest.fixture(scope="module")
def runs():
    from repro.provisioning import batched

    model, members, _ = batched.lower_ensemble(_spec(site_scenario()))
    return model, {e: batched.run_tick_model(model, members, engine=e,
                                             keep_series=True)
                   for e in ("jax", "numpy")}


def test_jax_oracle_and_reference_agree_on_the_tree(runs):
    from chipbench.reference import sitesim

    model, by_engine = runs
    cfg = site_config()
    want = sitesim.simulate_seeds(range(SEED0, SEED0 + N_SEEDS), cfg)
    names = sitesim.tree_from_config(cfg).names
    assert model.node_names[model.n_rows:] == names
    np.testing.assert_array_equal(model.node_capacity_w,
                                  sitesim.tree_from_config(cfg).rating_w)
    over = want["node_over"]
    # the ratings bind for some members and nodes, not for all
    assert 0 < np.count_nonzero(over) < over.size
    for run in by_engine.values():
        np.testing.assert_array_equal(run.node_over_ticks, over)
        np.testing.assert_allclose(run.node_peak_w, want["node_peak"],
                                   rtol=1e-9, atol=0)
        np.testing.assert_array_equal(run.n_brakes.sum(axis=1),
                                      want["n_brakes"])
        # the folded peaks are the peaks of the folded series
        np.testing.assert_allclose(
            run.node_peak_w, run.node_w.max(axis=1)[:, model.n_rows:],
            rtol=1e-12, atol=0)


def test_rating_is_what_the_count_is_held_to(runs):
    """A rating 1 W above one node's load at one tick leaves that tick
    under; 1 W lower counts it over."""
    from repro.provisioning import batched

    model, by_engine = runs
    node_w = by_engine["numpy"].node_w[0, :, model.n_rows]  # first rack
    k = int(np.argmax(node_w))
    cap = model.node_capacity_w.copy()
    cap[:4] = node_w[k] + 0.5  # every rack, at member 0's peak + 0.5 W
    counts = [batched.run_tick_model(
        dataclasses.replace(model, node_capacity_w=cap + shift), [],
        engine="jax", keep_series=False).node_over_ticks[0, 0]
        for shift in (0.0, -1.0)]
    assert counts[0] == 0 and counts[1] >= 1


def test_sharded_and_chunked_site_are_bit_identical():
    from repro.launch.mesh import data_mesh
    from repro.provisioning.batched import run_batched_ensemble

    spec = _spec(site_scenario(), n=12)
    one = run_batched_ensemble(spec, engine="jax")
    for kw in (dict(mesh=data_mesh(8)), dict(member_chunk=5),
               dict(mesh=data_mesh(4), member_chunk=2)):
        other = run_batched_ensemble(spec, engine="jax", **kw)
        for f in ("node_peak_w", "node_over_ticks", "brake_counts",
                  "peak_fracs", "mean_fracs", "power_frac"):
            np.testing.assert_array_equal(getattr(other, f), getattr(one, f),
                                          err_msg=f"{kw} {f}")
        assert other.node_names == one.node_names


def test_ratings_leave_the_budget_tree_conservative():
    from repro.experiments import get_scenario
    from repro.experiments.scenario import TABLE2_ROW_BUDGET_W

    sc = get_scenario("site56")
    h = sc.hierarchy.build([TABLE2_ROW_BUDGET_W] * sc.fleet.n_rows)
    assert h.conservation_errors() == []
    sb, msb, root = h.levels
    for level, rating in zip((root, msb, sb), sc.hierarchy.level_capacity_w):
        np.testing.assert_array_equal(h.capacity_w[level], rating)
        # each node rated below the sum of its children's budgets
        assert all(rating < h.node_budget_w[h.children[i]].sum()
                   for i in level)
    np.testing.assert_array_equal(h.capacity_w[:h.n_leaves], h.leaf_budget_w)
    assert len(h.interior) == 13 and h.n_leaves == 56


def test_a_level_left_unrated_is_rated_at_its_budget():
    from repro.core.hierarchy import PowerHierarchy

    h = PowerHierarchy.from_shape((2, 3), [10.0] * 6,
                                  level_capacity_w=(None, 25.0))
    np.testing.assert_array_equal(h.capacity_w, [10.0] * 6 + [25.0, 25.0,
                                                              60.0])
    np.testing.assert_array_equal(
        PowerHierarchy.from_shape((2, 3), [10.0] * 6).capacity_w,
        h.node_budget_w)
    with pytest.raises(ValueError, match="level ratings"):
        PowerHierarchy.from_shape((2, 3), [10.0] * 6,
                                  level_capacity_w=(1.0, 2.0, 3.0))


def test_unrated_tree_runs_as_before(runs):
    """Without ratings the program has no fold, and the rows' answers are
    the rated run's bit for bit: the fold reads the rows, never steers
    them."""
    from repro.provisioning import batched

    model, by_engine = runs
    sc = site_scenario()
    bare = sc.with_(hierarchy=dataclasses.replace(sc.hierarchy,
                                                  level_capacity_w=None))
    m, members, _ = batched.lower_ensemble(_spec(bare))
    assert m.node_shape == () and m.node_capacity_w is None
    cfg, _, _ = batched._plan_bucket([m], keep_series=True, keep_fire=True,
                                     member_chunk=None, mesh=None)
    assert cfg.fold == ()
    for engine in ("jax", "numpy"):
        run = batched.run_tick_model(m, members, engine=engine,
                                     keep_series=True)
        assert run.node_peak_w is None and run.node_over_ticks is None
        rated = by_engine[engine]
        for f in ("brake_fire", "n_brakes", "peak_frac", "mean_frac",
                  "impacts_hp", "impacts_lp", "total_frac", "row_w",
                  "node_w"):
            np.testing.assert_array_equal(getattr(run, f), getattr(rated, f),
                                          err_msg=f"{engine} {f}")
    ens = batched.run_batched_ensemble(_spec(bare), engine="jax")
    assert ens.node_names == () and ens.node_peak_w is None


def test_level_capacity_round_trips_through_json():
    from repro.experiments.scenario import HierarchySpec, Scenario

    sc = site_scenario((None, 1.009e6, 0.506e6))
    back = Scenario.from_json(sc.to_json())
    assert back == sc
    assert back.hierarchy.level_capacity_w == (None, 1.009e6, 0.506e6)
    assert isinstance(back.hierarchy, HierarchySpec)
    plain = sc.with_(hierarchy=HierarchySpec(shape=(2, 2, 3)))
    assert Scenario.from_json(plain.to_json()).hierarchy.level_capacity_w \
        is None


def test_site56_is_registered_as_its_configuration_states():
    from repro.experiments import get_scenario
    from repro.experiments.scenario import Scenario

    cfg = json.loads((ROOT / "chipbench" / "configs" / "site56.json")
                     .read_text())
    assert Scenario.from_dict(cfg["scenario"]) == get_scenario("site56")


def test_ensemble_and_plan_carry_the_node_stats():
    from repro.provisioning.batched import run_batched_ensemble
    from repro.provisioning.planner import RiskConstraints, plan_capacity

    sc = site_scenario()
    ens = run_batched_ensemble(_spec(sc), engine="jax")
    assert ens.node_names == ("rack0.0", "rack0.1", "rack1.0", "rack1.1",
                              "pdu0", "pdu1", "site")
    assert ens.node_over_ticks.shape == ens.node_peak_w.shape == (N_SEEDS, 7)
    plan = plan_capacity(sc, engine="jax", n_seeds=2, seed0=SEED0,
                         max_added_frac=0.05, keep_ensembles=True,
                         constraints=RiskConstraints(
                             max_brakes=0, max_slo_violation_prob=1.0))
    for p in plan.probes:
        assert p.ensemble.node_over_ticks.shape == (2, 7)
        assert p.ensemble.node_names == ens.node_names
