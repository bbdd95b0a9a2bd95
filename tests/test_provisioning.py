"""Provisioning subsystem: ensemble determinism, composition invariants,
batched-vs-sequential Monte-Carlo bit-parity, planner monotonicity."""

import numpy as np
import pytest

from repro.core.slo import DEFAULT_SLO, SLO
from repro.core.traces import (
    get_occupancy_generator,
    list_occupancy_generators,
    occupancy_generator_varies,
    register_occupancy_generator,
    replication_report,
)
from repro.experiments import (
    FleetSpec,
    PolicySpec,
    Scenario,
    TrafficSpec,
    get_scenario,
    run_experiment,
)
from repro.provisioning import (
    MC_SCENARIO_FAMILY,
    EnsembleSpec,
    RiskConstraints,
    compose_rows,
    compose_site,
    plan_capacity,
    resolve_ensemble_budget,
    run_ensemble,
    run_ensemble_grid,
)

T_GRID = np.arange(0.0, 6 * 3600.0, 60.0)

SMALL = Scenario(
    name="prov-small",
    duration_s=1800.0,
    fleet=FleetSpec(n_provisioned=20, added_frac=0.30),
    policy=PolicySpec("polca"),
    traffic=TrafficSpec(occ_peak=0.9),
    budget="nominal",
    compare_to_reference=False,
)


# ------------------------------------------------------------- generators
def test_generator_family_registered():
    names = list_occupancy_generators()
    for expected in ("diurnal", "bursty", "colocated", "failover-surge",
                     "rack-incident", "nighttime"):
        assert expected in names


@pytest.mark.parametrize("name", ["bursty", "colocated", "failover-surge",
                                  "rack-incident", "nighttime"])
def test_generator_determinism_and_range(name):
    gen = get_occupancy_generator(name)
    a = gen(T_GRID, seed=11, peak=0.62)
    b = gen(T_GRID, seed=11, peak=0.62)
    c = gen(T_GRID, seed=12, peak=0.62)
    assert np.array_equal(a, b), "same seed must replay bit-identically"
    assert not np.array_equal(a, c), "different seeds must differ"
    assert a.shape == T_GRID.shape
    assert a.min() >= 0.05 - 1e-12 and a.max() <= 0.98 + 1e-12


def test_generator_rows_are_deterministic_per_row():
    gen = get_occupancy_generator("bursty")
    r0 = gen(T_GRID, seed=3, peak=0.62, n_rows=4, row=0, rho=0.5)
    r0b = gen(T_GRID, seed=3, peak=0.62, n_rows=4, row=0, rho=0.5)
    r1 = gen(T_GRID, seed=3, peak=0.62, n_rows=4, row=1, rho=0.5)
    assert np.array_equal(r0, r0b)
    assert not np.array_equal(r0, r1), "rows must decorrelate at rho<1"


@pytest.mark.parametrize("name", ["diurnal", "bursty", "colocated",
                                  "failover-surge", "rack-incident",
                                  "nighttime"])
def test_generator_curve_ignores_what_it_does_not_vary_with(name):
    """The batched lowering calls a generator once per value of what it
    declares its curve varies with: diurnal declares nothing, the families
    seed and row. A per-member argument left out of the declaration must
    leave the curve bit-identical."""
    varies = occupancy_generator_varies(name)
    assert varies == (frozenset() if name == "diurnal"
                      else frozenset({"seed", "row"}))
    gen = get_occupancy_generator(name)
    ref = gen(T_GRID, seed=3, peak=0.62, n_rows=4, row=1)
    if "seed" not in varies:
        assert np.array_equal(gen(T_GRID, seed=2**31 + 7, peak=0.62,
                                  n_rows=4, row=1), ref)
    if "row" not in varies:
        assert np.array_equal(gen(T_GRID, seed=3, peak=0.62, n_rows=4,
                                  row=3), ref)


def test_generator_registry_refuses_an_unknown_member_argument():
    with pytest.raises(ValueError, match="varies_with"):
        register_occupancy_generator("never-registered", lambda t, **kw: t,
                                     varies_with=("peak",))
    assert "never-registered" not in list_occupancy_generators()
    with pytest.raises(KeyError, match="never-registered"):
        occupancy_generator_varies("never-registered")


def test_rack_incident_zeroes_lost_rack_rows():
    gen = get_occupancy_generator("rack-incident")
    rows = [gen(T_GRID, seed=5, peak=0.62, n_rows=4, row=r, rows_per_rack=2)
            for r in range(4)]
    floors = [np.isclose(r, 0.05).mean() for r in rows]
    # exactly one rack (2 rows) sits at the idle floor during the incident
    assert sum(f > 0.2 for f in floors) == 2, floors


# ------------------------------------------------------------ composition
def test_compose_rows_correlation_extremes():
    base = get_occupancy_generator("diurnal")(T_GRID, seed=1, peak=0.62)
    sync = compose_rows(base, 3, rho=1.0, seed=9, t_grid=T_GRID)
    indep = compose_rows(base, 3, rho=0.0, seed=9, t_grid=T_GRID)
    assert np.array_equal(sync[0], sync[1]), "rho=1: rows identical"
    assert not np.array_equal(indep[0], indep[1]), "rho=0: rows differ"
    assert sync.shape == (3, len(T_GRID))


def test_compose_site_conservation_invariants():
    rng = np.random.default_rng(0)
    row_w = rng.uniform(10.0, 100.0, size=(6, 40))
    site = compose_site(row_w, rows_per_rack=2)
    assert site.rack_w.shape == (3, 40)
    for k in range(3):
        np.testing.assert_allclose(site.rack_w[k],
                                   row_w[site.rack_of == k].sum(axis=0),
                                   rtol=1e-12)
    np.testing.assert_allclose(site.site_w, row_w.sum(axis=0), rtol=1e-12)
    np.testing.assert_allclose(site.site_w, site.rack_w.sum(axis=0), rtol=1e-12)
    # the full per-node series is carried too (leaves, racks, root)
    assert site.node_w.shape == (6 + 3 + 1, 40)
    assert site.node_names[-1] == "cluster"


def test_compose_site_rejects_ragged_racks():
    """Regression: n_rows not divisible by rows_per_rack used to compose a
    silently mis-sized tail rack; it must raise a clear ValueError now."""
    row_w = np.ones((5, 16))
    with pytest.raises(ValueError, match="do not divide into racks"):
        compose_site(row_w, rows_per_rack=2)
    with pytest.raises(ValueError, match="rows_per_rack"):
        compose_site(np.ones((4, 8)), rows_per_rack=0)
    # an explicit hierarchy is the sanctioned escape hatch for ragged trees
    from repro.core.hierarchy import PowerHierarchy
    ragged = PowerHierarchy.two_level(np.ones(5), rows_per_rack=2)
    site = compose_site(row_w, hierarchy=ragged)
    assert site.rack_w.shape == (3, 16)
    np.testing.assert_allclose(site.site_w, row_w.sum(axis=0), rtol=1e-12)


# ---------------------------------------------------------------- registry
def test_mc_scenarios_registered_and_serializable():
    for name in MC_SCENARIO_FAMILY:
        sc = get_scenario(name)
        assert Scenario.from_json(sc.to_json()) == sc


# --------------------------------------------------------------- ensembles
def test_ensemble_determinism_and_worker_invariance():
    spec1 = EnsembleSpec(SMALL, n_seeds=3, seed0=700, n_workers=1)
    spec2 = EnsembleSpec(SMALL, n_seeds=3, seed0=700, n_workers=2)
    a, b, c = run_ensemble(spec1), run_ensemble(spec1), run_ensemble(spec2)
    for other in (b, c):
        assert np.array_equal(a.power_frac, other.power_frac)
        assert np.array_equal(a.brake_counts, other.brake_counts)
        for ma, mo in zip(a.members, other.members):
            assert ma.result.latencies == mo.result.latencies


def test_batched_bit_parity_with_sequential_run_experiment():
    """Acceptance: the batched engine reproduces a sequential
    ``run_experiment`` loop bit-for-bit on a 4-member ensemble."""
    spec = EnsembleSpec(SMALL, n_seeds=4, seed0=900, n_workers=2)
    ens = run_ensemble(spec)
    for m, sc in zip(ens.members, spec.member_scenarios(ens.budget_w)):
        o = run_experiment(sc)
        assert m.result.latencies == o.result.latencies
        assert np.array_equal(m.result.power_w, o.result.power_w)
        assert (m.result.n_brakes, m.result.cap_events, m.result.n_completed) \
            == (o.result.n_brakes, o.result.cap_events, o.result.n_completed)
        assert m.result.peak_power_frac == o.result.peak_power_frac


def test_batched_reference_mode_matches_run_experiment_stats():
    spec = EnsembleSpec(SMALL, n_seeds=2, seed0=900, n_workers=1,
                        with_reference=True)
    ens = run_ensemble(spec)
    for m, sc in zip(ens.members, spec.member_scenarios(ens.budget_w)):
        o = run_experiment(sc)
        assert m.result.latencies == o.result.latencies
        assert m.stats.summary() == o.stats.summary()
        assert m.meets == o.meets


def test_ensemble_distributional_telemetry():
    ens = run_ensemble(EnsembleSpec(SMALL, n_seeds=3, seed0=700, n_workers=1))
    counts, cdf = ens.brake_cdf()
    assert len(counts) == 3 and cdf[-1] == 1.0
    assert np.all(np.diff(cdf) >= 0)
    levels = [0.2, 0.6, 1.0]
    pe = ens.peak_exceedance(levels)
    pw = ens.power_exceedance(levels)
    for curve in (pe, pw):
        assert np.all(curve >= 0.0) and np.all(curve <= 1.0)
        assert np.all(np.diff(curve) <= 1e-12), "exceedance must be decreasing"
    assert 0.0 <= ens.brake_prob() <= 1.0
    assert ens.power_frac.shape[0] == 3


def test_ensemble_grid_groups_by_scenario():
    other = SMALL.with_(name="prov-small-nocap", policy=PolicySpec("no-cap"))
    out = run_ensemble_grid([SMALL, other], n_seeds=2, seed0=700, n_workers=2)
    assert set(out) == {"prov-small", "prov-small-nocap"}
    solo = run_ensemble(EnsembleSpec(SMALL, n_seeds=2, seed0=700, n_workers=1))
    assert np.array_equal(out["prov-small"].brake_counts, solo.brake_counts)
    assert np.array_equal(out["prov-small"].power_frac, solo.power_frac)


# ------------------------------------------------------------------ planner
def test_planner_monotonic_in_risk_constraints():
    """Acceptance: tighter risk bound -> fewer deployable servers."""
    base = SMALL.with_fleet(added_frac=0.0)
    kw = dict(n_seeds=2, seed0=810, max_added_frac=0.5, n_workers=2)
    loose = plan_capacity(base, constraints=RiskConstraints(
        max_brake_prob=1.0, max_slo_violation_prob=1.0), **kw)
    mid = plan_capacity(base, constraints=RiskConstraints(
        max_brake_prob=1.0, max_slo_violation_prob=1.0,
        slo=SLO(hp_p50=10.0, hp_p99=10.0, lp_p50=10.0, lp_p99=10.0)), **kw)
    tight = plan_capacity(base, constraints=RiskConstraints(
        max_brake_prob=0.0, max_slo_violation_prob=0.0), **kw)
    assert loose.capped and loose.safe_added_servers == 10
    assert tight.safe_added_servers <= mid.safe_added_servers
    assert mid.safe_added_servers <= loose.safe_added_servers
    assert tight.safe_added_servers < loose.safe_added_servers
    assert tight.probes, "planner must record its probes"
    assert tight.budget_w == pytest.approx(loose.budget_w)


def test_planner_monotonic_in_brake_budget():
    """Loosening the per-horizon brake-count budget (max_brakes) admits
    fleets at least as large, and a brake budget sits between zero-tolerance
    and unconstrained (ROADMAP open item: brake budgets, not just zero)."""
    # a budget tight enough that brake counts grow with the fleet (nominal
    # would never brake inside the search range)
    budget = 0.88 * 20 * SMALL.fleet.server().provisioned_w
    base = SMALL.with_fleet(added_frac=0.0).with_(budget=budget)
    slo_off = SLO(hp_p50=10.0, hp_p99=10.0, lp_p50=10.0, lp_p99=10.0,
                  max_powerbrakes=10**9)
    kw = dict(n_seeds=2, seed0=810, max_added_frac=0.5, n_workers=2,
              budget_w=budget)
    plans = [plan_capacity(base, constraints=RiskConstraints(
                 max_brakes=mb, slo=slo_off,
                 max_slo_violation_prob=1.0), **kw)
             for mb in (0, 20, 10**6)]
    sizes = [p.safe_added_servers for p in plans]
    assert sizes == sorted(sizes), f"brake budget must be monotone: {sizes}"
    assert plans[-1].capped and sizes[-1] == 10
    assert sizes[0] < sizes[-1], "zero-tolerance must bind on this envelope"
    assert all(p.probes for p in plans), "planner must record its probes"
    assert plans[1].budget_w == pytest.approx(plans[0].budget_w)
    # the underlying exceedance is monotone in the brake budget too
    ens = run_ensemble(EnsembleSpec(SMALL, n_seeds=3, seed0=810, n_workers=1))
    probs = [ens.brake_prob(k) for k in (0, 1, 5, 10**6)]
    assert probs == sorted(probs, reverse=True)
    assert probs[-1] == 0.0


def test_planner_reports_infeasible_at_zero():
    # a budget so tight even the provisioned fleet brakes
    base = SMALL.with_fleet(added_frac=0.0).with_(budget=1000.0)
    plan = plan_capacity(base, n_seeds=2, seed0=810, n_workers=1,
                         budget_w=1000.0)
    assert plan.safe_added_servers == 0 and not plan.feasible_at_zero


# ------------------------------------------------------------- cvar gate
HOT = SMALL.with_(power_scale=1.15, traffic=TrafficSpec(occ_peak=0.95))


def _dense_tail(n_seeds=64):
    return run_ensemble(EnsembleSpec(HOT, n_seeds=n_seeds, seed0=5),
                        engine="jax")


def test_cvar_monotone_in_alpha():
    """CVaR averages a shrinking worst-case tail, so it is nondecreasing in
    alpha — on brake counts and on the SLO-impact tail alike."""
    ens = _dense_tail()
    alphas = [0.0, 0.25, 0.5, 0.75, 0.9, 0.95]
    brake = [ens.brake_cvar(a) for a in alphas]
    slo = [ens.slo_cvar("low", a) for a in alphas]
    assert all(b2 >= b1 - 1e-12 for b1, b2 in zip(brake, brake[1:]))
    assert all(s2 >= s1 - 1e-12 for s1, s2 in zip(slo, slo[1:]))
    # alpha=0 degenerates to the plain mean
    np.testing.assert_allclose(ens.brake_cvar(0.0),
                               ens.brake_counts.mean(), rtol=1e-12)


def test_cvar_degenerates_to_max_as_alpha_approaches_one():
    """Once the (1 - alpha) tail holds <= 1 member, CVaR is the sample
    max — the max-brake / worst-member statistic."""
    ens = _dense_tail()
    n = ens.n_members
    alpha = 1.0 - 0.5 / n  # tail mass 0.5 member
    np.testing.assert_allclose(ens.brake_cvar(alpha),
                               float(ens.brake_counts.max()), rtol=0.0)
    per_member = [float(np.percentile(m.stats.lp_impacts, 99.0))
                  if len(m.stats.lp_impacts) else 0.0 for m in ens.members]
    np.testing.assert_allclose(ens.slo_cvar("low", alpha), max(per_member),
                               rtol=1e-12)
    with pytest.raises(ValueError):
        ens.brake_cvar(1.0)  # alpha must stay < 1


def test_planner_cvar_gate_infeasible_at_zero_on_dense_tail():
    """With a zero CVaR budget on a tail that has real LP capping impact,
    the dense-jax plan is infeasible even at zero added servers — the gate
    actually bites (other gates are opened wide so only CVaR can fail)."""
    ens = _dense_tail(n_seeds=16)
    assert ens.slo_cvar("low", 0.9) > 0.0  # the tail is genuinely loaded
    base = HOT.with_fleet(added_frac=0.0)
    # an envelope 20% under nominal: even the provisioned fleet caps LP
    tight = 0.8 * resolve_ensemble_budget(base)
    cons = RiskConstraints(max_brakes=10 ** 9, max_slo_violation_prob=1.0,
                           slo_cvar_alpha=0.9, max_slo_cvar=0.0,
                           slo_cvar_priority="low")
    plan = plan_capacity(base, n_seeds=16, seed0=5, engine="jax",
                         budget_w=tight, constraints=cons)
    assert plan.safe_added_servers == 0 and not plan.feasible_at_zero
    assert all(p.slo_cvar is not None and p.slo_cvar > 0.0
               for p in plan.probes)
    # loosening the CVaR budget past the observed tail re-admits the fleet
    loose = RiskConstraints(max_brakes=10 ** 9, max_slo_violation_prob=1.0,
                            slo_cvar_alpha=0.9, max_slo_cvar=1e9,
                            slo_cvar_priority="low")
    plan2 = plan_capacity(base, n_seeds=16, seed0=5, engine="jax",
                          budget_w=tight, constraints=loose)
    assert plan2.feasible_at_zero
    assert plan2.safe_added_servers >= plan.safe_added_servers


def test_planner_cvar_requires_enough_seeds():
    """alpha's tail must hold >= 1 full member: n_seeds >= 1 / (1 - alpha)."""
    with pytest.raises(ValueError, match="n_seeds >= 20"):
        plan_capacity(HOT, n_seeds=8, engine="jax",
                      constraints=RiskConstraints(slo_cvar_alpha=0.95))


def test_planner_survive_requires_numpy_engine():
    """The survivability gate rides the routed FleetSimulator, which the
    batched tick engines reject."""
    from repro.chaos.faults import FaultEvent, FaultSpec
    from repro.experiments.scenario import RoutingSpec

    routed = HOT.with_(routing=RoutingSpec(router="round-robin"))
    survive = FaultSpec(
        (FaultEvent("site-demand-response", t=600.0, factor=0.9,
                    until=1200.0),))
    with pytest.raises(ValueError, match="engine='numpy'"):
        plan_capacity(routed, n_seeds=4, engine="jax",
                      constraints=RiskConstraints(survive=survive))


# ---------------------------------------------------- speculative rounds
# (occ_peak, power_scale, n_seeds, lanes): lanes=None keeps the engine's
# own member block, so n_seeds alone sets how many candidates a round holds
ROUND_CASES = {
    "capped": (0.35, 0.90, 4, None),  # the top of the range is feasible
    "infeasible_at_zero": (0.99, 2.2, 4, None),
    "full_bisection": (0.90, 1.08, 4, None),  # all 13 candidates, 1 round
    "multi_round": (0.95, 1.00, 4, 3),  # 3 candidates a round, padded
    "one_candidate_a_round": (0.90, 1.08, 512, None),  # today's probes
}


def _sequential_plan(base, *, n_seeds, seed0, max_added_frac):
    """The bisection of ``plan_capacity`` over one ``run_ensemble`` per
    probed fleet, for constraints that admit a fleet where no member
    brakes. Returns (safe, capped, feasible_at_zero, [(k, ensemble)])."""
    n_prov = base.fleet.n_provisioned
    budget = resolve_ensemble_budget(base)
    path = []

    def feasible(k):
        sc = base.with_fleet(added_frac=k / n_prov).with_(budget=budget)
        ens = run_ensemble(EnsembleSpec(sc, n_seeds=n_seeds, seed0=seed0,
                                        with_reference=True),
                           budget_w=budget, engine="jax")
        path.append((k, ens))
        return ens.brake_prob(0) <= 1e-12

    hi = max(1, int(np.floor(n_prov * max_added_frac)))
    if feasible(hi):
        return hi, True, True, path
    if not feasible(0):
        return 0, False, False, path
    lo = 0
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if feasible(mid) else (lo, mid)
    return lo, False, True, path


@pytest.mark.parametrize("case", sorted(ROUND_CASES))
def test_planner_rounds_equal_the_sequential_search(case, monkeypatch):
    """plan_capacity(engine="jax") evaluates candidates in speculative
    rounds and replays the bisection over them: the decision, the probe
    path and every probe's ensemble are the sequential search's, bit for
    bit, whether a round holds the whole range, part of it, or one
    candidate (n_seeds >= the member block: no rounds at all)."""
    from conftest import parity_scenario
    from repro.obs.metrics import MetricsRecorder, recording
    from repro.provisioning import batched

    occ, scale, n_seeds, lanes = ROUND_CASES[case]
    if lanes is not None:
        monkeypatch.setattr(batched, "_AUTO_CHUNK_MEMBERS", lanes * n_seeds)
    base = parity_scenario(occ_peak=occ, power_scale=scale,
                           duration_s=1800.0, n_provisioned=20,
                           added_frac=0.0)
    kw = dict(n_seeds=n_seeds, seed0=42, max_added_frac=0.6)
    rec = MetricsRecorder()
    with recording(rec):
        plan = plan_capacity(base, engine="jax", keep_ensembles=True,
                             constraints=RiskConstraints(
                                 max_brakes=0, max_slo_violation_prob=1.0),
                             **kw)
    safe, capped, at_zero, path = _sequential_plan(base, **kw)
    assert (plan.safe_added_servers, plan.capped, plan.feasible_at_zero) == \
        (safe, capped, at_zero)
    assert [p.added_servers for p in plan.probes] == [k for k, _ in path]
    for p, (k, want) in zip(plan.probes, path):
        got = p.ensemble
        assert p.feasible == (want.brake_prob(0) <= 1e-12)
        assert p.brake_prob == want.brake_prob(0)
        assert p.slo_violation_prob == want.slo_violation_prob(DEFAULT_SLO)
        for name in ("brake_counts", "peak_fracs", "mean_fracs",
                     "power_t", "power_frac"):
            np.testing.assert_array_equal(getattr(got, name),
                                          getattr(want, name), err_msg=name)
        assert [(m.stats.hp_impacts, m.stats.lp_impacts)
                for m in got.members] == \
            [(m.stats.hp_impacts, m.stats.lp_impacts) for m in want.members]
    snap = rec.snapshot()
    want_rounds = {"capped": 1, "infeasible_at_zero": 1, "full_bisection": 1,
                   "multi_round": 3, "one_candidate_a_round": 0}[case]
    assert snap.counter_total("planner_rounds_total") == want_rounds
    assert snap.counter_total("planner_probes_total") == len(plan.probes)
    if case == "one_candidate_a_round":
        assert snap.counter_total("planner_candidates_total") == 0
    else:
        assert snap.counter_total("planner_candidates_total") >= \
            len(plan.probes)


def test_round_candidates_cover_the_bracket_nearest_first():
    from repro.provisioning.planner import _round_candidates

    # a bracket that fits a round is evaluated whole, ends first
    assert sorted(_round_candidates(0, 24, 64, first=True)) == \
        list(range(25))
    assert _round_candidates(0, 24, 64, first=True)[:5] == [24, 0, 12, 6, 18]
    # a later round: the top levels of the bracket, breadth first
    assert _round_candidates(6, 12, 3, first=False) == [9, 7, 10]
    assert _round_candidates(10, 12, 3, first=False) == [11]
    assert _round_candidates(0, 12, 2, first=True) == [12, 0]


# ---------------------------------------------------------------- traces
def test_replication_report_public_api():
    sc = get_scenario("table2-baseline").with_(duration_s=6 * 3600.0)
    res = run_experiment(sc).result
    from benchmarks.common import SERVER, bloom_workloads
    wls, shares = bloom_workloads()
    rep = replication_report(res.power_t, res.power_w, wls, shares, SERVER,
                             40, 40, occ_peak=sc.traffic.occ_peak,
                             duration_s=sc.duration_s)
    assert np.isfinite(rep.mape) and rep.mape >= 0.0
    assert len(rep.sim_smooth) == len(rep.target_smooth) > 0
