"""Differential-testing oracle harness for the batched ensemble engine.

DESIGN.md §15: the jax jit/vmap/`lax.scan` device program in
``provisioning/batched.py`` must reproduce the numpy tick oracle (which
drives the *real* ``PolcaPolicy``/``PredictivePolcaPolicy`` objects) exactly
— brake-tick sets bit-identical, power series within 1e-6 relative error,
planner decisions identical. Scenarios are property-sampled across the
generator family x hierarchy shape x policy x fault timeline axes; the
shared helpers live in ``tests/conftest.py``.

Durations are deliberately short (0.5 h = 900 ticks) so each drawn example
stays fast while still crossing T1/T2 and (at high ``power_scale``) the
brake threshold; every example still runs the full two-engine round trip.
"""

from typing import NamedTuple

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st  # real hypothesis in CI
from conftest import (
    PARITY_GENERATORS,
    PARITY_POWER_RTOL,
    assert_engine_parity,
    parity_scenario,
    run_both_engines,
)

from repro.chaos.faults import FaultEvent, FaultSpec
from repro.core.policy import PolcaPolicy
from repro.core.telemetry import Telemetry
from repro.experiments.scenario import HierarchySpec
from repro.provisioning.batched import (
    lower_ensemble,
    run_batched_ensemble,
    run_batched_grid,
    run_tick_model,
    split_run,
    stack_tick_models,
)
from repro.provisioning.montecarlo import (
    EnsembleSpec,
    resolve_ensemble_budget,
    run_ensemble,
    run_ensemble_grid,
)
from repro.provisioning.planner import RiskConstraints, plan_capacity

HALF_HOUR = 1800.0

generators = st.sampled_from(PARITY_GENERATORS)
occ_hot = st.floats(min_value=0.85, max_value=0.99)
scales_hot = st.floats(min_value=1.05, max_value=1.30)
seeds = st.integers(min_value=0, max_value=10_000)


# ---------------------------------------------------------------------------
# the oracle contract, property-sampled across scenario axes
# ---------------------------------------------------------------------------

@given(generators, occ_hot, scales_hot, seeds)
@settings(max_examples=6, deadline=None)
def test_brake_set_equality_across_generators(gen, occ, scale, seed0):
    """Brake-tick sets are BIT-identical for every generator family."""
    sc = parity_scenario(generator=gen, occ_peak=occ, power_scale=scale,
                         duration_s=HALF_HOUR)
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2, seed0=seed0)
    assert np.array_equal(oracle.brake_fire, jaxed.brake_fire)
    np.testing.assert_array_equal(oracle.n_brakes, jaxed.n_brakes)


@given(generators, occ_hot, seeds)
@settings(max_examples=6, deadline=None)
def test_power_series_within_tolerance(gen, occ, seed0):
    """Full power matrices (total, per-row) within 1e-6 relative error."""
    sc = parity_scenario(generator=gen, occ_peak=occ, duration_s=HALF_HOUR)
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2, seed0=seed0)
    np.testing.assert_allclose(jaxed.total_frac, oracle.total_frac,
                               rtol=PARITY_POWER_RTOL, atol=0.0)
    np.testing.assert_allclose(jaxed.row_w, oracle.row_w,
                               rtol=PARITY_POWER_RTOL, atol=0.0)


@given(generators, occ_hot, scales_hot)
@settings(max_examples=4, deadline=None)
def test_full_contract_parity(gen, occ, scale):
    """The whole oracle contract in one sweep (peaks, means, SLO impacts)."""
    sc = parity_scenario(generator=gen, occ_peak=occ, power_scale=scale,
                         duration_s=HALF_HOUR)
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2)
    assert_engine_parity(oracle, jaxed)


@given(generators, occ_hot, scales_hot, seeds)
@settings(max_examples=4, deadline=None)
def test_predictive_policy_parity(gen, occ, scale, seed0):
    """PredictivePolcaPolicy (EWMA window + 40 s OOB slope extrapolation +
    informed escalation) carried in scan state matches the real policy."""
    sc = parity_scenario(generator=gen, occ_peak=occ, power_scale=scale,
                         duration_s=HALF_HOUR, policy="polca-predictive")
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2, seed0=seed0)
    assert_engine_parity(oracle, jaxed)


@given(st.sampled_from([(2, 2), (2, 3), (3, 2)]), generators, seeds)
@settings(max_examples=4, deadline=None)
def test_hierarchy_node_fold_parity(shape, gen, seed0):
    """Hierarchy folds (segment-sum matmuls over the node matrix) match the
    oracle, and the site fold conserves the row total on both engines."""
    n_rows = shape[0] * shape[1]
    sc = parity_scenario(generator=gen, n_rows=n_rows, occ_peak=0.93,
                         duration_s=HALF_HOUR,
                         hierarchy=HierarchySpec(shape=shape,
                                                 budget_fracs={"0": 0.85}))
    model, oracle, jaxed = run_both_engines(sc, n_seeds=2, seed0=seed0)
    assert_engine_parity(oracle, jaxed)
    site = model.node_names.index("site")
    for run in (oracle, jaxed):
        np.testing.assert_allclose(run.node_w[:, :, site],
                                   run.row_w.sum(axis=2), rtol=1e-9)


@given(st.floats(min_value=0.5, max_value=0.9),
       st.integers(min_value=200, max_value=1100),
       st.booleans(), seeds)
@settings(max_examples=4, deadline=None)
def test_fault_timeline_parity(factor, t_fault, ramp, seed0):
    """Random fault timelines (interior derate with/without ramp, row
    crash/revive, site demand response) lower identically on both engines."""
    faults = FaultSpec((
        FaultEvent("node-derate", t=float(t_fault), node="pdu1",
                   factor=factor, until=float(t_fault + 600),
                   ramp_s=120.0 if ramp else 0.0),
        FaultEvent("row-crash", t=300.0, row=1),
        FaultEvent("row-revive", t=900.0, row=1),
        FaultEvent("site-demand-response", t=1200.0, factor=0.9,
                   until=1600.0),
    ))
    sc = parity_scenario(n_rows=4, occ_peak=0.95, duration_s=HALF_HOUR,
                         hierarchy=HierarchySpec(shape=(2, 2)), faults=faults)
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2, seed0=seed0)
    assert_engine_parity(oracle, jaxed)


# ---------------------------------------------------------------------------
# determinism + invariance properties
# ---------------------------------------------------------------------------

@given(generators, seeds)
@settings(max_examples=4, deadline=None)
def test_seed_determinism(gen, seed0):
    """Same spec -> bit-identical lowering and bit-identical jax results on
    repeat runs; a different seed0 changes the sampled occupancy."""
    sc = parity_scenario(generator=gen, duration_s=HALF_HOUR)
    spec = EnsembleSpec(sc, n_seeds=2, seed0=seed0)
    m1, mem1, _ = lower_ensemble(spec)
    m2, mem2, _ = lower_ensemble(spec)
    np.testing.assert_array_equal(m1.occ60, m2.occ60)
    np.testing.assert_array_equal(m1.alive, m2.alive)
    np.testing.assert_array_equal(m1.budget_scale, m2.budget_scale)
    r1 = run_tick_model(m1, mem1, engine="jax")
    r2 = run_tick_model(m2, mem2, engine="jax")
    np.testing.assert_array_equal(r1.total_frac, r2.total_frac)
    np.testing.assert_array_equal(r1.brake_fire, r2.brake_fire)
    m3, _, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=2, seed0=seed0 + 77))
    assert not np.array_equal(m1.occ60, m3.occ60)


@given(generators, seeds)
@settings(max_examples=3, deadline=None)
def test_member_batch_invariance(gen, seed0):
    """vmap independence: member m's series is bit-identical whether it runs
    in a batch of 4 or alone (no cross-member leakage in the device
    program)."""
    sc = parity_scenario(generator=gen, occ_peak=0.95, duration_s=HALF_HOUR)
    model, members, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=4,
                                                    seed0=seed0))
    full = run_tick_model(model, members, engine="jax")
    for m in (0, 3):
        import dataclasses
        solo_model = dataclasses.replace(model, n_members=1,
                                         occ60=model.occ60[m:m + 1],
                                         seeds=model.seeds[m:m + 1])
        solo = run_tick_model(solo_model, [members[m]], engine="jax")
        np.testing.assert_array_equal(solo.total_frac[0], full.total_frac[m])
        np.testing.assert_array_equal(solo.brake_fire[0], full.brake_fire[m])
        np.testing.assert_array_equal(solo.impacts_lp[0], full.impacts_lp[m])


def _occupancy_per_member_row(sc, seeds, t60, n_rows, n_servers):
    """The lowering's occupancy written out as one generator call, one
    jitter stream and one sigma, add and clip per member and row."""
    from repro.core.traces import get_occupancy_generator

    gen = get_occupancy_generator(sc.traffic.generator)
    occ = np.empty((len(seeds), n_rows, len(t60)))
    for mi, seed in enumerate(seeds):
        for r in range(n_rows):
            base = np.asarray(gen(t60, seed=int(seed), peak=sc.traffic.occ_peak,
                                  n_rows=n_rows, row=r,
                                  **sc.traffic.gen_params), dtype=np.float64)
            rng = np.random.default_rng([int(seed), r, 9173])
            sigma = np.sqrt(np.clip(base * (1.0 - base), 0.0, None) / n_servers)
            occ[mi, r] = np.clip(base + rng.standard_normal(len(t60)) * sigma,
                                 0.0, 1.0)
    return occ


def _bursty_varying_with(varies):
    """Bursty's curve, at the member's seed and row where ``varies`` names
    them and at seed 1, row 0 where it does not."""
    from repro.core.traces import get_occupancy_generator

    bursty = get_occupancy_generator("bursty")

    def gen(t, *, seed=1, row=0, **kw):
        return bursty(t, seed=seed if "seed" in varies else 1,
                      row=row if "row" in varies else 0, **kw)

    return gen


# (generator, declared varies_with or None for a registered family, rows,
#  generator calls a cold lowering of 5 members makes)
_OCC_CASES = [
    ("diurnal", None, 1, 1),
    ("diurnal", None, 56, 1),
    ("bursty", None, 2, 10),
    ("seed-only", ("seed",), 3, 5),
    ("row-only", ("row",), 3, 3),
]


@pytest.mark.parametrize("gen,varies,n_rows,curves", _OCC_CASES,
                         ids=[f"{c[0]}-{c[2]}" for c in _OCC_CASES])
def test_member_occupancy_equals_the_per_member_row_loop(gen, varies, n_rows,
                                                         curves, monkeypatch):
    """The lowering's occupancy is bit-equal to a per-member-row loop for
    each declaration a generator can make: a curve that varies with neither
    seed nor row (diurnal), with both (bursty), with the seed alone or with
    the row alone. A cold cache calls the generator once per distinct curve;
    a warm one, not at all."""
    from repro.core import traces
    from repro.obs.metrics import MetricsRecorder, recording
    from repro.provisioning import batched

    monkeypatch.setattr(batched, "_BASE_OCC_CACHE", {})
    if varies is not None:
        monkeypatch.setitem(
            traces._OCC_GENERATORS, gen,
            (_bursty_varying_with(varies), frozenset(varies)))
    sc = parity_scenario(generator=gen, occ_peak=0.62)
    t60 = np.arange(0.0, 3 * 3600.0, 60.0)
    seeds = [7, 2**31 + 11, 40_000_001, 3, 2**32 + 5]
    want = _occupancy_per_member_row(sc, seeds, t60, n_rows, 26)
    for calls in (curves, 0):
        rec = MetricsRecorder()
        with recording(rec):
            got = batched._member_occupancy(sc, seeds, t60, n_rows, 26)
        assert got.shape == want.shape and np.array_equal(got, want)
        assert rec.snapshot().counter_total(
            "batched_occupancy_curves_total") == calls
    assert not np.array_equal(got[0], got[1])


def test_lowering_rejects_routed_and_short_scenarios():
    from repro.experiments.scenario import RoutingSpec

    sc = parity_scenario(duration_s=HALF_HOUR)
    routed = sc.with_(routing=RoutingSpec(router="round-robin"))
    with pytest.raises(ValueError, match="engine='numpy'"):
        lower_ensemble(EnsembleSpec(routed, n_seeds=2))
    with pytest.raises(ValueError, match="duration"):
        lower_ensemble(EnsembleSpec(sc.with_(duration_s=60.0), n_seeds=2))


# ---------------------------------------------------------------------------
# EnsembleResult statistic parity + planner decisions
# ---------------------------------------------------------------------------

@given(generators, occ_hot, seeds)
@settings(max_examples=3, deadline=None)
def test_ensemble_result_statistic_parity(gen, occ, seed0):
    """run_ensemble(engine='jax') and the tick oracle produce matching
    EnsembleResult statistics end to end (summary dict, CDFs, CVaRs)."""
    sc = parity_scenario(generator=gen, occ_peak=occ, duration_s=HALF_HOUR)
    spec = EnsembleSpec(sc, n_seeds=4, seed0=seed0)
    a = run_ensemble(spec, engine="jax")
    b = run_ensemble(spec, engine="batched-numpy")
    np.testing.assert_array_equal(a.brake_counts, b.brake_counts)
    np.testing.assert_allclose(a.peak_fracs, b.peak_fracs,
                               rtol=PARITY_POWER_RTOL)
    np.testing.assert_allclose(a.mean_fracs, b.mean_fracs,
                               rtol=PARITY_POWER_RTOL)
    np.testing.assert_allclose(a.power_frac, b.power_frac,
                               rtol=PARITY_POWER_RTOL)
    sa, sb = a.summary(), b.summary()
    assert sa.keys() == sb.keys()
    for k in sa:
        np.testing.assert_allclose(sa[k], sb[k], rtol=1e-6, atol=1e-9,
                                   err_msg=f"summary[{k}] differs")
    for alpha in (0.0, 0.5, 0.75):
        np.testing.assert_allclose(a.brake_cvar(alpha), b.brake_cvar(alpha),
                                   rtol=1e-9, atol=0.0)
        np.testing.assert_allclose(a.slo_cvar("low", alpha),
                                   b.slo_cvar("low", alpha),
                                   rtol=1e-6, atol=1e-12)


def test_planner_decisions_identical_across_engines():
    """plan_capacity lands on the same safe_added_servers with the same
    per-probe feasibility verdicts on both batched engines."""
    sc = parity_scenario(occ_peak=0.95, duration_s=HALF_HOUR,
                         n_provisioned=10, added_frac=0.0)
    cons = RiskConstraints(max_brakes=0, max_slo_violation_prob=1.0,
                           slo_cvar_alpha=0.5, max_slo_cvar=2.0,
                           slo_cvar_priority="low")
    plans = {eng: plan_capacity(sc, n_seeds=4, seed0=42, engine=eng,
                                constraints=cons, max_added_frac=0.4)
             for eng in ("jax", "batched-numpy")}
    a, b = plans["jax"], plans["batched-numpy"]
    assert a.safe_added_servers == b.safe_added_servers
    assert [(p.added_servers, p.feasible) for p in a.probes] == \
        [(p.added_servers, p.feasible) for p in b.probes]
    for pa, pb in zip(a.probes, b.probes):
        np.testing.assert_allclose(pa.brake_prob, pb.brake_prob)
        np.testing.assert_allclose(pa.slo_cvar, pb.slo_cvar, rtol=1e-6)


@pytest.mark.parametrize("engine", ["jax", "numpy"])
def test_stacked_candidates_split_back_to_their_own_runs(engine):
    """The candidate fleets of one decision stacked on the member axis and
    run once: each candidate's slice is its own run, bit for bit, on the
    device program and on the oracle."""
    sc = parity_scenario(occ_peak=0.97, power_scale=1.2,
                         duration_s=HALF_HOUR, n_provisioned=10,
                         added_frac=0.0, policy="polca-predictive")
    budget = resolve_ensemble_budget(sc)
    lowered = [lower_ensemble(EnsembleSpec(sc.with_fleet(added_frac=k / 10),
                                           n_seeds=3, seed0=11),
                              budget_w=budget) for k in (6, 0, 3)]
    models = [m for m, _, _ in lowered]
    stacked = stack_tick_models(models)
    assert stacked.n_members == 9
    np.testing.assert_array_equal(stacked.servers(),
                                  np.repeat([16.0, 10.0, 13.0], 3))
    run = run_tick_model(stacked, [s for _, mem, _ in lowered for s in mem],
                         engine=engine)
    assert run.n_brakes.sum() > 0, "the stack should brake"
    for (model, members, _), got in zip(lowered, split_run(run, models)):
        assert got.model is model
        want = run_tick_model(model, members, engine=engine)
        for name in ("brake_fire", "n_brakes", "peak_frac", "mean_frac",
                     "impacts_hp", "impacts_lp", "total_frac", "row_w"):
            np.testing.assert_array_equal(getattr(got, name),
                                          getattr(want, name), err_msg=name)


def test_stacking_refuses_models_that_differ_beyond_members():
    sc = parity_scenario(duration_s=HALF_HOUR)
    a, _, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=2), budget_w=1e6)
    b, _, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=2), budget_w=2e6)
    with pytest.raises(ValueError, match="row_budget_w"):
        stack_tick_models([a, b])


def test_runner_reads_both_interpolation_weights_from_the_host():
    """Each tick's two occupancy weights, ``1 - w`` and ``w``, are operands
    computed on the host: the loop subtracts nothing from 1.0, since a TPU's
    emulated float64 kept ``1.0 - w`` to about float32 precision."""
    from repro.provisioning import batched

    sc = parity_scenario(duration_s=HALF_HOUR)
    model, _, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=2, seed0=5))
    operands = batched._bucket_operands([model], np.arange(2))
    _, w = batched._interp_weights(model)
    np.testing.assert_array_equal(operands[5], np.stack([1.0 - w, w], 1))
    assert np.any((w > 0.0) & (w < 0.5))


def test_brakes_actually_fire_and_match():
    """The harness demonstrably covers the brake path: at power_scale=1.30
    the fleet must brake, and the brake-tick sets still match bit-for-bit."""
    sc = parity_scenario(occ_peak=0.99, power_scale=1.30,
                         duration_s=HALF_HOUR)
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2)
    assert oracle.n_brakes.sum() > 0, "scenario failed to exercise brakes"
    assert np.array_equal(oracle.brake_fire, jaxed.brake_fire)
    assert_engine_parity(oracle, jaxed)


# ---------------------------------------------------------------------------
# clock factors: each row's f ** gamma read from the scenario's table
# ---------------------------------------------------------------------------

# the policy's default clocks, clocks of its own, and two that coincide
CLOCK_POLICIES = {
    "default": {},
    "own": dict(lp_freq_t1=0.93, lp_freq_t2=0.81, hp_freq_t2=0.88,
                brake_freq=0.3),
    "lp_t2_is_brake": dict(lp_freq_t2=0.75, brake_freq=0.75),
}


@pytest.mark.parametrize("params", CLOCK_POLICIES.values(),
                         ids=CLOCK_POLICIES.keys())
def test_step_clock_factors_equal_numpy_pow_bit_for_bit(params):
    """For every clock a row can hold (1.0, lp_t1, lp_t2, hp_t2 and the
    brake clock), the step's factor equals numpy's ``f ** gamma`` — the
    oracle's — bit for bit, also where two clocks coincide."""
    import jax
    import jax.numpy as jnp

    from repro.provisioning import batched

    sc = parity_scenario(duration_s=HALF_HOUR).with_policy("polca", **params)
    model, _, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=2))
    consts = jax.tree.map(lambda a: a[0],
                          batched._bucket_operands([model], np.arange(2))[2])
    clocks = np.array([1.0, model.lp_freq_t1, model.lp_freq_t2,
                       model.hp_freq_t2, model.brake_freq])
    if params:
        assert model.lp_freq_t2 == params["lp_freq_t2"]
    with jax.enable_x64(True):
        g_lp, g_hp = jax.jit(lambda c, f: batched._clock_factors(
            c, f, f, jnp))(consts, jnp.asarray(clocks))
    want = clocks ** model.gamma
    lp, hp = [0, 1, 2, 4], [0, 3, 4]  # the clocks each priority can hold
    np.testing.assert_array_equal(np.asarray(g_lp)[lp], want[lp])
    np.testing.assert_array_equal(np.asarray(g_hp)[hp], want[hp])


def test_predictive_brakes_fire_and_match():
    """The brake-covering scenario of
    :func:`test_brakes_actually_fire_and_match` under the predictive
    policy: its clocks come from the same table, and brake sets and power
    still match the oracle."""
    sc = parity_scenario(occ_peak=0.99, power_scale=1.30,
                         duration_s=HALF_HOUR, policy="polca-predictive")
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2)
    assert oracle.n_brakes.sum() > 0, "scenario failed to exercise brakes"
    assert_engine_parity(oracle, jaxed)


@pytest.mark.parametrize("site", [False, True], ids=["one_row", "site56"])
def test_scan_program_computes_power_only_at_one_row(site):
    """Programs of two rows or more read their clock factors from the
    table and lower no ``power`` op (the 56-row site's); a one-row program
    computes ``f ** gamma`` (the row40 cells')."""
    import jax

    from repro.experiments import get_scenario
    from repro.provisioning import batched

    assert batched._CLOCK_TABLE_MIN_ROWS == 2
    sc = (get_scenario("site56").with_(duration_s=HALF_HOUR) if site
          else parity_scenario(n_rows=1, duration_s=HALF_HOUR))
    model, _, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=2))
    assert model.n_rows == (56 if site else 1)
    assert bool(model.node_shape) == site
    cfg, mesh, idx = batched._plan_bucket(
        [model], keep_series=False, keep_fire=False, member_chunk=None,
        mesh=None)
    assert cfg.clock_table == site
    with jax.enable_x64(True):
        text = batched._jax_runner(cfg, mesh).lower(
            *batched._bucket_operands([model], idx)).as_text()
    assert "stablehlo.while" in text
    assert ("stablehlo.power" in text) != site


def test_quiet_scenario_is_quiet_on_both_engines():
    """Low occupancy: no brakes, no caps biting, ~zero SLO impact — and the
    engines agree exactly."""
    sc = parity_scenario(occ_peak=0.35, power_scale=0.9,
                         duration_s=HALF_HOUR)
    _, oracle, jaxed = run_both_engines(sc, n_seeds=2)
    for run in (oracle, jaxed):
        assert run.n_brakes.sum() == 0
        assert run.peak_frac.max() < 1.0
        assert np.abs(run.impacts_hp).max() < 1e-9
    assert_engine_parity(oracle, jaxed)


# ---------------------------------------------------------------------------
# the scan's latch step, against the policy objects it vectorizes
# ---------------------------------------------------------------------------

class _LatchConsts(NamedTuple):
    """The ``_Consts`` fields the latch step reads."""

    t1: float = 0.90
    t2: float = 0.97
    t1_buf: float = 0.02
    t2_buf: float = 0.02
    lp_t1: float = 0.85
    lp_t2: float = 0.70
    hp_t2: float = 0.85


def _run_latch_step(power, esc, c=_LatchConsts()):
    """Drive ``batched.polca_latch_step`` as the scan does (jax, float64)
    over a ``[T, N, R]`` power trace; returns the per-tick fire and command
    planes and the final latches, as numpy."""
    import jax
    import jax.numpy as jnp

    from repro.provisioning import batched

    with jax.enable_x64(True):
        step = jax.jit(lambda lat, p, c: batched.polca_latch_step(
            lat, p, p, 0.4 * p, c, jnp, esc=esc, predictive=False))
        shape = power.shape[1:]
        lat = batched.PolcaLatches(
            *(jnp.zeros(shape, bool) for _ in range(4)),
            t2s=jnp.zeros(shape, jnp.int32))
        planes = []
        for p in power:
            lat, fire, lp_cmd, hp_cmd = step(lat, jnp.asarray(p), c)
            planes.append([np.asarray(a) for a in (fire, lp_cmd, hp_cmd)])
    fire, lp_cmd, hp_cmd = (np.stack(a) for a in zip(*planes))
    return fire, lp_cmd, hp_cmd, [np.asarray(a) for a in lat]


LATCH_CASES = [
    # (N, T, R, esc, power_scale)
    (8, 96, 2, 25, 1.10),
    (5, 96, 2, 25, 1.10),
    (13, 64, 3, 25, 1.18),  # hot: brakes fire
    (3, 48, 1, 4, 1.05),    # fast escalation
    (16, 32, 2, 25, 0.95),  # cool: mostly uncapped
]


@pytest.mark.parametrize("case", LATCH_CASES,
                         ids=lambda c: f"n{c[0]}t{c[1]}r{c[2]}e{c[3]}ps{c[4]}")
def test_latch_step_matches_policy_observe(case):
    """Each tick, the latch step's brake firings and LP/HP command planes
    equal N x R real ``PolcaPolicy`` objects fed the same power through
    ``Telemetry``, bit for bit: a plane is NaN where the policy issues no
    command, else its command list folded field by field, later commands
    overwriting earlier ones (brake commands are the fire plane)."""
    N, T, R, esc, ps = case
    rng = np.random.default_rng(N * 1000 + T)
    k = np.arange(T)[:, None, None]
    wave = 0.87 + 0.09 * np.sin(2 * np.pi * k / rng.uniform(20, 60, (N, R))
                                + rng.uniform(0, 2 * np.pi, (N, R)))
    power = ps * (wave + rng.normal(0.0, 0.01, (T, N, R)))  # [T, N, R]
    fire, lp_cmd, hp_cmd, _ = _run_latch_step(power, esc)

    c = _LatchConsts()
    policies = [[PolcaPolicy(t1=c.t1, t2=c.t2, t1_buffer=c.t1_buf,
                             t2_buffer=c.t2_buf, lp_freq_t1=c.lp_t1,
                             lp_freq_t2=c.lp_t2, hp_freq_t2=c.hp_t2,
                             escalation_ticks=esc)
                 for _ in range(R)] for _ in range(N)]
    want_fire = np.zeros((T, N, R), bool)
    want_lp = np.full((T, N, R), np.nan)
    want_hp = np.full((T, N, R), np.nan)
    for t in range(T):
        for n in range(N):
            for r in range(R):
                pol = policies[n][r]
                before = pol.n_brakes
                cmds = pol.observe(Telemetry(
                    t=2.0 * (t + 1), power_frac=float(power[t, n, r]),
                    lp_power_frac=float(0.4 * power[t, n, r]), row_index=r))
                want_fire[t, n, r] = pol.n_brakes > before
                for cmd in cmds:
                    if cmd.brake:
                        continue
                    if cmd.lp_freq is not None:
                        want_lp[t, n, r] = cmd.lp_freq
                    if cmd.hp_freq is not None:
                        want_hp[t, n, r] = cmd.hp_freq
    np.testing.assert_array_equal(fire, want_fire)
    np.testing.assert_array_equal(lp_cmd, want_lp)
    np.testing.assert_array_equal(hp_cmd, want_hp)
    assert np.isfinite(want_lp).any(), "the trace issued no command"


def test_latch_step_fires_and_releases_brakes():
    """A hand-worked trace on row 0 (row 1 stays idle): a brake fires once
    per overload, its release falls back to the T2 caps, and a drop below
    T1 - buffer releases T2 and then T1 in the same tick."""
    power = np.array([0.5, 0.5, 1.05, 1.05, 0.99, 0.5, 1.02, 0.5])
    power = np.stack([power, np.full(8, 0.5)], axis=1)[:, None, :]  # [T, 1, 2]
    fire, lp_cmd, hp_cmd, latches = _run_latch_step(power, esc=25)
    nan = np.nan
    np.testing.assert_array_equal(np.flatnonzero(fire[:, 0, 0]), [2, 6])
    np.testing.assert_array_equal(
        lp_cmd[:, 0, 0], [nan, nan, nan, nan, 0.70, 1.0, nan, 1.0])
    np.testing.assert_array_equal(
        hp_cmd[:, 0, 0], [nan, nan, nan, nan, 0.85, 1.0, nan, 1.0])
    assert not fire[:, 0, 1].any()
    assert np.isnan(lp_cmd[:, 0, 1]).all() and np.isnan(hp_cmd[:, 0, 1]).all()
    for a in latches:
        assert not a.any()


# every entry that takes an engine name, given one that does not exist
REFUSING_ENTRIES = {
    "run_tick_model": lambda spec: run_tick_model(
        *lower_ensemble(spec)[:2], engine="pallas"),
    "run_batched_ensemble": lambda spec: run_batched_ensemble(
        spec, engine="pallas"),
    "run_batched_grid": lambda spec: run_batched_grid([spec],
                                                      engine="pallas"),
    "run_ensemble": lambda spec: run_ensemble(spec, engine="pallas"),
    "run_ensemble_grid": lambda spec: run_ensemble_grid(
        [spec.base], n_seeds=2, engine="pallas"),
}


@pytest.mark.parametrize("entry", REFUSING_ENTRIES.values(),
                         ids=REFUSING_ENTRIES.keys())
def test_removed_engine_is_refused(entry):
    spec = EnsembleSpec(parity_scenario(duration_s=600.0), n_seeds=2)
    with pytest.raises(ValueError, match="engine 'pallas'") as err:
        entry(spec)
    assert "'numpy'" in str(err.value) and "'jax'" in str(err.value)


# ---------------------------------------------------------------------------
# dense tails
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dense_tail_10k_members():
    """10^4-member tail smoke: the jax engine completes a full ensemble in
    one device program and its statistics are sane. (The same tail is
    PASS-gated with throughput in benchmarks/batched_engine.py.)"""
    sc = parity_scenario(occ_peak=0.97, power_scale=1.15,
                         duration_s=HALF_HOUR)
    res = run_batched_ensemble(EnsembleSpec(sc, n_seeds=10_000, seed0=1),
                               engine="jax", keep_series=False)
    assert res.n_members == 10_000
    assert res.power_frac.size == 0  # series dropped above the cell limit
    assert np.isfinite(res.peak_fracs).all()
    assert 0.0 <= res.brake_prob() <= 1.0
    assert res.brake_cvar(0.999) >= res.brake_cvar(0.9) >= res.brake_cvar(0.0)
    tail = res.slo_cvar("low", 0.999)
    assert np.isfinite(tail)
