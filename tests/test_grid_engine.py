"""Grid-engine semantics (DESIGN.md §16): scenario-axis vmap, member
chunking, device sharding, dense-tail statistics, and compile-count reuse.

The contract under test is *bit*-identity, not closeness: the grid program,
the chunked program, and the sharded program are the same computation graph
over the same float64 operands, so XLA must produce identical bits — any
drift means the lowering changed the math, exactly what these properties
exist to catch.
"""

import numpy as np
import pytest

from conftest import (
    PARITY_GENERATORS,
    assert_engine_parity,
    parity_scenario,
    run_both_engines,
)
from repro.launch.mesh import data_mesh
from repro.provisioning.batched import (
    jax_trace_count,
    lower_ensemble,
    run_batched_ensemble,
    run_batched_grid,
    run_tick_model,
)
from repro.provisioning.montecarlo import (
    EnsembleSpec,
    run_ensemble,
    run_ensemble_grid,
)
from repro.provisioning.planner import plan_capacity

GRID_GENERATORS = ("diurnal", "bursty", "colocated", "nighttime")


def _grid_specs(n_seeds=4):
    return [EnsembleSpec(parity_scenario(generator=g), n_seeds=n_seeds)
            for g in GRID_GENERATORS]


def _assert_results_identical(a, b):
    assert a.base_name == b.base_name
    np.testing.assert_array_equal(a.brake_counts, b.brake_counts)
    np.testing.assert_array_equal(a.peak_fracs, b.peak_fracs)
    np.testing.assert_array_equal(a.mean_fracs, b.mean_fracs)
    np.testing.assert_array_equal(a.power_frac, b.power_frac)


def test_grid_bit_identical_to_per_scenario_loop_and_one_trace():
    """M scenarios sharing tick geometry: one vmapped program, results
    bit-identical to M independent run_ensemble calls."""
    specs = _grid_specs()
    t0 = jax_trace_count()
    grid = run_batched_grid(specs, engine="jax")
    assert jax_trace_count() - t0 == 1, (
        "a same-geometry grid must lower to ONE traced program")
    loop = [run_ensemble(s, engine="jax") for s in specs]
    for g, l in zip(grid, loop):
        _assert_results_identical(g, l)


def test_run_ensemble_grid_jax_dispatch():
    """montecarlo.run_ensemble_grid(engine='jax') routes to the batched grid
    and keys results by base name, same numbers as run_ensemble."""
    bases = [parity_scenario(generator=g) for g in GRID_GENERATORS[:2]]
    out = run_ensemble_grid(bases, n_seeds=3, engine="jax")
    assert set(out) == {b.name for b in bases}
    for b in bases:
        single = run_ensemble(EnsembleSpec(b, n_seeds=3), engine="jax")
        _assert_results_identical(out[b.name], single)


def test_grid_scenarios_with_their_own_clocks_match_their_own_runs():
    """Two policies whose clocks differ share one geometry bucket: each
    scenario's clock factors ride the scenario vmap as ``[M]`` leaves, so
    each result equals the scenario's own run bit for bit."""
    from repro.provisioning.batched import _geometry_key

    hot = parity_scenario(occ_peak=0.99, power_scale=1.30,
                          duration_s=1800.0)
    specs = [EnsembleSpec(hot, n_seeds=3),
             EnsembleSpec(hot.with_policy(
                 "polca", lp_freq_t1=0.93, lp_freq_t2=0.81, hp_freq_t2=0.88,
                 brake_freq=0.3).with_(name="parity-own-clocks"),
                 n_seeds=3)]
    models = [lower_ensemble(s)[0] for s in specs]
    assert _geometry_key(models[0]) == _geometry_key(models[1])
    assert models[0].lp_freq_t2 != models[1].lp_freq_t2
    grid = run_batched_grid(specs, engine="jax")
    for g, s in zip(grid, specs):
        _assert_results_identical(g, run_batched_ensemble(s, engine="jax"))
    assert grid[0].brake_counts.sum() > 0
    assert not np.array_equal(grid[0].power_frac, grid[1].power_frac)


@pytest.mark.parametrize("chunk", [3, 5, 12])
def test_member_chunk_invariance(chunk):
    """Chunked lax.scan over member blocks (including a non-dividing chunk,
    which pads with cyclic members and slices back) is bit-identical to the
    flat vmap."""
    spec = EnsembleSpec(parity_scenario(generator="bursty"), n_seeds=12)
    flat = run_ensemble(spec, engine="jax")
    chunked = run_ensemble(spec, engine="jax", member_chunk=chunk)
    _assert_results_identical(flat, chunked)


@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_device_count_invariance(n_dev):
    """shard_map over the forced host-CPU 'data' axis: 1 vs N devices give
    identical bits (the member axis is embarrassingly parallel)."""
    spec = EnsembleSpec(parity_scenario(generator="diurnal"), n_seeds=8)
    base = run_ensemble(spec, engine="jax")
    sharded = run_ensemble(spec, engine="jax", mesh=data_mesh(n_dev))
    _assert_results_identical(base, sharded)


def test_sharded_and_chunked_compose():
    spec = EnsembleSpec(parity_scenario(generator="colocated"), n_seeds=10)
    base = run_ensemble(spec, engine="jax")
    both = run_ensemble(spec, engine="jax", mesh=data_mesh(4), member_chunk=2)
    _assert_results_identical(base, both)


@pytest.mark.parametrize("lanes", [None, 3])
def test_plan_capacity_probe_count_does_not_multiply_compiles(lanes,
                                                              monkeypatch):
    """Per-scenario scalars and fleet sizes are traced operands, so a whole
    decision (fleet size varies, budget pinned) compiles at most once, and
    runs one scan per speculative round: all candidates in one round, or
    (3 candidates a round) every round padded to the first one's shape."""
    from repro.obs.metrics import MetricsRecorder, recording
    from repro.provisioning import batched

    if lanes is not None:
        monkeypatch.setattr(batched, "_AUTO_CHUNK_MEMBERS", lanes * 4)
    scans = []
    orig = batched.run_tick_model

    def counting(model, *a, **kw):
        scans.append(model.n_members)
        return orig(model, *a, **kw)
    monkeypatch.setattr(batched, "run_tick_model", counting)
    sc = parity_scenario(generator="diurnal")
    t0 = jax_trace_count()
    rec = MetricsRecorder()
    with recording(rec):
        plan = plan_capacity(sc, n_seeds=4, engine="jax")
    assert len(plan.probes) >= 3, "bisection too shallow to regression-test"
    assert jax_trace_count() - t0 <= 1, (
        f"{len(plan.probes)} probes retraced the engine "
        f"{jax_trace_count() - t0} times; scalar consts leaked back into "
        "the jit cache key")
    rounds = rec.snapshot().counter_total("planner_rounds_total")
    assert len(scans) == rounds  # one scan per round
    if lanes is None:
        assert rounds == 1
    else:
        assert rounds > 1 and scans == [lanes * 4] * len(scans)


@pytest.mark.parametrize("generator", PARITY_GENERATORS)
def test_scan_engine_parity(generator):
    """The scan engine satisfies the oracle contract on each generator
    family's harness scenario: brake sets bit-identical, power within 1e-6
    relative."""
    _, oracle, jaxed = run_both_engines(parity_scenario(generator=generator),
                                        n_seeds=3)
    assert jaxed.engine == "jax"
    assert_engine_parity(oracle, jaxed)


def test_plan_capacity_rounds_invariant_to_mesh():
    """A round's stacked members shard over the "data" mesh, each with its
    own fleet size, padded to the device multiple: the decision and every
    probe's numbers equal the one-device plan's."""
    sc = parity_scenario(generator="diurnal", n_provisioned=9)
    one = plan_capacity(sc, n_seeds=3, engine="jax", keep_ensembles=True)
    four = plan_capacity(sc, n_seeds=3, engine="jax", keep_ensembles=True,
                         mesh=data_mesh(4))
    assert four.safe_added_servers == one.safe_added_servers
    assert [p.added_servers for p in four.probes] == \
        [p.added_servers for p in one.probes]
    for a, b in zip(four.probes, one.probes):
        _assert_results_identical(a.ensemble, b.ensemble)


def test_dense_member_stats_equivalent():
    """member_stats=False drops the per-member python objects but every
    distributional statistic must return the same numbers."""
    spec = EnsembleSpec(parity_scenario(generator="bursty"), n_seeds=12)
    rich = run_batched_ensemble(spec, engine="jax", member_stats=True)
    dense = run_batched_ensemble(spec, engine="jax", member_stats=False)
    assert rich.n_members == dense.n_members == 12
    assert len(dense.members) == 0 and dense.member_impacts_hp is not None
    for prio in ("high", "low"):
        np.testing.assert_array_equal(rich.slo_impacts(prio),
                                      dense.slo_impacts(prio))
        for q in (50.0, 99.0):
            assert rich.slo_percentile(prio, q) == dense.slo_percentile(prio, q)
        for alpha in (0.0, 0.5, 0.9):
            assert rich.slo_cvar(prio, alpha) == dense.slo_cvar(prio, alpha)
    assert rich.meets_fraction() == dense.meets_fraction()
    assert rich.slo_violation_prob() == dense.slo_violation_prob()
    assert rich.summary() == dense.summary()


def test_keep_brake_fire_false_drops_plane_keeps_counts():
    spec = EnsembleSpec(parity_scenario(generator="diurnal"), n_seeds=3)
    model, members, _ = lower_ensemble(spec)
    full = run_tick_model(model, members, engine="jax")
    lean = run_tick_model(model, members, engine="jax", keep_brake_fire=False)
    assert lean.brake_fire is None
    np.testing.assert_array_equal(full.n_brakes, lean.n_brakes)
    with pytest.raises(ValueError, match="keep_brake_fire"):
        lean.brake_ticks()


def test_engine_opts_rejected_on_event_driven_engine():
    spec = EnsembleSpec(parity_scenario(generator="diurnal"), n_seeds=2)
    with pytest.raises(ValueError, match="engine options"):
        run_ensemble(spec, engine="numpy", member_chunk=4)
