"""Observability subsystem: zero-perturbation recording, exact
reconciliation, deterministic export (DESIGN.md §14).

Covers the hard guarantees end to end: recorder-on vs recorder-off
bit-parity on routed-fleet / tree-controller / chaos / Monte-Carlo runs,
brake-edge events reconciling exactly with ``braked_series``, ensemble
traces invariant to the worker count, histogram snapshot/merge algebra,
Prometheus + JSONL + manifest round-trips, the ``--only`` benchmark
selector, the artifact report renderer, and the shared launcher logging."""

import io
import json
import logging
import os

import numpy as np
import pytest

from repro.chaos import FaultEvent, FaultSpec
from repro.experiments import (
    ControllerSpec,
    FleetSpec,
    HierarchySpec,
    PolicySpec,
    RoutingSpec,
    Scenario,
    TrafficSpec,
    run_experiment,
)
from repro.obs.export import (
    EVENTS_NAME,
    METRICS_NAME,
    event_lines,
    prometheus_text,
    read_events,
    read_manifest,
    read_prometheus,
    run_manifest,
    write_artifacts,
    write_events,
)
from repro.obs.metrics import (
    Event,
    Histogram,
    MetricsRecorder,
    NullRecorder,
    get_recorder,
    label_key,
    recording,
    set_recorder,
)
from repro.provisioning import EnsembleSpec, run_ensemble


def _obs_scenario(faults=None, **kw) -> Scenario:
    base = dict(
        name="obs-test",
        duration_s=1500.0,
        fleet=FleetSpec(n_provisioned=16, added_frac=0.25, n_rows=8),
        policy=PolicySpec("polca"),
        traffic=TrafficSpec(occ_peak=0.9),
        routing=RoutingSpec("cap-aware"),
        controller=ControllerSpec("predictive", interval_s=30.0, scope="tree"),
        hierarchy=HierarchySpec(shape=(2, 2, 2)),
        budget="nominal",
        compare_to_reference=False,
        faults=faults,
    )
    base.update(kw)
    return Scenario(**base)


_DERATE = FaultSpec((FaultEvent("node-derate", t=300.0, node="pdu0",
                                factor=0.7, until=1200.0),))


def _run_recorded(scenario):
    rec = MetricsRecorder()
    with recording(rec):
        res = run_experiment(scenario)
    return res, rec.snapshot()


def _assert_bit_identical(off, on):
    assert off.result.latencies == on.result.latencies
    assert off.fleet.decisions == on.fleet.decisions
    assert off.fleet.n_shed == on.fleet.n_shed
    assert np.array_equal(off.fleet.cluster_power_frac,
                          on.fleet.cluster_power_frac)
    assert np.array_equal(off.fleet.row_power_frac, on.fleet.row_power_frac)
    assert off.result.n_brakes == on.result.n_brakes


# ------------------------------------------------------------- recorder core
def test_default_recorder_is_disabled_null():
    rec = get_recorder()
    assert isinstance(rec, NullRecorder) and not rec.enabled
    # every write is a no-op and must not raise
    rec.counter("x", row=1)
    rec.gauge("g", 1.0)
    rec.observe("h", 0.5)
    rec.event("sub", "kind", t=0.0)
    with rec.span("s"):
        pass


def test_recording_context_installs_and_restores():
    rec = MetricsRecorder()
    outer = get_recorder()
    with recording(rec):
        assert get_recorder() is rec
        get_recorder().counter("inside")
    assert get_recorder() is outer
    assert rec.snapshot().counter_total("inside") == 1.0


def test_set_recorder_none_resets_to_null():
    set_recorder(MetricsRecorder())
    try:
        assert get_recorder().enabled
    finally:
        set_recorder(None)
    assert not get_recorder().enabled


# ------------------------------------------------------- bit-parity contract
def test_fleet_bit_parity_recorder_on_vs_off():
    """Acceptance: instrumentation observes, never perturbs — a routed
    tree-controller fleet run is bit-identical with a live recorder."""
    sc = _obs_scenario()
    off = run_experiment(sc)
    on, snap = _run_recorded(sc)
    _assert_bit_identical(off, on)
    # and the trace actually recorded the run: every non-shed routing
    # decision is a dispatch increment, every shed one a shed increment
    n_shed = sum(1 for d in on.fleet.decisions if d.row < 0)
    assert snap.counter_total("fleet_dispatch_total") == \
        len(on.fleet.decisions) - n_shed
    assert snap.counter_total("fleet_shed_total") == n_shed
    assert snap.counter_total("fleet_ticks_total") > 0


def test_chaos_bit_parity_and_fault_transition_events():
    sc = _obs_scenario(faults=_DERATE)
    off = run_experiment(sc)
    on, snap = _run_recorded(sc)
    _assert_bit_identical(off, on)
    # one chaos event per applied fault phase, reconciling with the audit log
    chaos_events = (snap.events_of("chaos", "fault_apply")
                    + snap.events_of("chaos", "fault_restore"))
    assert len(chaos_events) == on.fleet.n_fault_events == 2
    assert snap.counter_total("chaos_fault_transitions_total") == 2


def test_controller_rebalance_events_reconcile():
    on, snap = _run_recorded(_obs_scenario())
    evs = snap.events_of("controller", "rebalance")
    assert len(evs) == on.fleet.n_rebalances
    assert snap.counter_total("controller_rebalance_total") == len(evs)
    if evs:  # label values are canonicalized to strings in the trace
        moved = sum(float(e.labels_dict()["moved_w"]) for e in evs)
        assert moved == pytest.approx(on.fleet.budget_moved_w(), abs=1e-3)


def test_brake_edges_reconcile_with_braked_series():
    on, snap = _run_recorded(_obs_scenario(
        traffic=TrafficSpec(occ_peak=1.0), budget="calibrated"))
    total_edges = 0
    for i, rr in enumerate(on.fleet.row_results):
        s = np.asarray(rr.braked_series, bool)
        prev = np.concatenate([[False], s[:-1]])
        want = (int(np.sum(~prev & s)), int(np.sum(prev & ~s)))
        eng = sum(1 for e in snap.events_of("row", "brake_engage")
                  if e.labels_dict().get("row") == str(i))
        rel = sum(1 for e in snap.events_of("row", "brake_release")
                  if e.labels_dict().get("row") == str(i))
        assert (eng, rel) == want, f"row {i}"
        total_edges += eng + rel
    assert total_edges == snap.counter_total("row_brake_edges_total")


# --------------------------------------------------- Monte-Carlo invariance
def test_ensemble_bit_parity_and_worker_invariant_traces():
    base = _obs_scenario(duration_s=900.0)
    spec = dict(n_seeds=2, seed0=700)
    off = run_ensemble(EnsembleSpec(base, n_workers=1, **spec))
    snaps = []
    for w in (1, 2):
        rec = MetricsRecorder()
        with recording(rec):
            on = run_ensemble(EnsembleSpec(base, n_workers=w, **spec))
        assert on.brake_prob() == off.brake_prob()
        snaps.append(rec.snapshot())
    s1, s2 = snaps
    assert s1.counters == s2.counters
    assert s1.gauges == s2.gauges
    assert s1.hists == s2.hists
    assert s1.events == s2.events
    # per-member shard spans were captured and merged
    assert any(name == "mc/shard" for (name, _) in s1.spans)


# ------------------------------------- batched engine: spans and counters
BATCHED_SPANS = ("batched/operands", "batched/h2d", "batched/run",
                 "batched/d2h", "batched/unpack")


def _batched_scenario():
    """Hot enough that the planner's top probe brakes and its bottom one
    does not: a 3-probe bisection (2 servers, then 0, then 1)."""
    from conftest import parity_scenario

    return parity_scenario(occ_peak=0.99, power_scale=1.30,
                           duration_s=1800.0, n_provisioned=10,
                           added_frac=0.0)


def _plan(sc):
    from repro.provisioning.planner import RiskConstraints, plan_capacity

    return plan_capacity(sc, n_seeds=4, seed0=42, engine="jax",
                         constraints=RiskConstraints(
                             max_brakes=0, max_slo_violation_prob=1.0),
                         max_added_frac=0.2)


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs every open and
    close with the names of the annotations open around it."""

    def __init__(self):
        self.stack, self.opened = [], []

    def __call__(self, name):
        outer = self

        class _Annotation:
            def __enter__(self):
                outer.opened.append((name, tuple(outer.stack)))
                outer.stack.append(name)

            def __exit__(self, *exc):
                assert outer.stack.pop() == name
                return False
        return _Annotation()

    def children(self, parent):
        """The annotations opened directly inside ``parent``, in order."""
        return [n for n, around in self.opened
                if around and around[-1] == parent]


def _batched_nbytes(model, n_dispatches):
    """Bytes each way of ``n_dispatches`` runs of ``model`` on the jax
    engine, from its shapes: operands in (occupancy and fleet size per
    member), outputs out (brake plane and series kept, members
    unpadded)."""
    from repro.provisioning import batched

    N, R, T, S = model.n_members, model.n_rows, model.n_ticks, model.n_slots
    T60 = model.occ60.shape[2]
    h2d = (8 * N * R * T60 + 8 * N + 8 * len(batched._CONST_SCALARS)
           + 8 * (1 + len(batched._CLOCKS)) + 8 * R
           + (8 + 4 + 2 * 8 + 4) * T + 2 * 8 * T * R)
    d2h = (4 * N * R + 8 * N + 8 * N + 8 * N * S * R * 2 + N * T * R
           + 8 * N * T + 8 * N * T * R)
    return n_dispatches * h2d, n_dispatches * d2h


def test_batched_spans_nest_once_per_dispatch(monkeypatch):
    """Each jax-engine dispatch opens the five ``batched/*`` spans, in
    order, directly inside its ``mc/run_batched``, or inside the
    ``planner/round`` that runs a plan's candidates; on the profiler's
    clock they are ``polca/<name>`` annotations."""
    import jax

    from repro.provisioning.batched import run_batched_ensemble

    sc = _batched_scenario()
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    rec = MetricsRecorder()
    with recording(rec):
        run_batched_ensemble(EnsembleSpec(sc, n_seeds=3, seed0=7),
                             engine="jax")
        plan = _plan(sc)
    assert len(plan.probes) == 3
    one_dispatch = ["polca/" + n for n in BATCHED_SPANS]
    assert ann.children("polca/mc/run_batched") == one_dispatch
    # the plan's three candidates are one round, before its first probe
    assert ann.children("polca/planner/round") == one_dispatch
    opened = [(n, around) for n, around in ann.opened
              if n in ("polca/mc/run_batched", "polca/planner/round",
                       "polca/planner/probe")]
    assert opened == [("polca/mc/run_batched", ()),
                      ("polca/planner/round", ())] + \
        [("polca/planner/probe", ())] * len(plan.probes)
    assert not ann.stack
    spans = {name: s.count for (name, _), s in rec.snapshot().spans.items()
             if name.startswith("batched/")}
    assert spans == {n: 2 for n in BATCHED_SPANS}


def test_planner_round_counters_for_a_one_round_decision():
    """A decision whose candidates fit one member block is one round: one
    ``planner/round`` span labelled with its candidates and members, and
    the round counters beside the per-probe ones, which stay one per probe
    on the bisection's path."""
    sc = _batched_scenario()
    rec = MetricsRecorder()
    with recording(rec):
        plan = _plan(sc)
    snap = rec.snapshot()
    assert [p.added_servers for p in plan.probes] == [2, 0, 1]
    assert snap.counter_total("planner_rounds_total") == 1
    assert snap.counter_total("planner_candidates_total") == 3
    assert snap.counter_total("planner_probes_total") == 3
    assert snap.counters[("planner_probes_total",
                          (("outcome", "feasible"),))] == 2
    rounds = {labels: s.count for (name, labels), s in snap.spans.items()
              if name == "planner/round"}
    assert rounds == {(("candidates", "3"), ("members", "12"),
                       ("scenario", sc.name)): 1}
    assert sum(s.count for (name, _), s in snap.spans.items()
               if name == "planner/probe") == 3
    assert len(snap.events_of("planner", "probe")) == 3


def test_batched_byte_counters_match_the_shapes():
    from repro.provisioning.batched import (
        _auto_flags,
        lower_ensemble,
        stack_tick_models,
    )

    sc = _batched_scenario()
    spec = EnsembleSpec(sc, n_seeds=3, seed0=7)
    model, _, _ = lower_ensemble(spec)
    assert _auto_flags(model, None, None, None)[:2] == (True, True)
    rec = MetricsRecorder()
    with recording(rec):
        from repro.provisioning.batched import run_batched_ensemble

        run_batched_ensemble(spec, engine="jax")
    snap = rec.snapshot()
    h2d, d2h = _batched_nbytes(model, 1)
    assert snap.counter_total("batched_h2d_bytes_total") == h2d
    assert snap.counter_total("batched_d2h_bytes_total") == d2h
    # a plan's three candidates: one dispatch, each at its own fleet size
    rec = MetricsRecorder()
    with recording(rec):
        plan = _plan(sc)
    assert sorted(p.added_servers for p in plan.probes) == [0, 1, 2]
    want = _batched_nbytes(stack_tick_models([lower_ensemble(EnsembleSpec(
        sc.with_fleet(added_frac=p.added_frac), n_seeds=4, seed0=42))[0]
        for p in plan.probes]), 1)
    snap = rec.snapshot()
    assert (snap.counter_total("batched_h2d_bytes_total"),
            snap.counter_total("batched_d2h_bytes_total")) == want


def test_batched_engine_bit_parity_recorder_on_vs_off():
    """Brake sets, power and planner decisions are bit-identical with the
    recorder on and off."""
    from repro.provisioning.batched import lower_ensemble, run_tick_model

    sc = _batched_scenario()
    # the plan's top probe: two of its four members brake
    model, members, _ = lower_ensemble(EnsembleSpec(
        sc.with_fleet(added_frac=0.2), n_seeds=4, seed0=42))
    off = run_tick_model(model, members, engine="jax")
    with recording(MetricsRecorder()):
        on = run_tick_model(model, members, engine="jax")
    assert off.n_brakes.sum() > 0, "the scenario should brake"
    for name in ("brake_fire", "n_brakes", "peak_frac", "mean_frac",
                 "total_frac", "row_w", "impacts_hp", "impacts_lp"):
        a, b = getattr(off, name), getattr(on, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    plan_off = _plan(sc)
    with recording(MetricsRecorder()):
        plan_on = _plan(sc)
    assert plan_on.safe_added_servers == plan_off.safe_added_servers
    assert [(p.added_servers, p.feasible, p.brake_prob,
             p.slo_violation_prob, p.peak_frac_max) for p in plan_on.probes] \
        == [(p.added_servers, p.feasible, p.brake_prob,
             p.slo_violation_prob, p.peak_frac_max) for p in plan_off.probes]


def test_occupancy_counters_count_curves_and_draws(monkeypatch):
    """A lowering counts the generator's calls and the member-row jitter
    draws: a diurnal curve is drawn once for every member and row, and not
    again by a lowering with fresh seeds; a bursty one once per
    member-row."""
    import dataclasses

    from repro.provisioning import batched
    from repro.provisioning.batched import lower_ensemble

    monkeypatch.setattr(batched, "_BASE_OCC_CACHE", {})
    sc = _batched_scenario()

    def counts(spec):
        rec = MetricsRecorder()
        with recording(rec):
            lower_ensemble(spec)
        snap = rec.snapshot()
        return (snap.counter_total("batched_occupancy_curves_total"),
                snap.counter_total("batched_occupancy_draws_total"))

    assert sc.traffic.generator == "diurnal" and sc.fleet.n_rows == 2
    assert counts(EnsembleSpec(sc, n_seeds=3, seed0=5)) == (1, 6)
    assert counts(EnsembleSpec(sc, n_seeds=4, seed0=2**31 + 9)) == (0, 8)
    bursty = sc.with_(traffic=dataclasses.replace(sc.traffic,
                                                  generator="bursty"))
    assert counts(EnsembleSpec(bursty, n_seeds=3, seed0=5)) == (6, 6)


def test_occupancy_counters_for_a_cold_site_lowering(monkeypatch):
    """The site's 256 members x 56 rows of diurnal occupancy call the
    generator once and draw 14,336 jitter streams."""
    from repro.provisioning import batched

    monkeypatch.setattr(batched, "_BASE_OCC_CACHE", {})
    sc = _batched_scenario()
    rec = MetricsRecorder()
    with recording(rec):
        occ = batched._member_occupancy(
            sc, range(256), np.arange(0.0, 600.0, 60.0), 56, 52)
    snap = rec.snapshot()
    assert occ.shape == (256, 56, 10)
    assert snap.counter_total("batched_occupancy_curves_total") == 1
    assert snap.counter_total("batched_occupancy_draws_total") == 256 * 56


def test_rated_tree_fold_span_counter_and_bit_parity(monkeypatch):
    """A rated tree's dispatch labels ``batched/run`` with the nodes it
    folds, counts ``batched_node_fold_cells_total`` (members x ticks x
    nodes) and times the host side of the node results as
    ``batched/node_stats`` inside ``batched/unpack``; an unrated one is
    labelled ``nodes=0`` and counts nothing. The recorder leaves every
    output bit-identical."""
    import jax

    from repro.experiments.scenario import HierarchySpec
    from repro.provisioning.batched import lower_ensemble, run_tick_model

    sc = _batched_scenario().with_hierarchy(
        (2, 2), level_names=("site", "rack"),
        level_capacity_w=(2.0e4, 1.0e4))
    model, members, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=3, seed0=5))
    off = run_tick_model(model, members, engine="jax")
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    rec = MetricsRecorder()
    with recording(rec):
        on = run_tick_model(model, members, engine="jax")
    assert off.node_over_ticks.shape == (3, 3)
    for name in ("brake_fire", "n_brakes", "peak_frac", "mean_frac",
                 "total_frac", "row_w", "impacts_hp", "impacts_lp",
                 "node_w", "node_peak_w", "node_over_ticks"):
        a, b = getattr(off, name), getattr(on, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    snap = rec.snapshot()
    runs = {labels: s.count for (name, labels), s in snap.spans.items()
            if name == "batched/run"}
    assert runs == {(("nodes", "3"), ("power", "table")): 1}
    assert snap.counter_total("batched_node_fold_cells_total") == \
        3 * model.n_ticks * 3
    assert ann.children("polca/batched/unpack") == ["polca/batched/node_stats"]
    bare = sc.with_(hierarchy=HierarchySpec(shape=(2, 2)))
    model, members, _ = lower_ensemble(EnsembleSpec(bare, n_seeds=3, seed0=5))
    rec = MetricsRecorder()
    with recording(rec):
        run_tick_model(model, members, engine="jax")
    snap = rec.snapshot()
    assert {labels for (name, labels) in snap.spans
            if name == "batched/run"} == {(("nodes", "0"),
                                           ("power", "table"))}
    assert "batched/node_stats" not in {name for name, _ in snap.spans}
    assert snap.counter_total("batched_node_fold_cells_total") == 0


def test_clock_table_counter_and_power_label():
    """A program of two rows or more reads each row's ``f ** gamma`` from
    the clock table: ``batched_clock_table_cells_total`` counts its members
    x ticks x rows (a grid's scenarios each add theirs) and ``batched/run``
    carries ``power="table"``. A one-row program computes the pow: it
    counts nothing and carries ``power="pow"``."""
    from repro.provisioning.batched import (
        lower_ensemble,
        run_batched_grid,
        run_tick_model,
    )

    sc = _batched_scenario()
    model, members, _ = lower_ensemble(EnsembleSpec(sc, n_seeds=3, seed0=5))
    assert model.n_rows == 2
    rec = MetricsRecorder()
    with recording(rec):
        run_tick_model(model, members, engine="jax")
        run_batched_grid([EnsembleSpec(sc, n_seeds=3, seed0=5),
                          EnsembleSpec(sc.with_(power_scale=1.1), n_seeds=3,
                                       seed0=9)], engine="jax")
    snap = rec.snapshot()
    cells = 3 * model.n_ticks * model.n_rows
    assert snap.counter_total("batched_clock_table_cells_total") == 3 * cells
    runs = {labels: s.count for (name, labels), s in snap.spans.items()
            if name == "batched/run"}
    assert runs == {(("nodes", "0"), ("power", "table")): 2}
    one = sc.with_fleet(n_rows=1, rows_per_rack=1)
    model, members, _ = lower_ensemble(EnsembleSpec(one, n_seeds=3, seed0=5))
    rec = MetricsRecorder()
    with recording(rec):
        run_tick_model(model, members, engine="jax")
    snap = rec.snapshot()
    assert snap.counter_total("batched_clock_table_cells_total") == 0
    assert {labels for (name, labels) in snap.spans
            if name == "batched/run"} == {(("nodes", "0"), ("power", "pow"))}


@pytest.mark.parametrize("enabled", [False, True])
def test_batched_engine_waits_on_operands_only_when_recording(enabled,
                                                              monkeypatch):
    """Untraced, the engine waits once, for its outputs; a recorder adds
    one wait, on the copied operands, so ``batched/h2d`` times the copy."""
    import jax

    from repro.provisioning.batched import lower_ensemble, run_tick_model

    model, members, _ = lower_ensemble(EnsembleSpec(_batched_scenario(),
                                                    n_seeds=2, seed0=3))
    waited = []
    orig = jax.block_until_ready

    def counting(x):
        waited.append(x)
        return orig(x)
    monkeypatch.setattr(jax, "block_until_ready", counting)
    with recording(MetricsRecorder() if enabled else None):
        run_tick_model(model, members, engine="jax")
    assert [type(x) for x in waited] == [tuple] * enabled + [dict]


def test_batched_runner_is_named_tick_scan():
    """The jitted runner has a stable name in profiles: ``jit_tick_scan``."""
    import jax

    from repro.provisioning import batched

    model, _, _ = batched.lower_ensemble(EnsembleSpec(_batched_scenario(),
                                                      n_seeds=2, seed0=3))
    cfg, mesh, idx = batched._plan_bucket(
        [model], keep_series=False, keep_fire=False, member_chunk=None,
        mesh=None)
    with jax.enable_x64(True):
        lowered = batched._jax_runner(cfg, mesh).lower(
            *batched._bucket_operands([model], idx))
    assert lowered.as_text().startswith("module @jit_tick_scan ")


def test_importing_obs_leaves_jax_unloaded():
    import subprocess
    import sys

    code = ("import sys, repro.obs, repro.obs.metrics; "
            "sys.exit('jax' in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], env=dict(
        os.environ, PYTHONPATH=os.pathsep.join(sys.path)), timeout=120)
    assert p.returncode == 0


# ------------------------------------------------------------ histogram math
def test_histogram_merge_is_concatenation():
    """Property: merge(hist(A), hist(B)) == hist(A ++ B), across random
    draws spanning every bucket regime (sub-min, mid, overflow)."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        a = rng.lognormal(mean=-2.0, sigma=3.0, size=137)
        b = rng.lognormal(mean=1.0, sigma=2.0, size=61)
        ha, hb, hab = Histogram(), Histogram(), Histogram()
        for x in a:
            ha.observe(float(x))
            hab.observe(float(x))
        for x in b:
            hb.observe(float(x))
            hab.observe(float(x))
        m = Histogram()
        m.merge(ha)
        m.merge(hb)
        assert m.counts == hab.counts and m.bounds == hab.bounds
        assert m.count == hab.count == len(a) + len(b)
        # summation order differs (partial sums vs interleaved): approx only
        assert m.sum == pytest.approx(hab.sum, rel=1e-12)


def test_histogram_quantile_and_cumulative():
    h = Histogram()
    for x in np.linspace(0.001, 10.0, 1000):
        h.observe(float(x))
    assert h.count == 1000
    q50, q99 = h.quantile(0.5), h.quantile(0.99)
    assert 0.0 < q50 <= q99
    cum = h.cumulative()
    assert cum == sorted(cum)  # cumulative counts are monotone
    assert cum[-1] == 1000  # everything lands under the top finite bound


def test_snapshot_merge_accumulates():
    r1, r2 = MetricsRecorder(), MetricsRecorder()
    r1.counter("c", k="a")
    r1.gauge("g", 1.0)
    r1.observe("h", 0.1)
    r1.event("s", "e1", t=1.0)
    r2.counter("c", k="a", value=2.0)
    r2.gauge("g", 5.0)
    r2.observe("h", 0.2)
    r2.event("s", "e2", t=2.0)
    s = r1.snapshot()
    s.merge(r2.snapshot())
    assert s.counter_total("c") == 3.0
    assert s.gauges[("g", ())] == 5.0  # per-key max wins
    assert s.hists[("h", ())].count == 2
    assert [e.kind for e in s.events] == ["e1", "e2"]


def test_snapshot_merge_gauges_order_independent():
    """Gauge merge is max-per-key: merging worker snapshots in either
    order yields the same gauges (last-write-wins depended on worker
    scheduling)."""
    r1, r2 = MetricsRecorder(), MetricsRecorder()
    r1.gauge("peak", 3.0)
    r1.gauge("only_a", 1.0)
    r2.gauge("peak", 2.0)
    r2.gauge("only_b", 4.0)
    ab = r1.snapshot().merge(r2.snapshot())
    ba = r2.snapshot().merge(r1.snapshot())
    assert ab.gauges == ba.gauges
    assert ab.gauges[("peak", ())] == 3.0
    assert ab.gauges[("only_a", ())] == 1.0
    assert ab.gauges[("only_b", ())] == 4.0


def test_fast_path_label_keys_match_kwargs_path():
    r1, r2 = MetricsRecorder(), MetricsRecorder()
    r1.counter("c", reason="x", row="3")
    r1.observe("h", 0.5, priority="high")
    r2.counter_k("c", 1.0, label_key({"reason": "x", "row": "3"}))
    r2.observe_k("h", 0.5, (("priority", "high"),))
    assert r1.snapshot().counters == r2.snapshot().counters
    assert r1.snapshot().hists == r2.snapshot().hists


# ------------------------------------------------------------------- export
def test_events_jsonl_roundtrip(tmp_path):
    rec = MetricsRecorder()
    rec.event("row", "brake_engage", t=0.5, row=3)
    rec.event("controller", "rebalance", t=1.0, moved_w=12.5,
              policy="predictive")
    rec.event("chaos", "fault_apply", t=2.0)
    snap = rec.snapshot()
    path = tmp_path / EVENTS_NAME
    with open(path, "w") as f:
        assert write_events(snap, f) == 3
    back = read_events(str(path))
    assert back == snap.events
    assert back[0] == Event(0.5, "row", "brake_engage", (("row", "3"),))
    # deterministic serialization: sorted keys, one JSON object per line
    lines = event_lines(snap)
    assert lines == event_lines(snap)
    assert all(json.loads(ln) for ln in lines)


def test_prometheus_roundtrip(tmp_path):
    rec = MetricsRecorder()
    rec.counter("fleet_dispatch_total", reason='ok "primary"', row="0")
    rec.counter("fleet_dispatch_total", reason="spill\nover", row="1",
                value=2.0)
    rec.gauge("fleet_cluster_power_frac", 0.875)
    rec.observe("row_queue_delay_seconds", 0.25, priority="high")
    with rec.span("mc/run_ensemble", base="obs-test"):
        pass
    snap = rec.snapshot()
    text = prometheus_text(snap)
    path = tmp_path / METRICS_NAME
    path.write_text(text)
    prom = read_prometheus(str(path))
    counters = dict()
    for labels, v in prom["counter"]["fleet_dispatch_total"]:
        counters[labels["reason"]] = v
    assert counters == {'ok "primary"': 1.0, "spill\nover": 2.0}
    assert prom["gauge"]["fleet_cluster_power_frac"][0][1] == 0.875
    # suffixed samples resolve to the declared base TYPE
    hist = prom["histogram"]
    [(labels, n)] = hist["row_queue_delay_seconds_count"]
    assert labels == {"priority": "high"} and n == 1.0
    inf = [v for lb, v in hist["row_queue_delay_seconds_bucket"]
           if lb["le"] == "+Inf"]
    assert inf == [1.0]
    [(labels, n)] = prom["summary"]["mc_run_ensemble_seconds_count"]
    assert labels == {"base": "obs-test"} and n == 1.0
    assert "untyped" not in prom


def test_manifest_and_write_artifacts(tmp_path):
    rec = MetricsRecorder()
    rec.counter("c")
    rec.event("s", "k", t=0.0)
    man = run_manifest(seed=123, scenario="obs-test",
                       argv=["benchmarks.run", "--quick"],
                       extra={"kind": "test"})
    write_artifacts(str(tmp_path), rec.snapshot(), man)
    back = read_manifest(str(tmp_path))
    assert back["seed"] == 123
    assert back["scenario"] == "obs-test"
    assert back["kind"] == "test"
    assert back["numpy"]
    assert (tmp_path / METRICS_NAME).exists()
    assert len(read_events(str(tmp_path / EVENTS_NAME))) == 1


# -------------------------------------------------------- benchmark selector
def test_select_modules_matching_rules():
    from benchmarks.run import MODULES, select_modules

    assert select_modules(None) == list(MODULES)
    assert select_modules("") == list(MODULES)
    # prefix match stops at an underscore boundary
    [m] = select_modules("table2")
    assert m.endswith("table2_cluster_stats")
    # comma list, original MODULES order, deduped
    sel = select_modules("capacity,table2,table2")
    assert [s.rsplit(".", 1)[-1][:8] for s in sel] == \
        [m.rsplit(".", 1)[-1][:8] for m in MODULES
         if m.rsplit(".", 1)[-1].startswith(("table2", "capacity"))]
    assert select_modules("observability") == ["benchmarks.observability"]


def test_select_modules_rejects_unknown_token():
    from benchmarks.run import select_modules

    with pytest.raises(SystemExit, match="matches no benchmark module"):
        select_modules("fig1")  # was the substring footgun: fig13 != fig1
    with pytest.raises(SystemExit, match="known:"):
        select_modules("table2,nope")


# ----------------------------------------------------------- report pipeline
def _synthetic_artifacts(d, ok=True, us=100.0):
    rows = {"r/a": {"us_per_call": us, "derived": "x", "ok": ok},
            "r/b": {"us_per_call": 5.0, "derived": "y", "ok": None}}
    with open(os.path.join(d, "BENCH_mod.json"), "w") as f:
        json.dump({"module": "mod", "rows": rows}, f)
    rec = MetricsRecorder()
    rec.counter("c_total", kind="k")
    rec.event("sub", "kind", t=1.0)
    with rec.span("stage", phase="p"):
        pass
    write_artifacts(d, rec.snapshot(), run_manifest(seed=7))


def test_report_render_and_diff(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "report", os.path.join(os.path.dirname(__file__), "..",
                               "tools", "report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    old, new = tmp_path / "old", tmp_path / "new"
    old.mkdir(), new.mkdir()
    _synthetic_artifacts(str(old), ok=True, us=100.0)
    _synthetic_artifacts(str(new), ok=False, us=150.0)
    rep = report.render_report(str(old))
    assert "| mod | 2 | 1 | 0 |" in rep
    assert "**seed**: `7`" in rep
    assert "stage" in rep  # span flame summary
    assert "| sub | kind | 1 |" in rep
    diff = report.render_diff(str(old), str(new))
    assert "Regressions" in diff and "r/a" in diff
    assert "+50.0%" in diff
    assert report.main([str(old)]) == 0
    assert report.main([]) == 2


# ---------------------------------------------------------- launcher logging
def test_logging_env_level_and_stream():
    from repro.obs import log as obslog

    buf = io.StringIO()
    old_env = os.environ.get(obslog.ENV_VAR)
    os.environ[obslog.ENV_VAR] = "WARNING"
    try:
        obslog.setup_logging(stream=buf, force=True)
        lg = obslog.get_logger("launch.test")
        assert lg.name == "repro.launch.test"
        lg.info("hidden")
        lg.warning("arch=%s", "t5x")
        assert buf.getvalue() == "arch=t5x\n"  # message-only, print-identical
    finally:
        if old_env is None:
            os.environ.pop(obslog.ENV_VAR, None)
        else:
            os.environ[obslog.ENV_VAR] = old_env
        obslog.setup_logging(force=True)  # restore default stderr handler


def test_launchers_use_shared_logger():
    import repro.launch.dryrun as dryrun
    import repro.launch.serve as serve
    import repro.launch.train as train

    for mod in (dryrun, serve, train):
        assert isinstance(mod.log, logging.Logger)
        assert mod.log.name.startswith("repro.")
