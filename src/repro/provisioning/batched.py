"""JAX-native batched ensemble engine with a numpy differential oracle.

The Monte-Carlo engine in ``provisioning.montecarlo`` parallelizes the
event-driven :class:`~repro.core.simulator.RowSimulator` across a fork pool —
throughput is capped by host cores (< 2 effective in CI), so risk tails stay
at tens of members. This module rebuilds the hot loop as a *tick-level fluid
model* that runs N ensemble members x T telemetry ticks as one batched device
program (DESIGN.md §15):

* **Lowering** — :func:`lower_ensemble` compiles a
  :class:`~repro.experiments.scenario.Scenario` + member seeds into a
  :class:`TickModel`: per-member occupancy on the 60 s trace grid, closed-form
  power coefficients from the Table-4 workload mix (idle + per-priority
  busy-power terms with the DVFS ``f^gamma`` law from
  ``core.power_model``), the POLCA thresholds/frequencies, fault timelines
  lowered to per-tick budget scales and row-alive masks, and the
  ``PowerHierarchy`` node matrix for segment-sum folds. A tree whose
  interior levels carry ratings (``HierarchySpec.level_capacity_w``) is
  folded inside the tick loop: each member's row watts sum up the tree
  every tick, and each interior node keeps its peak watts and its count of
  ticks over its rating (:func:`_node_w`).

* **Two backends, one contract** — ``engine="jax"`` runs the tick advance
  as a ``lax.scan`` over time ``vmap``-ed over members, with the
  :class:`~repro.core.policy.PolcaPolicy` /
  :class:`~repro.core.policy.PredictivePolcaPolicy` observe step (windowed
  least-squares slope over the 40 s OOB horizon) carried in scan state as a
  vectorized boolean state machine (:func:`polca_latch_step`).
  ``engine="numpy"`` is the differential **oracle**: the identical
  tick/ring contract driven by the *real* policy objects through
  :class:`~repro.core.telemetry.Telemetry`, one instance per (member, row)
  — so the vectorized state machine is checked against the genuine
  Algorithm-1 implementation, not a reimplementation of itself
  (``tests/test_batched_parity.py``).

* **Grids, shards, chunks (DESIGN.md §16)** — per-scenario scalars are
  *traced* operands (:class:`_Consts`), not compile-time constants, so one
  compiled program serves every scenario sharing tick geometry:
  :func:`run_batched_grid` stacks M lowered models and ``vmap``s the
  scenario axis on top of the member axis (one jit call per geometry
  bucket), and a ``plan_capacity`` bisection stops recompiling per probe
  (``jax_trace_count`` is the regression hook). The member axis optionally
  shards over a ``("data",)`` mesh (``launch.mesh.data_mesh`` +
  ``shard_map``) and/or advances in ``member_chunk``-sized ``lax.scan``
  blocks, bounding live memory so 10^5-10^6-member tails fit on one host.

* **Actuation ring** — out-of-band cap commands apply ``ceil(40/2)=20``
  ticks after issue and powerbrakes ``ceil(5/2)=3`` ticks after, modeled as
  a ``[rows, D, 2]`` ring buffer (NaN = no command); later-issued commands
  overwrite earlier ones per frequency field, which is exactly the DES event
  queue's same-due-time resolution.

The oracle contract deliberately accepts two float nonidentities, both
documented in DESIGN.md §15: XLA may fuse multiply-adds (power series agree
to ~1e-15, asserted <= 1e-6 relative), and ``jnp.sum`` may reorder the
predictive slope accumulation (~1e-16). Brake-tick *sets* are compared for
bit-equality on the harness scenarios; a flip would need a power sample
within ~1e-12 of a threshold.

``montecarlo.run_ensemble(engine=...)`` dispatches here, and
``planner.plan_capacity(engine="jax")`` uses the dense tails to activate the
CVaR gate in ``RiskConstraints``.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core.policy import PolcaPolicy, PredictivePolcaPolicy
from repro.core.simulator import SimResult
from repro.core.slo import LatencyStats
from repro.core.telemetry import Telemetry
from repro.core.traces import TABLE4, get_occupancy_generator
from repro.experiments.runner import build_workloads, row_budgets
from repro.experiments.scenario import Scenario
from repro.obs.metrics import get_recorder
from repro.provisioning.montecarlo import (
    EnsembleResult,
    EnsembleSpec,
    MemberStats,
    resolve_ensemble_budget,
)

# members x ticks above which run_batched_ensemble drops per-tick series by
# default (a [N, T] float64 matrix; 4e6 ~ 32 MB) — mirroring the
# record_power=False path of the DES engine
_SERIES_CELL_LIMIT = 4_000_000
# per-member SLO-impact samples are decimated onto at most this many slots
_IMPACT_SLOTS = 256
# member_chunk=None (auto) scans blocks of about this many members (counted
# across the whole scenario axis): the ~2 KB/member scan carry then stays
# cache-resident, which beats a flat vmap well before memory binds
_AUTO_CHUNK_MEMBERS = 512
_JITTER_SALT = 9173  # member-occupancy jitter stream, disjoint from arrivals


@dataclass(frozen=True)
class TickModel:
    """A Scenario + member seeds lowered to the batched tick program.

    Everything both backends consume: static arrays on the tick/trace grids
    plus closed-form scalars. The model is engine-agnostic — running it with
    ``engine="numpy"`` and ``engine="jax"`` must agree per the oracle
    contract (DESIGN.md §15)."""

    base_name: str
    n_members: int
    n_rows: int
    n_ticks: int  # T
    dt: float  # telemetry_s
    occ60: np.ndarray = field(repr=False)  # [N, R, T60] occupancy, 60 s grid
    alive: np.ndarray = field(repr=False)  # [T, R] 0/1 row-crash mask
    budget_scale: np.ndarray = field(repr=False)  # [T, R] fault derates
    row_budget_w: np.ndarray = field(repr=False)  # [R] static budgets
    # power plane (closed form over the Table-4 mix; watts per server)
    p0_srv_w: float  # idle server watts
    k_lp_w: float  # LP busy-power coefficient (x f_lp^gamma)
    k_hp_w: float  # HP busy-power coefficient (x f_hp^gamma)
    lp_share: float  # LP fraction of the server pool
    gamma: float
    n_servers: int
    power_scale: float
    # policy constants (resolved from the PolicySpec)
    predictive: bool
    t1: float
    t2: float
    t1_buffer: float
    t2_buffer: float
    lp_freq_t1: float
    lp_freq_t2: float
    hp_freq_t2: float
    brake_freq: float
    escalation_ticks: int
    horizon_s: float
    window: int
    # actuation ring
    oob_ticks: int
    brake_ticks: int
    ring_depth: int  # D = max(oob, brake) + 1
    # SLO fluid proxy (per-priority clock-sensitive fraction + service time)
    a_hp: float
    a_lp: float
    svc_hp: float
    svc_lp: float
    has_hp: bool
    has_lp: bool
    # impact decimation
    stride: int
    n_slots: int  # S = ceil(T / stride)
    # hierarchy segment-sum fold (None = flat row accounting)
    node_matrix: Optional[np.ndarray] = field(default=None, repr=False)  # [n_nodes, R]
    node_names: Tuple[str, ...] = ()
    # a rated tree, folded inside the tick loop: its root-down fan-outs and
    # the ratings of its interior nodes, children before parents (the
    # PowerHierarchy's order); () and None = no fold
    node_shape: Tuple[int, ...] = ()
    node_capacity_w: Optional[np.ndarray] = field(default=None, repr=False)  # [nodes]
    seeds: Tuple[int, ...] = ()
    # [N] fleet size of each member where the members of several candidate
    # fleets share one model (stack_tick_models); None = n_servers for all
    member_servers: Optional[np.ndarray] = field(default=None, repr=False)

    @property
    def total_budget_w(self) -> float:
        return float(self.row_budget_w.sum())

    def servers(self) -> np.ndarray:
        """[N] fleet size of each member, as the program reads it."""
        if self.member_servers is None:
            return np.full(self.n_members, float(self.n_servers))
        return self.member_servers

    def tick_times(self) -> np.ndarray:
        """Telemetry timestamps: tick k samples t = (k+1) * dt."""
        return (np.arange(self.n_ticks, dtype=np.float64) + 1.0) * self.dt


@dataclass
class BatchedRun:
    """Raw output of one tick-program run (either backend).

    ``brake_fire[m, k, r]`` marks the policy firing a powerbrake on row r at
    tick k of member m — the brake-tick set the differential harness compares
    bit-for-bit. Series fields are ``None`` when the run dropped them
    (``keep_series=False``)."""

    engine: str
    model: TickModel
    # [N, T, R] bool; None when the run dropped the per-tick plane
    # (keep_brake_fire=False — dense tails keep only the n_brakes counts)
    brake_fire: Optional[np.ndarray] = field(repr=False)
    n_brakes: np.ndarray = field(repr=False)  # [N, R] int
    peak_frac: np.ndarray = field(repr=False)  # [N]
    mean_frac: np.ndarray = field(repr=False)  # [N]
    impacts_hp: np.ndarray = field(repr=False)  # [N, R, S]
    impacts_lp: np.ndarray = field(repr=False)  # [N, R, S]
    total_frac: Optional[np.ndarray] = field(default=None, repr=False)  # [N, T]
    row_w: Optional[np.ndarray] = field(default=None, repr=False)  # [N, T, R]
    node_w: Optional[np.ndarray] = field(default=None, repr=False)  # [N, T, nodes]
    # a rated tree's interior nodes (TickModel.node_shape): peak watts and
    # ticks over the rating, per member
    node_peak_w: Optional[np.ndarray] = field(default=None, repr=False)  # [N, nodes]
    node_over_ticks: Optional[np.ndarray] = field(default=None, repr=False)  # [N, nodes]

    def brake_ticks(self) -> np.ndarray:
        """Sorted (member, tick, row) index triples of every brake firing —
        the bit-compared set of the oracle contract."""
        if self.brake_fire is None:
            raise ValueError(
                "this run dropped the per-tick brake plane "
                "(keep_brake_fire=False); only n_brakes counts survive")
        return np.argwhere(self.brake_fire)

    def member_stats(self, m: int) -> LatencyStats:
        hp = self.impacts_hp[m].ravel() if self.model.has_hp else np.zeros(0)
        lp = self.impacts_lp[m].ravel() if self.model.has_lp else np.zeros(0)
        return LatencyStats(hp_impacts=[float(x) for x in hp],
                            lp_impacts=[float(x) for x in lp])


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

def _policy_constants(sc: Scenario) -> Dict[str, object]:
    pol = sc.policy.build()
    if isinstance(pol, PredictivePolcaPolicy):
        predictive = True
    elif isinstance(pol, PolcaPolicy):
        predictive = False
    else:
        raise ValueError(
            f"batched engine supports polca/polca-predictive policies; "
            f"scenario {sc.name!r} uses {sc.policy.kind!r} (run it on the "
            f"event-driven engine instead)")
    return dict(
        predictive=predictive,
        t1=float(pol.t1), t2=float(pol.t2),
        t1_buffer=float(pol.t1_buffer), t2_buffer=float(pol.t2_buffer),
        lp_freq_t1=float(pol.lp_freq_t1), lp_freq_t2=float(pol.lp_freq_t2),
        hp_freq_t2=float(pol.hp_freq_t2), brake_freq=float(pol.brake_freq),
        escalation_ticks=int(pol.escalation_ticks),
        horizon_s=float(getattr(pol, "horizon_s", 40.0)),
        window=int(getattr(pol, "window", 8)),
    )


_POWER_CONSTS_CACHE: Dict[tuple, Dict[str, float]] = {}


def _power_constants(sc: Scenario) -> Dict[str, float]:
    """Closed-form power/SLO coefficients over the Table-4 workload mix.

    A busy server running class w draws ``idle + k_w * f^gamma`` watts where
    ``k_w = n_dev * (p_peak - idle) * u_eff_w`` and ``u_eff_w`` is the
    prefill/decode-time-weighted roofline utilization — exactly
    ``DevicePower.power`` evaluated at the class's two
    :class:`~repro.core.workload.PhasePoint` operating points. Classes then
    collapse into one LP and one HP coefficient via share x priority mix.

    Per-server coefficients are independent of fleet *size*, so the result
    is cached on the (model, device, devices/server, mix) key — a
    ``plan_capacity`` bisection re-lowers per probe (the occupancy jitter
    scales with ``n_servers``, so member traces legitimately change) but
    never recomputes this plane."""
    key = (sc.fleet.model, sc.fleet.device, sc.fleet.n_devices_per_server,
           sc.traffic.priority_mix_override)
    hit = _POWER_CONSTS_CACHE.get(key)
    if hit is not None:
        return hit
    wls, shares = build_workloads(sc)
    server = sc.fleet.server()
    dev = server.device
    k_lp = k_hp = lp_share = 0.0
    a_num = {"high": 0.0, "low": 0.0}
    svc_num = {"high": 0.0, "low": 0.0}
    wgt_tot = {"high": 0.0, "low": 0.0}
    for wl, share, spec in zip(wls, shares, TABLE4):
        mean_out = 0.5 * (spec.out_range[0] + spec.out_range[1])
        t_total = wl.timing.t_prefill + mean_out * wl.timing.t_token
        f_pre = wl.timing.t_prefill / t_total
        u_eff = 0.0
        cf_eff = 0.0
        for frac, pt in ((f_pre, wl.timing.prefill_point),
                         (1.0 - f_pre, wl.timing.token_point)):
            u = min(1.0, dev.w_compute * min(pt.u_compute, 1.0)
                    + dev.w_memory * min(pt.u_memory, 1.0))
            u_eff += frac * u
            cf_eff += frac * pt.compute_frac
        k_srv = server.n_devices * (dev.p_peak - dev.idle_w) * u_eff
        mix = wl.priority_mix
        k_hp += share * mix * k_srv
        k_lp += share * (1.0 - mix) * k_srv
        lp_share += share * (1.0 - mix)
        for prio, wgt in (("high", share * mix), ("low", share * (1.0 - mix))):
            wgt_tot[prio] += wgt
            a_num[prio] += wgt * cf_eff
            svc_num[prio] += wgt * t_total
    out = dict(p0_srv_w=float(server.idle_power), k_lp_w=float(k_lp),
               k_hp_w=float(k_hp), lp_share=float(lp_share),
               gamma=float(dev.gamma))
    for prio, pkey in (("high", "hp"), ("low", "lp")):
        has = wgt_tot[prio] > 0.0
        out[f"has_{pkey}"] = bool(has)
        out[f"a_{pkey}"] = float(a_num[prio] / wgt_tot[prio]) if has else 0.0
        out[f"svc_{pkey}"] = float(svc_num[prio] / wgt_tot[prio]) if has else 1.0
    _POWER_CONSTS_CACHE[key] = out
    return out


# base generator curves are independent of fleet size (only the CLT jitter
# scales with n_servers), so a plan_capacity bisection — which re-lowers per
# probe because fleets differ — reuses them across every probe
_BASE_OCC_CACHE: Dict[tuple, np.ndarray] = {}
# sized for a 4-generator x 10^3-seed x 2-row grid with headroom; entries
# are short 60 s-grid curves (a few KB each), so the cap is ~100 MB worst
# case and far smaller in practice
_BASE_OCC_CACHE_MAX = 16384


def _member_occupancy(sc: Scenario, seeds: Sequence[int], t60: np.ndarray,
                      n_rows: int, n_servers: int) -> np.ndarray:
    """[N, R, T60] occupancy: the scenario's registered generator per member
    seed + row, plus a member-seeded CLT busy-fraction jitter
    (sigma = sqrt(occ(1-occ)/n_servers)) standing in for the arrival-sampling
    noise of the DES — without it the diurnal family (which deliberately
    ignores the member seed) would collapse every member onto one curve."""
    gen = get_occupancy_generator(sc.traffic.generator)
    gkey = (sc.traffic.generator, len(t60),
            float(t60[-1]) if len(t60) else 0.0,
            float(sc.traffic.occ_peak), n_rows,
            tuple(sorted((k, repr(v))
                         for k, v in sc.traffic.gen_params.items())))
    occ = np.empty((len(seeds), n_rows, len(t60)), dtype=np.float64)
    for mi, seed in enumerate(seeds):
        for r in range(n_rows):
            ck = gkey + (int(seed), r)
            base = _BASE_OCC_CACHE.get(ck)
            if base is None:
                base = np.asarray(
                    gen(t60, seed=int(seed), peak=sc.traffic.occ_peak,
                        n_rows=n_rows, row=r, **sc.traffic.gen_params),
                    dtype=np.float64)
                if len(_BASE_OCC_CACHE) < _BASE_OCC_CACHE_MAX:
                    _BASE_OCC_CACHE[ck] = base
            rng = np.random.default_rng([int(seed), r, _JITTER_SALT])
            sigma = np.sqrt(np.clip(base * (1.0 - base), 0.0, None) / n_servers)
            occ[mi, r] = np.clip(base + rng.standard_normal(len(t60)) * sigma,
                                 0.0, 1.0)
    return occ


def _lower_faults(sc: Scenario, n_ticks: int, dt: float, n_rows: int,
                  hierarchy) -> Tuple[np.ndarray, np.ndarray]:
    """Fault timeline -> ([T, R] alive mask, [T, R] budget scale).

    Row crashes zero a row's occupancy (it idles until revived); budget
    events scale the *derated subtree's* row budgets per tick, ramping
    linearly over ``ramp_s`` and restoring at ``until`` — the same
    conservative-tree semantics the ChaosInjector enforces on the DES path.
    Unlike ``run_experiment``, faults here do not require a RoutingSpec: the
    tick model has no dispatcher to fence, so the masks are the whole story."""
    alive = np.ones((n_ticks, n_rows), dtype=np.float64)
    bscale = np.ones((n_ticks, n_rows), dtype=np.float64)
    faults = sc.faults
    if faults is None or faults.is_noop:
        return alive, bscale
    names = list(hierarchy.names) if hierarchy is not None else None
    faults.validate(duration_s=sc.duration_s, n_rows=n_rows, node_names=names)
    t_ticks = (np.arange(n_ticks, dtype=np.float64) + 1.0) * dt
    for e in sorted(faults.row_events(), key=lambda e: e.t):
        alive[t_ticks >= e.t, int(e.row)] = (
            0.0 if e.kind == "row-crash" else 1.0)
    for e in faults.budget_events():
        if e.kind == "site-demand-response" or hierarchy is None:
            if e.kind == "node-derate" and hierarchy is None:
                raise ValueError(
                    f"fault event {e.describe()} targets a hierarchy node "
                    f"but scenario {sc.name!r} has no HierarchySpec")
            rows = np.arange(n_rows)
        else:
            rows = hierarchy.subtree_leaves(list(hierarchy.names).index(e.node))
        ramp = (np.clip((t_ticks - e.t) / e.ramp_s, 0.0, 1.0) if e.ramp_s > 0
                else (t_ticks >= e.t).astype(np.float64))
        scale = 1.0 - (1.0 - e.factor) * ramp
        if e.until is not None:
            scale = np.where(t_ticks >= e.until, 1.0, scale)
        bscale[:, rows] *= scale[:, None]
    return alive, bscale


def lower_ensemble(spec: EnsembleSpec, *, budget_w: Optional[float] = None
                   ) -> Tuple[TickModel, List[Scenario], float]:
    """Lower an EnsembleSpec to the batched tick program. Returns
    ``(model, member_scenarios, resolved_budget_w)`` — members carry the
    same pinned budget ``run_ensemble`` would pin, so planner decisions on
    either engine answer the same question."""
    sc = spec.base
    if sc.routing is not None:
        raise ValueError(
            f"batched engine runs unrouted row/cluster scenarios; "
            f"{sc.name!r} carries a RoutingSpec (use engine='numpy' — the "
            f"event-driven fleet path)")
    if sc.duration_s < 120.0:
        raise ValueError(
            f"batched engine needs duration_s >= 120 (two 60 s occupancy "
            f"samples to interpolate); {sc.name!r} has {sc.duration_s:g}")
    dt = float(sc.telemetry.telemetry_s)
    n_ticks = int(math.floor(sc.duration_s / dt))
    t60 = np.arange(0.0, sc.duration_s, 60.0)
    fleet = sc.fleet
    server = fleet.server()
    budget = (resolve_ensemble_budget(sc) if budget_w is None
              else float(budget_w))
    members = spec.member_scenarios(budget)

    hierarchy = None
    node_matrix = None
    node_names: Tuple[str, ...] = ()
    node_shape: Tuple[int, ...] = ()
    node_capacity_w = None
    base_budgets = row_budgets(sc, budget, server)
    if sc.hierarchy is not None:
        if sc.hierarchy.n_rows != fleet.n_rows:
            raise ValueError(
                f"hierarchy shape {sc.hierarchy.shape} implies "
                f"{sc.hierarchy.n_rows} rows; fleet has {fleet.n_rows}")
        hierarchy = sc.hierarchy.build(base_budgets)
        row_budget = np.asarray(hierarchy.leaf_budget_w, dtype=np.float64)
        node_matrix = np.zeros((hierarchy.n_nodes, fleet.n_rows))
        for n in range(hierarchy.n_nodes):
            node_matrix[n, hierarchy.leaf_desc[n]] = 1.0
        node_names = tuple(hierarchy.names)
        if sc.hierarchy.level_capacity_w is not None:
            node_shape = tuple(sc.hierarchy.shape)
            node_capacity_w = np.asarray(
                hierarchy.capacity_w[hierarchy.n_leaves:], dtype=np.float64)
    else:
        row_budget = np.asarray(base_budgets, dtype=np.float64)

    alive, bscale = _lower_faults(sc, n_ticks, dt, fleet.n_rows, hierarchy)
    occ60 = _member_occupancy(sc, spec.seeds(), t60, fleet.n_rows,
                              fleet.n_servers)
    stride = max(1, math.ceil(n_ticks / _IMPACT_SLOTS))
    tc = sc.telemetry
    oob_ticks = max(1, math.ceil(tc.oob_latency_s / dt))
    brake_ticks = max(1, math.ceil(tc.brake_latency_s / dt))
    model = TickModel(
        base_name=sc.name, n_members=spec.n_seeds, n_rows=fleet.n_rows,
        n_ticks=n_ticks, dt=dt, occ60=occ60, alive=alive, budget_scale=bscale,
        row_budget_w=row_budget, n_servers=fleet.n_servers,
        power_scale=float(sc.power_scale),
        oob_ticks=oob_ticks, brake_ticks=brake_ticks,
        ring_depth=max(oob_ticks, brake_ticks) + 1,
        stride=stride, n_slots=math.ceil(n_ticks / stride),
        node_matrix=node_matrix, node_names=node_names,
        node_shape=node_shape, node_capacity_w=node_capacity_w,
        seeds=tuple(spec.seeds()),
        **_policy_constants(sc), **_power_constants(sc))
    return model, members, budget


# ---------------------------------------------------------------------------
# shared tick math (both backends call these with their own array module)
# ---------------------------------------------------------------------------

def _row_power_w(model: TickModel, occ, g_lp, g_hp):
    """Per-row watts at occupancy + clock factors ``g = f ** gamma`` (the
    closed-form fluid power plane; identical expression on both
    backends)."""
    busy = model.k_lp_w * g_lp + model.k_hp_w * g_hp
    return (model.power_scale * model.n_servers
            * (model.p0_srv_w + occ * busy))


def _lp_power_w(model: TickModel, occ, g_lp):
    return (model.power_scale * model.n_servers
            * (model.lp_share * model.p0_srv_w + occ * model.k_lp_w * g_lp))


# the clocks a row runs at besides the uncapped 1.0: the latch step's
# commands and the brake. LP holds 1.0, lp_t1, lp_t2 or the brake clock; HP
# 1.0, hp_t2 or the brake clock
_CLOCKS = ("lp_t1", "lp_t2", "hp_t2", "brake_freq")
_LP_CLOCKS = ("lp_t1", "lp_t2", "brake_freq")
_HP_CLOCKS = ("hp_t2", "brake_freq")
# programs of at least this many rows read f ** gamma from the clock table;
# narrower ones compute the pow. On a v5e the table made the [256, 56] site
# scan 2.5x faster, but one-row scans slower (2% at 512 members, 8% at
# 200): it removes 28 of 79 kernels a step, yet leaves two that read 18-19
# scalar operands and run about 4x longer, a cost fixed per kernel while
# the pow's grows with the rows (measured: 1 and 56 rows)
_CLOCK_TABLE_MIN_ROWS = 2


def _clock_factors(c, f_lp, f_hp, xp):
    """``(f_lp ** gamma, f_hp ** gamma)`` read from the scenario's table of
    clock factors (the ``_Consts`` fields ``gf_<clock>``, ``gf_one`` for
    1.0) by exact equality, for clocks the step can hold. A select chain:
    the device computes no ``pow``, which a TPU emulates in float64 at
    thousands of instructions."""
    def pick(f, clocks):
        g = c.gf_one
        for name in clocks:
            g = xp.where(f == getattr(c, name), getattr(c, "gf_" + name), g)
        return g
    return pick(f_lp, _LP_CLOCKS), pick(f_hp, _HP_CLOCKS)


class PolcaLatches(NamedTuple):
    """The boolean cap/brake state machine of one policy instance,
    vectorized over arbitrary leading shape (rows, or members x rows)."""

    t1c: object  # T1 cap active
    t2c: object  # T2 cap active
    hpc: object  # HP cap active (escalated)
    brk: object  # braking right now
    t2s: object  # escalation tick counter (int32)


def polca_latch_step(latches: PolcaLatches, p_obs, p_raw, lp_frac, c, xp, *,
                     esc: int, predictive: bool):
    """One vectorized tick of ``PolcaPolicy.observe`` over any batch shape.

    Mirrors ``core.policy`` line for line: the overload path sets every cap
    flag and skips releases; cap/escalation branches run only out of
    overload; releases read the *post-cap* flags, and the T1 release
    additionally requires T2 to have just released or been clear.
    ``predictive`` adds the informed-escalation shortcut of
    ``PredictivePolcaPolicy`` (p_obs is then the extrapolated power).

    Returns ``(latches', fire, lp_cmd, hp_cmd)`` — ``fire`` marks brake
    firings; the command planes are NaN where no command is issued, in the
    policy's cmd-list order (later overwrites earlier, the DES
    same-due-time rule).
    """
    t1c, t2c, hpc, brk, t2s = latches
    over = p_obs > 1.0
    fire = over & ~brk
    rel_brake = ~over & brk
    if predictive:
        informed = (t2c & ~hpc & (p_raw > c.t2)
                    & (lp_frac < p_raw - c.t2))
        t2s = xp.where(informed, esc, t2s)
    hi2 = p_obs > c.t2
    cap_t2 = ~over & hi2 & ~t2c
    esc_tick = ~over & hi2 & t2c & ~hpc
    t2s = xp.where(cap_t2, 0, xp.where(esc_tick, t2s + 1, t2s))
    cap_hp = esc_tick & (t2s >= esc)
    cap_t1 = ~over & ~hi2 & (p_obs > c.t1) & ~t1c
    t2c_mid = t2c | over | cap_t2
    t1c_mid = t1c | over | cap_t2 | cap_t1
    hpc_mid = hpc | over | cap_hp
    rel_t2 = ~over & t2c_mid & (p_obs < c.t2 - c.t2_buf)
    t2c = t2c_mid & ~rel_t2
    hpc = hpc_mid & ~rel_t2
    rel_t1 = (~over & t1c_mid & ~t2c
              & (p_obs < c.t1 - c.t1_buf))
    t1c = t1c_mid & ~rel_t1
    nanv = xp.full(p_obs.shape, xp.nan, dtype=p_obs.dtype)
    lp_cmd = nanv
    hp_cmd = nanv
    lp_cmd = xp.where(rel_brake, c.lp_t2, lp_cmd)
    hp_cmd = xp.where(rel_brake, c.hp_t2, hp_cmd)
    lp_cmd = xp.where(cap_t2, c.lp_t2, lp_cmd)
    hp_cmd = xp.where(cap_hp, c.hp_t2, hp_cmd)
    lp_cmd = xp.where(cap_t1, c.lp_t1, lp_cmd)
    lp_cmd = xp.where(rel_t2, c.lp_t1, lp_cmd)
    hp_cmd = xp.where(rel_t2, 1.0, hp_cmd)
    lp_cmd = xp.where(rel_t1, 1.0, lp_cmd)
    return (PolcaLatches(t1c=t1c, t2c=t2c, hpc=hpc, brk=over, t2s=t2s),
            fire, lp_cmd, hp_cmd)


def _slo_step(model: TickModel, occ, f_lp, f_hp, backlog_hp, backlog_lp, xp):
    """One tick of the per-priority fluid SLO proxy: slowdown from the DVFS
    perf model (``a/f + (1-a)``) plus a queue-delay backlog integrator —
    occupancy x slowdown > 1 means the row can't keep up and delay accrues.
    Returns (backlog_hp', backlog_lp', impact_hp, impact_lp)."""
    sd_hp = model.a_hp / xp.maximum(f_hp, 1e-3) + (1.0 - model.a_hp)
    sd_lp = model.a_lp / xp.maximum(f_lp, 1e-3) + (1.0 - model.a_lp)
    backlog_hp = xp.maximum(0.0, backlog_hp + (occ * sd_hp - 1.0) * model.dt)
    backlog_lp = xp.maximum(0.0, backlog_lp + (occ * sd_lp - 1.0) * model.dt)
    imp_hp = (sd_hp - 1.0) + backlog_hp / model.svc_hp
    imp_lp = (sd_lp - 1.0) + backlog_lp / model.svc_lp
    return backlog_hp, backlog_lp, imp_hp, imp_lp


def _node_w(shape: Tuple[int, ...], row_w, xp):
    """``[..., R]`` row watts -> ``[..., nodes]`` watts of the interior
    nodes of the regular tree with root-down fan-outs ``shape``, children
    before parents (the PowerHierarchy's order). Leaves under a node are
    contiguous, so each level is a reshape and a sum over the one below."""
    levels, x = [], row_w
    for fan in reversed(shape):
        x = xp.sum(x.reshape(x.shape[:-1] + (-1, fan)), axis=-1)
        levels.append(x)
    return xp.concatenate(levels, axis=-1)


def _n_nodes(shape: Tuple[int, ...]) -> int:
    """Interior nodes of the regular tree ``shape``."""
    return sum(math.prod(shape[:d]) for d in range(len(shape)))


def _interp_weights(model: TickModel) -> Tuple[np.ndarray, np.ndarray]:
    """Per-tick (left index, right weight) into the 60 s occupancy grid —
    precomputed once so both backends interpolate identically."""
    t = model.tick_times()
    g = t / 60.0
    n60 = model.occ60.shape[2]
    i = np.clip(np.floor(g).astype(np.int64), 0, n60 - 2)
    w = np.clip(g - i, 0.0, 1.0)
    return i, w


# ---------------------------------------------------------------------------
# numpy oracle: the tick/ring contract driven by the real policy objects
# ---------------------------------------------------------------------------

def _run_oracle(model: TickModel, members: List[Scenario],
                keep_series: bool) -> BatchedRun:
    N, R, T, D = model.n_members, model.n_rows, model.n_ticks, model.ring_depth
    i_idx, i_w = _interp_weights(model)
    t_ticks = model.tick_times()
    brake_fire = np.zeros((N, T, R), dtype=bool)
    n_brakes = np.zeros((N, R), dtype=np.int64)
    peak = np.zeros(N)
    mean = np.zeros(N)
    imp_hp = np.zeros((N, R, model.n_slots))
    imp_lp = np.zeros((N, R, model.n_slots))
    total = np.zeros((N, T)) if keep_series else None
    row_w_out = np.zeros((N, T, R)) if keep_series else None
    total_budget = model.total_budget_w
    fold = model.node_shape
    node_peak = np.zeros((N, _n_nodes(fold)))
    node_over = np.zeros((N, _n_nodes(fold)), dtype=np.int64)

    for m, member in enumerate(members):
        # the member's own fleet size where candidates share the model
        pm = (model if model.member_servers is None else dataclasses.replace(
            model, n_servers=int(model.member_servers[m])))
        policies = [member.policy.build() for _ in range(R)]
        f_lp = np.ones(R)
        f_hp = np.ones(R)
        ring = np.full((R, D, 2), np.nan)
        backlog_hp = np.zeros(R)
        backlog_lp = np.zeros(R)
        occ60 = model.occ60[m]  # [R, T60]
        frac_sum = 0.0
        frac_peak = 0.0
        for k in range(T):
            slot = k % D
            pend = ring[:, slot, :]
            has = ~np.isnan(pend)
            f_lp = np.where(has[:, 0], pend[:, 0], f_lp)
            f_hp = np.where(has[:, 1], pend[:, 1], f_hp)
            ring[:, slot, :] = np.nan
            occ = (occ60[:, i_idx[k]] * (1.0 - i_w[k])
                   + occ60[:, i_idx[k] + 1] * i_w[k]) * model.alive[k]
            g_lp = f_lp ** model.gamma
            rw = _row_power_w(pm, occ, g_lp, f_hp ** model.gamma)
            frac = float(rw.sum()) / total_budget
            frac_peak = max(frac_peak, frac)
            frac_sum += frac
            if keep_series:
                total[m, k] = frac
                row_w_out[m, k] = rw
            if fold:
                nw = _node_w(fold, rw, np)
                node_peak[m] = np.maximum(node_peak[m], nw)
                node_over[m] += nw > model.node_capacity_w
            tick_budget = model.row_budget_w * model.budget_scale[k]
            p = rw / tick_budget
            lp_frac = _lp_power_w(pm, occ, g_lp) / tick_budget
            for r in range(R):
                pol = policies[r]
                before = pol.n_brakes
                cmds = pol.observe(Telemetry(
                    t=float(t_ticks[k]), power_frac=float(p[r]),
                    lp_power_frac=float(lp_frac[r]), row_index=r))
                if pol.n_brakes > before:
                    brake_fire[m, k, r] = True
                for cmd in cmds:
                    d = model.brake_ticks if cmd.brake else model.oob_ticks
                    s = (k + d) % D
                    if cmd.lp_freq is not None:
                        ring[r, s, 0] = cmd.lp_freq
                    if cmd.hp_freq is not None:
                        ring[r, s, 1] = cmd.hp_freq
            backlog_hp, backlog_lp, ih, il = _slo_step(
                model, occ, f_lp, f_hp, backlog_hp, backlog_lp, np)
            if k % model.stride == 0:
                imp_hp[m, :, k // model.stride] = ih
                imp_lp[m, :, k // model.stride] = il
        n_brakes[m] = [pol.n_brakes for pol in policies]
        peak[m] = frac_peak
        mean[m] = frac_sum / T

    node_w = None
    if keep_series and model.node_matrix is not None:
        node_w = np.einsum("ntr,mr->ntm", row_w_out, model.node_matrix)
    run = BatchedRun(engine="numpy", model=model, brake_fire=brake_fire,
                     n_brakes=n_brakes, peak_frac=peak, mean_frac=mean,
                     impacts_hp=imp_hp, impacts_lp=imp_lp, total_frac=total,
                     row_w=row_w_out, node_w=node_w)
    if fold:
        run.node_peak_w, run.node_over_ticks = node_peak, node_over
    return run


# ---------------------------------------------------------------------------
# jax engine: scenario-axis vmap over (member vmap / chunked scan) over a
# lax.scan over ticks
# ---------------------------------------------------------------------------

class _JaxCfg(NamedTuple):
    """Static (compile-time) shape/flag key for the jitted runner.

    Deliberately *only* shapes and branch flags: every scalar constant
    (thresholds, power coefficients) travels as a traced operand in
    :class:`_Consts`, and the fleet size — which changes per
    ``plan_capacity`` candidate — as a per-member operand, so one compiled
    program serves a whole decision and every scenario of a grid bucket.
    ``jax_trace_count()`` is the regression hook asserting that."""

    T: int
    R: int
    D: int
    W: int
    S: int
    stride: int
    oob_ticks: int
    brake_ticks: int
    esc: int
    predictive: bool
    keep_series: bool
    keep_fire: bool
    chunk: int  # member-block size for the inner lax.scan; 0 = plain vmap
    # a rated tree's root-down fan-outs (TickModel.node_shape), folded in
    # the loop; () compiles the program without the fold
    fold: Tuple[int, ...] = ()
    # f ** gamma from the clock table (_clock_factors), else the pow; set
    # from R (_CLOCK_TABLE_MIN_ROWS)
    clock_table: bool = True


class _Consts(NamedTuple):
    """Traced per-scenario constants of the tick program. Scalar leaves are
    0-d (single scenario) or ``[M]`` (grid mode — the scenario-axis vmap
    maps over the leading axis of every leaf); ``row_budget`` is ``[R]`` /
    ``[M, R]``. ``n_servers`` is ``None`` in the operands: the fleet size
    is per member (the runner's ``[M, N]`` operand) and each member's
    program fills it in. The shared step math (:func:`_row_power_w`,
    :func:`polca_latch_step`, ...) reads its fields by name."""

    t1: object
    t2: object
    t1_buf: object
    t2_buf: object
    lp_t1: object
    lp_t2: object
    hp_t2: object
    brake_freq: object
    p0_srv_w: object
    k_lp_w: object
    k_hp_w: object
    lp_share: object
    gamma: object
    # f ** gamma of each clock the step can hold (_clock_factors)
    gf_one: object
    gf_lp_t1: object
    gf_lp_t2: object
    gf_hp_t2: object
    gf_brake_freq: object
    n_servers: object
    power_scale: object
    dt: object
    horizon: object
    a_hp: object
    a_lp: object
    svc_hp: object
    svc_lp: object
    total_budget: object
    row_budget: object
    node_cap: object = None  # [nodes] / [M, nodes] ratings; None = no fold


_CONST_SCALARS = (
    "t1", "t2", "t1_buf", "t2_buf", "lp_t1", "lp_t2", "hp_t2", "brake_freq",
    "p0_srv_w", "k_lp_w", "k_hp_w", "lp_share", "gamma", "power_scale", "dt",
    "horizon", "a_hp", "a_lp", "svc_hp", "svc_lp", "total_budget")

_MODEL_FIELD = dict(t1_buf="t1_buffer", t2_buf="t2_buffer",
                    lp_t1="lp_freq_t1", lp_t2="lp_freq_t2",
                    hp_t2="hp_freq_t2", horizon="horizon_s",
                    total_budget="total_budget_w")


def _model_const(model: TickModel, name: str) -> float:
    return float(getattr(model, _MODEL_FIELD.get(name, name)))


# every trace of the batched runner (== one XLA compile of one _JaxCfg +
# operand-shape combination), appended at trace time
_TRACE_EVENTS: List[_JaxCfg] = []


def jax_trace_count() -> int:
    """How many times this process has traced the batched jax runner.

    Each trace is one XLA compilation; constants are operands, so only a
    *new geometry* (fresh ``_JaxCfg`` or operand shapes) retraces. The
    planner regression gate asserts a multi-probe bisection traces once."""
    return len(_TRACE_EVENTS)


@lru_cache(maxsize=64)
def _jax_runner(cfg: _JaxCfg, mesh=None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def predict(c, t, p, consts):
        """PredictivePolcaPolicy._predict: windowed least-squares slope
        extrapolated horizon_s ahead, clamped below 1.0 unless the measured
        power already breached (brakes are never predicted). Raw samples
        enter the history, exactly as in the reference policy."""
        ht, hp, k = c["hist_t"], c["hist_p"], c["k"]
        W = cfg.W
        idx = jnp.minimum(k, W - 1)
        ins_t = ht.at[:, idx].set(t)
        ins_p = hp.at[:, idx].set(p)
        roll_t = jnp.roll(ht, -1, axis=1).at[:, -1].set(t)
        roll_p = jnp.roll(hp, -1, axis=1).at[:, -1].set(p)
        grow = k < W
        ht = jnp.where(grow, ins_t, roll_t)
        hp = jnp.where(grow, ins_p, roll_p)
        nn = jnp.minimum(k + 1, W).astype(jnp.float64)
        valid = (jnp.arange(W) < jnp.minimum(k + 1, W))[None, :]
        tm = jnp.sum(jnp.where(valid, ht, 0.0), axis=1) / nn
        pm = jnp.sum(jnp.where(valid, hp, 0.0), axis=1) / nn
        dt_ = jnp.where(valid, ht - tm[:, None], 0.0)
        dp_ = jnp.where(valid, hp - pm[:, None], 0.0)
        num = jnp.sum(dt_ * dp_, axis=1)
        den = jnp.sum(dt_ * dt_, axis=1)
        slope = num / jnp.where(den > 0.0, den, 1.0)
        p_ext = jnp.where((nn >= 3) & (den > 0.0),
                          jnp.maximum(p, p + slope * consts.horizon), p)
        p_obs = jnp.where(p <= 1.0, jnp.minimum(p_ext, 1.0 - 1e-9), p_ext)
        return dict(c, hist_t=ht, hist_p=hp), p_obs

    def run_scenario(occ60_all, srv_all, consts, xs):
        T, R, D, S = cfg.T, cfg.R, cfg.D, cfg.S

        def step_for(occ60, consts):
            def step(c, x):
                k, t, ii, iw, alive, bscale = x
                slot = k % D
                pend = lax.dynamic_index_in_dim(c["ring"], slot, axis=1,
                                                keepdims=False)  # [R, 2]
                has = ~jnp.isnan(pend)
                f_lp = jnp.where(has[:, 0], pend[:, 0], c["f_lp"])
                f_hp = jnp.where(has[:, 1], pend[:, 1], c["f_hp"])
                ring = lax.dynamic_update_index_in_dim(
                    c["ring"], jnp.full((R, 2), jnp.nan), slot, axis=1)
                occ = ((occ60[:, ii] * iw[0] + occ60[:, ii + 1] * iw[1])
                       * alive)
                if cfg.clock_table:
                    g_lp, g_hp = _clock_factors(consts, f_lp, f_hp, jnp)
                else:
                    g_lp, g_hp = f_lp ** consts.gamma, f_hp ** consts.gamma
                rw = _row_power_w(consts, occ, g_lp, g_hp)
                frac = jnp.sum(rw) / consts.total_budget
                tick_budget = consts.row_budget * bscale
                p_raw = rw / tick_budget
                lp_frac = _lp_power_w(consts, occ, g_lp) / tick_budget
                c = dict(c, f_lp=f_lp, f_hp=f_hp, ring=ring, k=k)
                if cfg.predictive:
                    c, p_obs = predict(c, t, p_raw, consts)
                else:
                    p_obs = p_raw
                lat = PolcaLatches(t1c=c["t1c"], t2c=c["t2c"], hpc=c["hpc"],
                                   brk=c["brk"], t2s=c["t2s"])
                lat, fire, lp_cmd, hp_cmd = polca_latch_step(
                    lat, p_obs, p_raw, lp_frac, consts, jnp,
                    esc=cfg.esc, predictive=cfg.predictive)
                c = dict(c, t1c=lat.t1c, t2c=lat.t2c, hpc=lat.hpc,
                         brk=lat.brk, t2s=lat.t2s,
                         nbr=c["nbr"] + fire.astype(jnp.int32))
                ring = c["ring"]
                s_oob = (k + cfg.oob_ticks) % D
                s_brk = (k + cfg.brake_ticks) % D
                oob_slot = lax.dynamic_index_in_dim(ring, s_oob, axis=1,
                                                    keepdims=False)
                oob_slot = jnp.stack([
                    jnp.where(jnp.isnan(lp_cmd), oob_slot[:, 0], lp_cmd),
                    jnp.where(jnp.isnan(hp_cmd), oob_slot[:, 1], hp_cmd)],
                    axis=1)
                ring = lax.dynamic_update_index_in_dim(ring, oob_slot, s_oob,
                                                       axis=1)
                brk_slot = lax.dynamic_index_in_dim(ring, s_brk, axis=1,
                                                    keepdims=False)
                brk_val = jnp.where(fire[:, None],
                                    jnp.full((R, 2), consts.brake_freq),
                                    brk_slot)
                ring = lax.dynamic_update_index_in_dim(ring, brk_val, s_brk,
                                                       axis=1)
                bh, bl, ih, il = _slo_step(consts, occ, f_lp, f_hp,
                                           c["backlog_hp"], c["backlog_lp"],
                                           jnp)
                imp = jnp.stack([ih, il], axis=1)  # [R, 2]
                zero = jnp.asarray(0, k.dtype)
                upd = lax.dynamic_update_slice(c["imp"], imp[None],
                                               (k // cfg.stride, zero, zero))
                imp_buf = jnp.where(k % cfg.stride == 0, upd, c["imp"])
                c = dict(c, ring=ring, backlog_hp=bh, backlog_lp=bl,
                         imp=imp_buf, peak=jnp.maximum(c["peak"], frac),
                         fsum=c["fsum"] + frac)
                if cfg.fold:
                    node_w = _node_w(cfg.fold, rw, jnp)
                    c = dict(c, node_peak=jnp.maximum(c["node_peak"], node_w),
                             node_over=c["node_over"] + (
                                 node_w > consts.node_cap).astype(jnp.int32))
                ys = ()
                if cfg.keep_fire:
                    ys += (fire,)
                if cfg.keep_series:
                    ys += (frac, rw)
                return c, ys
            return step

        def run_member(occ60, n_srv):
            carry = dict(
                f_lp=jnp.ones(R), f_hp=jnp.ones(R),
                ring=jnp.full((R, D, 2), jnp.nan),
                t1c=jnp.zeros(R, bool), t2c=jnp.zeros(R, bool),
                hpc=jnp.zeros(R, bool), brk=jnp.zeros(R, bool),
                t2s=jnp.zeros(R, jnp.int32), nbr=jnp.zeros(R, jnp.int32),
                backlog_hp=jnp.zeros(R), backlog_lp=jnp.zeros(R),
                imp=jnp.zeros((S, R, 2)), peak=jnp.asarray(0.0),
                fsum=jnp.asarray(0.0), k=jnp.asarray(0, jnp.int32),
            )
            if cfg.predictive:
                carry.update(hist_t=jnp.zeros((R, cfg.W)),
                             hist_p=jnp.zeros((R, cfg.W)))
            if cfg.fold:
                nodes = _n_nodes(cfg.fold)
                carry.update(node_peak=jnp.zeros(nodes),
                             node_over=jnp.zeros(nodes, jnp.int32))
            # the member's fleet folds into power_scale before the loop, so
            # a step multiplies by one loop-invariant factor as it does for
            # a scalar fleet size; power_scale * n_servers * (...) keeps
            # its order and its bits (x * 1.0 == x)
            fleet = consts._replace(power_scale=consts.power_scale * n_srv,
                                    n_servers=1.0)
            final, ys = lax.scan(step_for(occ60, fleet), carry, xs)
            out = dict(nbr=final["nbr"], peak=final["peak"],
                       mean=final["fsum"] / T, imp=final["imp"])
            if cfg.fold:
                out.update(node_peak=final["node_peak"],
                           node_over=final["node_over"])
            i = 0
            if cfg.keep_fire:
                out["fire"] = ys[i]
                i += 1
            if cfg.keep_series:
                out["frac"] = ys[i]
                out["row_w"] = ys[i + 1]
            return out

        if cfg.chunk <= 0:
            return jax.vmap(run_member)(occ60_all, srv_all)
        # bounded-memory tails: scan over member blocks so the in-flight
        # working set is one block's state, not all N members' at once
        N = occ60_all.shape[0]
        blocked = [a.reshape((N // cfg.chunk, cfg.chunk) + a.shape[1:])
                   for a in (occ60_all, srv_all)]
        _, outs = lax.scan(
            lambda _, blk: (None, jax.vmap(run_member)(*blk)), None, blocked)
        return jax.tree_util.tree_map(
            lambda a: a.reshape((N,) + a.shape[2:]), outs)

    # the jit takes this function's name: the program is ``jit_tick_scan``
    def tick_scan(occ60_g, srv_g, consts_g, t_g, ii_g, iw_g, alive_g,
                  bscale_g, ks):
        _TRACE_EVENTS.append(cfg)

        def scenario(occ60_all, srv_all, consts, t, ii, iw, alive, bscale):
            return run_scenario(occ60_all, srv_all, consts,
                                (ks, t, ii, iw, alive, bscale))

        # scenario axis on top of the member axis: one program, M scenarios.
        # t / ii / iw are geometry-determined (n_ticks, dt, n60 — all in
        # _geometry_key), hence identical across the bucket: in_axes=None
        # keeps the per-tick occ60 interpolation a dynamic-slice instead of
        # an M-batched gather (~1.5x per-member cost on CPU at M=4).
        return jax.vmap(scenario,
                        in_axes=(0, 0, 0, None, None, None, 0, 0))(
            occ60_g, srv_g, consts_g, t_g, ii_g, iw_g, alive_g, bscale_g)

    fn = tick_scan
    if mesh is not None:
        # shard the member axis (dim 1 of the occupancy and the fleet
        # sizes) over the mesh's "data" axis; constants/timelines
        # replicate. Each device runs the whole scan on its member shard —
        # no cross-device collectives in the hot loop, so throughput scales
        # with device count.
        from jax.sharding import PartitionSpec
        member = PartitionSpec(None, "data")
        rep = PartitionSpec()
        fn = jax.shard_map(
            tick_scan, mesh=mesh,
            in_specs=(member, member, rep, rep, rep, rep, rep, rep, rep),
            out_specs=member, check_vma=False)
    # donating the occupancy grid lets XLA reuse its buffer for outputs on
    # accelerators; the CPU backend has no donation and would only warn
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(fn, donate_argnums=donate)


def _geometry_key(model: TickModel) -> tuple:
    """The bucket key for grid lowering: two TickModels sharing this key
    compile to the same XLA program (same ``_JaxCfg`` + operand shapes) and
    can run stacked under the scenario-axis vmap."""
    return (model.n_ticks, model.n_rows, model.ring_depth,
            max(1, model.window), model.n_slots, model.stride,
            model.oob_ticks, model.brake_ticks, model.escalation_ticks,
            model.predictive, model.n_members, model.occ60.shape[2],
            float(model.dt), model.node_shape)


def _plan_bucket(models: Sequence[TickModel], *, keep_series: bool,
                 keep_fire: bool, member_chunk: Optional[int], mesh
                 ) -> Tuple[_JaxCfg, object, np.ndarray]:
    """The static program key, effective mesh and padded member index of one
    geometry bucket. Members are padded (cyclically) to the chunk x device
    multiple; padding members are independent lanes, sliced off after."""
    m0 = models[0]
    key0 = _geometry_key(m0)
    for m in models[1:]:
        if _geometry_key(m) != key0:
            raise ValueError(
                f"grid bucket mixes tick geometries: {_geometry_key(m)} vs "
                f"{key0} (bucket specs with run_batched_grid)")
    N = m0.n_members
    n_dev = 1
    if mesh is not None:
        n_dev = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
        if n_dev <= 1:
            mesh = None
    if member_chunk is None:
        # auto: cache-sized member blocks. The scan carry is ~2 KB/member,
        # so a flat vmap over 10^3+ members thrashes L2 and per-member
        # throughput drops ~40% (benchmarks/batched_engine.py measures the
        # cliff); scanning blocks of ~_AUTO_CHUNK_MEMBERS members (counted
        # across the whole scenario axis) keeps the live state
        # cache-resident long before memory becomes the binding constraint.
        # The block count is rounded so padding stays minimal.
        if N * len(models) <= _AUTO_CHUNK_MEMBERS:
            member_chunk = 0
        else:
            c0 = max(1, _AUTO_CHUNK_MEMBERS // len(models))
            n_blocks = math.ceil(N / (max(1, n_dev) * c0))
            member_chunk = math.ceil(N / (max(1, n_dev) * n_blocks))
    chunk = max(0, int(member_chunk or 0))
    mult = max(1, n_dev) * max(1, chunk)
    idx = np.resize(np.arange(N), N + (-N) % mult)
    cfg = _JaxCfg(T=m0.n_ticks, R=m0.n_rows, D=m0.ring_depth,
                  W=max(1, m0.window), S=m0.n_slots, stride=m0.stride,
                  oob_ticks=m0.oob_ticks, brake_ticks=m0.brake_ticks,
                  esc=m0.escalation_ticks, predictive=m0.predictive,
                  keep_series=keep_series, keep_fire=keep_fire, chunk=chunk,
                  fold=m0.node_shape,
                  clock_table=m0.n_rows >= _CLOCK_TABLE_MIN_ROWS)
    return cfg, mesh, idx


def _bucket_operands(models: Sequence[TickModel], idx: np.ndarray) -> tuple:
    """The runner's operands for one bucket, as host arrays (float64 where
    the program computes in float64). Per-scenario constants stack on a
    leading ``[M]`` axis, and each member's fleet size rides beside its
    occupancy as ``[M, N]``, padded with the same ``idx``; the tick grid
    (``t``/``ii``/``iw``, the last ``[T, 2]``: each tick's left and right
    weight) is shared across the bucket by construction
    (geometry-keyed) and passes unbatched so the runner's scenario vmap
    broadcasts it."""
    m0 = models[0]

    def f64(vals):
        return np.asarray(vals, dtype=np.float64)

    i_idx, i_w = _interp_weights(m0)
    scalars = {name: f64([_model_const(m, name) for m in models])
               for name in _CONST_SCALARS}
    # each clock's f ** gamma by numpy's array pow, the one the oracle
    # evaluates (with AVX-512 it may differ by an ulp from the scalar pow)
    clocks = dict(one=np.ones(len(models)),
                  **{name: scalars[name] for name in _CLOCKS})
    consts = _Consts(
        **scalars, n_servers=None,
        **{"gf_" + name: f ** scalars["gamma"] for name, f in clocks.items()},
        row_budget=f64(np.stack([m.row_budget_w for m in models])),
        node_cap=(f64(np.stack([m.node_capacity_w for m in models]))
                  if m0.node_shape else None))
    # both weights of each tick, (1 - w, w), come from the host: on a TPU
    # the loop's emulated float64 ``1.0 - w`` kept about float32 precision
    # for w < 0.5, which put row watts up to 6e-9 from the oracle's
    return (np.stack([m.occ60[idx] for m in models]),
            np.stack([m.servers()[idx] for m in models]), consts,
            f64(m0.tick_times()), np.asarray(i_idx, dtype=np.int32),
            f64(np.stack([1.0 - i_w, i_w], axis=1)),
            f64(np.stack([m.alive for m in models])),
            f64(np.stack([m.budget_scale for m in models])),
            np.arange(m0.n_ticks, dtype=np.int32))


def _run_jax_models(models: Sequence[TickModel], *, keep_series: bool,
                    keep_fire: bool = True,
                    member_chunk: Optional[int] = None,
                    mesh=None) -> List[BatchedRun]:
    """Run one geometry bucket of TickModels as a single device program.

    Per-scenario constants stack on a leading ``[M]`` axis and the runner
    vmaps the scenario axis over the member program — so an M-scenario grid
    (or an M-probe planner sweep re-using one compiled program) costs one
    dispatch, not M. ``member_chunk`` bounds device memory by scanning
    member blocks; ``mesh`` shards the member axis over its "data" axis.
    Results are invariant to both knobs (tier-1 asserted).

    Each step is a span of the current recorder (``batched/operands``,
    ``/h2d``, ``/run``, ``/d2h``, ``/unpack``), and the bytes each way are
    its counters ``batched_h2d_bytes_total`` and ``batched_d2h_bytes_total``.
    A rated tree adds ``batched_node_fold_cells_total`` (members x ticks x
    nodes folded; ``batched/run`` carries the nodes as its label) and a
    ``batched/node_stats`` span per model inside ``batched/unpack``.
    ``batched_clock_table_cells_total`` counts the members x ticks x rows
    whose ``f ** gamma`` the loop read from the clock table
    (:func:`_clock_factors`, 0 where it computed the pow); ``batched/run``
    carries the path as its label ``power``, ``table`` or ``pow``.
    Only a recorder that is enabled makes the copy to the device wait, so
    that ``batched/h2d`` times the copy and not its enqueue."""
    import jax

    rec = get_recorder()
    with rec.span("batched/operands"):
        cfg, mesh, idx = _plan_bucket(models, keep_series=keep_series,
                                      keep_fire=keep_fire,
                                      member_chunk=member_chunk, mesh=mesh)
        operands = _bucket_operands(models, idx)
    N = models[0].n_members
    with jax.enable_x64(True):
        with rec.span("batched/h2d"):
            args = jax.tree.map(jax.numpy.asarray, operands)
            if rec.enabled:
                jax.block_until_ready(args)
        with rec.span("batched/run", nodes=_n_nodes(cfg.fold),
                      power="table" if cfg.clock_table else "pow"):
            out = _jax_runner(cfg, mesh)(*args)
            jax.block_until_ready(out)
        del args  # the operands' device buffers go before the copy back
        with rec.span("batched/d2h"):
            out = {k: np.asarray(v) for k, v in out.items()}
    if rec.enabled:
        rec.counter("batched_h2d_bytes_total", float(sum(
            a.nbytes for a in jax.tree.leaves(operands))))
        rec.counter("batched_d2h_bytes_total",
                    float(sum(v.nbytes for v in out.values())))
        if cfg.fold:
            rec.counter("batched_node_fold_cells_total", float(
                N * len(models) * cfg.T * _n_nodes(cfg.fold)))
        rec.counter("batched_clock_table_cells_total",
                    float(N * len(models) * cfg.T * cfg.R * cfg.clock_table))
    runs: List[BatchedRun] = []
    with rec.span("batched/unpack"):
        for i, m in enumerate(models):
            sub = {k: v[i][:N] for k, v in out.items()}
            imp = sub["imp"]  # [N, S, R, 2]
            run = BatchedRun(
                engine="jax", model=m,
                brake_fire=(np.asarray(sub["fire"], dtype=bool)
                            if keep_fire else None),
                n_brakes=np.asarray(sub["nbr"], dtype=np.int64),
                peak_frac=np.asarray(sub["peak"], dtype=np.float64),
                mean_frac=np.asarray(sub["mean"], dtype=np.float64),
                impacts_hp=np.ascontiguousarray(
                    imp[:, :, :, 0].transpose(0, 2, 1)),
                impacts_lp=np.ascontiguousarray(
                    imp[:, :, :, 1].transpose(0, 2, 1)),
            )
            if keep_series:
                run.total_frac = np.asarray(sub["frac"], dtype=np.float64)
                run.row_w = np.asarray(sub["row_w"], dtype=np.float64)
                if m.node_matrix is not None:
                    run.node_w = np.einsum("ntr,mr->ntm", run.row_w,
                                           m.node_matrix)
            if cfg.fold:
                with rec.span("batched/node_stats"):
                    run.node_peak_w = np.asarray(sub["node_peak"],
                                                 dtype=np.float64)
                    run.node_over_ticks = np.asarray(sub["node_over"],
                                                     dtype=np.int64)
            runs.append(run)
    return runs


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def run_tick_model(model: TickModel, members: List[Scenario], *,
                   engine: str = "jax", keep_series: bool = True,
                   keep_brake_fire: bool = True,
                   member_chunk: Optional[int] = None,
                   mesh=None) -> BatchedRun:
    """Run a lowered tick program on one backend. ``engine="numpy"`` is the
    oracle (real policy objects through Telemetry); ``engine="jax"`` the
    vectorized device program. Differential tests run both on the same
    model and compare."""
    if engine == "numpy":
        return _run_oracle(model, members, keep_series)
    if engine == "jax":
        return _run_jax_models([model], keep_series=keep_series,
                               keep_fire=keep_brake_fire,
                               member_chunk=member_chunk, mesh=mesh)[0]
    raise ValueError(f"unknown batched engine {engine!r} "
                     "(expected 'numpy' or 'jax')")


def run_tick_models(models: Sequence[TickModel], *,
                    keep_series: bool = True, keep_brake_fire: bool = True,
                    member_chunk: Optional[int] = None,
                    mesh=None) -> List[BatchedRun]:
    """Run a same-geometry bucket of lowered tick programs as ONE
    scenario-vmapped jit call (DESIGN.md §16) and return one
    :class:`BatchedRun` per model, in order.

    This is the model-level grid entry — :func:`run_batched_grid` lowers
    specs, buckets them by :func:`_geometry_key`, and lands here. It is
    jax-engine only: the oracle has no scenario axis and runs per model
    via :func:`run_tick_model`."""
    return _run_jax_models(list(models), keep_series=keep_series,
                           keep_fire=keep_brake_fire,
                           member_chunk=member_chunk, mesh=mesh)


# dense-tail cutover: above this member count run_batched_ensemble stops
# materializing per-member python MemberStats/LatencyStats objects (O(N)
# python floats) and returns the vectorized EnsembleResult arrays instead
_MEMBER_STATS_LIMIT = 20_000


def _to_ensemble_result(model: TickModel, members: List[Scenario],
                        budget_w: float, run: BatchedRun,
                        member_stats: bool = True) -> EnsembleResult:
    """Adapt a BatchedRun to the EnsembleResult shape the planner and the
    distributional statistics consume. ``power_frac`` rows are member
    total-budget fractions (the same quantity the DES engine stacks —
    ``SimResult.power_w`` records the telemetry fraction series).

    ``member_stats=False`` is the dense-tail mode: the members list stays
    empty and per-member SLO impacts ride as ``[N, K]`` arrays — every
    distributional statistic on EnsembleResult falls back to the
    vectorized path (same numbers, no 10^5 python objects)."""
    t = model.tick_times()
    if run.total_frac is not None:
        power = np.asarray(run.total_frac)
        power_t = t
    else:
        power = np.zeros((0, 0))
        power_t = np.zeros(0)
    common = dict(
        base_name=model.base_name, budget_w=budget_w,
        power_t=power_t, power_frac=power,
        brake_counts=np.asarray(run.n_brakes.sum(axis=1)),
        peak_fracs=np.asarray(run.peak_frac),
        mean_fracs=np.asarray(run.mean_frac))
    if run.node_peak_w is not None:
        common.update(node_names=model.node_names[model.n_rows:],
                      node_peak_w=run.node_peak_w,
                      node_over_ticks=run.node_over_ticks)
    if not member_stats:
        N = run.impacts_hp.shape[0]
        return EnsembleResult(
            members=[],
            member_impacts_hp=(run.impacts_hp.reshape(N, -1)
                               if model.has_hp else np.zeros((N, 0))),
            member_impacts_lp=(run.impacts_lp.reshape(N, -1)
                               if model.has_lp else np.zeros((N, 0))),
            **common)
    stats: List[MemberStats] = []
    for m, sc in enumerate(members):
        series = (run.total_frac[m] if run.total_frac is not None else None)
        res = SimResult(
            latency=run.member_stats(m),
            n_brakes=int(run.n_brakes[m].sum()),
            n_dropped=0, n_completed=0, served_tokens=0.0,
            peak_power_frac=float(run.peak_frac[m]),
            mean_power_frac=float(run.mean_frac[m]),
            power_t=(t if series is not None else None),
            power_w=series)
        stats.append(MemberStats(sc, res, res.latency))
    return EnsembleResult(members=stats, **common)


def _auto_flags(model: TickModel, keep_series: Optional[bool],
                keep_brake_fire: Optional[bool],
                member_stats: Optional[bool]) -> Tuple[bool, bool, bool]:
    """Resolve the None-means-auto memory knobs from the model's size."""
    cells = model.n_members * model.n_ticks
    if keep_series is None:
        keep_series = cells <= _SERIES_CELL_LIMIT
    if keep_brake_fire is None:
        # the bool [N, T, R] plane; 50x the f64 series budget in cells
        keep_brake_fire = cells * model.n_rows <= 50 * _SERIES_CELL_LIMIT
    if member_stats is None:
        member_stats = model.n_members <= _MEMBER_STATS_LIMIT
    return keep_series, keep_brake_fire, member_stats


def run_batched_ensemble(spec: EnsembleSpec, *,
                         budget_w: Optional[float] = None,
                         engine: str = "jax",
                         keep_series: Optional[bool] = None,
                         keep_brake_fire: Optional[bool] = None,
                         member_stats: Optional[bool] = None,
                         member_chunk: Optional[int] = None,
                         mesh=None) -> EnsembleResult:
    """Evaluate an ensemble on the batched tick engine.

    The drop-in dense-tail counterpart of ``montecarlo.run_ensemble`` —
    same EnsembleResult surface, 10^5+ members in one device program.
    The ``None``-default knobs auto-scale with ensemble size (DESIGN.md
    §16 memory budget): ``keep_series`` keeps per-tick power series under
    4e6 member-tick cells; ``keep_brake_fire`` drops the [N, T, R] brake
    plane (counts survive) past 2e8 cells; ``member_stats`` switches to
    dense [N, K] impact arrays past 2e4 members. ``member_chunk`` scans
    member blocks for bounded memory and cache residency (``None`` = auto:
    ~512-member blocks once the run is big enough; ``0`` = flat vmap);
    ``mesh`` shards the member axis over a "data" mesh axis
    (``launch.mesh.data_mesh``)."""
    with get_recorder().span("mc/run_batched", base=spec.base.name,
                             members=spec.n_seeds, engine=engine):
        model, members, budget = lower_ensemble(spec, budget_w=budget_w)
        keep_series, keep_fire, member_stats = _auto_flags(
            model, keep_series, keep_brake_fire, member_stats)
        run = run_tick_model(model, members, engine=engine,
                             keep_series=keep_series,
                             keep_brake_fire=keep_fire,
                             member_chunk=member_chunk, mesh=mesh)
        return _to_ensemble_result(model, members, budget, run,
                                   member_stats=member_stats)


def run_batched_grid(specs: Sequence[EnsembleSpec], *,
                     budget_w: Optional[float] = None,
                     engine: str = "jax",
                     keep_series: Optional[bool] = None,
                     keep_brake_fire: Optional[bool] = None,
                     member_stats: Optional[bool] = None,
                     member_chunk: Optional[int] = None,
                     mesh=None) -> List[EnsembleResult]:
    """Evaluate M ensembles as (at most a few) single device programs.

    Specs are lowered individually (per-spec budget resolution unless
    ``budget_w`` pins one envelope), bucketed by tick geometry
    (:func:`_geometry_key`), and each bucket runs stacked under the
    scenario-axis vmap — the mc-* scenario family (shared fleet/duration/
    telemetry) is one bucket, so a 6-family CVaR frontier is one jit call.
    Results come back in spec order, one EnsembleResult per spec.

    ``engine="numpy"`` falls back to a per-scenario loop (the oracle is the
    reference semantics) — the grid API stays engine-agnostic for
    differential tests."""
    lowered = [lower_ensemble(s, budget_w=budget_w) for s in specs]
    with get_recorder().span("mc/run_grid", scenarios=len(specs),
                             members=sum(m.n_members for m, _, _ in lowered),
                             engine=engine):
        runs: List[Optional[BatchedRun]] = [None] * len(lowered)
        flags = [_auto_flags(m, keep_series, keep_brake_fire, member_stats)
                 for m, _, _ in lowered]
        if engine == "jax":
            buckets: Dict[tuple, List[int]] = {}
            for i, (m, _, _) in enumerate(lowered):
                # keep_* flags join the key: they change the traced program
                key = _geometry_key(m) + flags[i][:2]
                buckets.setdefault(key, []).append(i)
            for idxs in buckets.values():
                ks, kf, _ = flags[idxs[0]]
                bruns = _run_jax_models(
                    [lowered[i][0] for i in idxs], keep_series=ks,
                    keep_fire=kf, member_chunk=member_chunk, mesh=mesh)
                for i, r in zip(idxs, bruns):
                    runs[i] = r
        else:
            for i, (m, mem, _) in enumerate(lowered):
                runs[i] = run_tick_model(m, mem, engine=engine,
                                         keep_series=flags[i][0],
                                         keep_brake_fire=flags[i][1])
        return [_to_ensemble_result(m, mem, budget, run,
                                    member_stats=flags[i][2])
                for i, ((m, mem, budget), run) in enumerate(zip(lowered,
                                                                runs))]


# ---------------------------------------------------------------------------
# candidate rounds: the fleets of one planner decision in one scan
# ---------------------------------------------------------------------------

# what the candidate fleets of one decision may differ in: each member
# keeps its own occupancy, seed and fleet size
_PER_MEMBER_FIELDS = ("n_members", "occ60", "n_servers", "member_servers",
                      "seeds")


def stack_tick_models(models: Sequence[TickModel]) -> TickModel:
    """One TickModel whose member axis holds every model's members, in
    order, each with its own fleet size (``member_servers``).

    The models must agree in everything but their members: the candidate
    fleets of one ``plan_capacity`` decision do, since they share the
    scenario, the seeds and the pinned budget. Running the stack and
    slicing it back (:func:`split_run`) gives each model's own run."""
    m0 = models[0]
    for m in models[1:]:
        for f in dataclasses.fields(TickModel):
            if f.name in _PER_MEMBER_FIELDS:
                continue
            a, b = getattr(m0, f.name), getattr(m, f.name)
            if not (np.array_equal(a, b) if isinstance(a, np.ndarray)
                    else a == b):
                raise ValueError(
                    f"cannot stack tick models that differ in {f.name!r} "
                    f"({m0.base_name!r} vs {m.base_name!r})")
    return dataclasses.replace(
        m0, n_members=sum(m.n_members for m in models),
        occ60=np.concatenate([m.occ60 for m in models]),
        member_servers=np.concatenate([m.servers() for m in models]),
        seeds=tuple(s for m in models for s in m.seeds))


def split_run(run: BatchedRun, models: Sequence[TickModel]
              ) -> List[BatchedRun]:
    """The run of a stack of ``models`` cut back into one run per model,
    each with its own model."""
    planes = {f.name: getattr(run, f.name)
              for f in dataclasses.fields(BatchedRun)
              if f.name not in ("engine", "model")}
    out, lo = [], 0
    for m in models:
        hi = lo + m.n_members
        out.append(BatchedRun(engine=run.engine, model=m, **{
            k: (None if v is None else v[lo:hi]) for k, v in planes.items()}))
        lo = hi
    return out


def run_candidate_round(specs: Sequence[EnsembleSpec], *, budget_w: float,
                        n_lanes: int = 0,
                        keep_series: Optional[bool] = None,
                        keep_brake_fire: Optional[bool] = None,
                        member_stats: Optional[bool] = None,
                        member_chunk: Optional[int] = None,
                        mesh=None) -> List[Callable[[], EnsembleResult]]:
    """Evaluate candidate fleets of one decision in ONE jax-engine scan.

    Each spec is lowered on its own; the models stack on the member axis,
    in order, and run in one :func:`run_tick_model` call. Pads repeating
    the first candidate lead the stack up to ``n_lanes`` candidates, so
    that every round of a decision runs one program, and are dropped. The
    memory flags come from one candidate's model, not the stack's, so each
    candidate's result is the one :func:`run_batched_ensemble` gives it.
    Returns one function per spec that assembles its EnsembleResult when
    called: candidates nobody reads are never assembled."""
    lowered = [lower_ensemble(s, budget_w=budget_w) for s in specs]
    models = [m for m, _, _ in lowered]
    keep_series, keep_fire, member_stats = _auto_flags(
        models[0], keep_series, keep_brake_fire, member_stats)
    pads = max(0, n_lanes - len(lowered))
    stacked = lowered[:1] * pads + lowered
    run = run_tick_model(
        stack_tick_models([m for m, _, _ in stacked]),
        [sc for _, mem, _ in stacked for sc in mem], engine="jax",
        keep_series=keep_series, keep_brake_fire=keep_fire,
        member_chunk=member_chunk, mesh=mesh)
    runs = split_run(run, models[:1] * pads + models)[pads:]

    def assemble(i: int):
        model, members, budget = lowered[i]
        return lambda: _to_ensemble_result(model, members, budget, runs[i],
                                           member_stats=member_stats)
    return [assemble(i) for i in range(len(lowered))]
