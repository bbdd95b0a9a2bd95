"""Plain numpy reference of the POLCA tick model and the capacity planner.

Written from the published semantics (POLCA, arXiv:2308.12908: Algorithm 1,
Tables 1, 3 and 4) and the deployment stated in a configuration file under
``chipbench/configs``. It imports nothing of the system under test and takes
no number the system made at run time: every constant comes from the
configuration file, and every member's traffic is drawn again from its seed.

One tick of one row, in order:

1. a frequency command that falls due this tick takes effect;
2. occupancy is read off the 60 s curve by linear interpolation;
3. row power is ``scale * servers * (idle + occ * sum_w k_w * f_w^gamma)``;
4. Algorithm 1 observes power over the row's budget and issues commands,
   which fall due 40 s later (caps) or 5 s later (powerbrakes);
5. the fluid SLO proxy integrates each priority's backlog, and every
   ``stride``-th tick records the latency impact.

``dtype`` sets the precision of every floating-point array, so the same code
run in float32 is the lower-precision control.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

DAY = 86_400.0
WEEK = 7 * DAY
IMPACT_SLOTS = 256


def diurnal_curve(t: np.ndarray, *, peak: float, trough: float, noise: float,
                  curve_seed: int) -> np.ndarray:
    """The diurnal + weekly busy-server curve on the 60 s grid ``t``."""
    rng = np.random.default_rng(curve_seed)
    mid = 0.5 * (peak + trough)
    amp = 0.5 * (peak - trough)
    diurnal = mid + amp * np.sin(2 * np.pi * (t / DAY - 0.375))
    weekly = 1.0 - 0.06 * (np.sin(2 * np.pi * t / WEEK - 1.1) > 0.62)
    knots = t[:: max(1, len(t) // 200)]
    slow = np.interp(t, knots, rng.normal(0, noise, size=len(knots)))
    return np.clip(diurnal * weekly + slow, 0.05, 0.98)


def leaf_derates(shape: Sequence[int], fracs: Dict[str, float]) -> np.ndarray:
    """Per-row budget multiplier of a uniform budget tree: a derate on the
    node at root-down path ``"a/b"`` multiplies every row below it."""
    shape = tuple(int(s) for s in shape)
    n_rows = int(np.prod(shape))
    out = np.ones(n_rows)
    for row in range(n_rows):
        digits, rest = [], row
        for s in reversed(shape):
            digits.append(rest % s)
            rest //= s
        digits.reverse()
        for d in range(1, len(shape)):
            path = "/".join(str(x) for x in digits[:d])
            out[row] *= float(fracs.get(path, 1.0))
    return out


@dataclass(frozen=True)
class Plane:
    """Every constant of one deployment's tick model, from its config."""

    n_rows: int
    n_provisioned: int
    n_ticks: int
    dt: float
    n60: int
    stride: int
    n_slots: int
    oob_ticks: int
    brake_ticks: int
    ring: int
    row_budget: np.ndarray  # [R] watts
    power_scale: float
    p0_srv_w: float
    k_lp_w: float
    k_hp_w: float
    lp_share: float
    gamma: float
    a_hp: float
    a_lp: float
    svc_hp: float
    svc_lp: float
    t1: float
    t2: float
    t1_buffer: float
    t2_buffer: float
    lp_freq_t1: float
    lp_freq_t2: float
    hp_freq_t2: float
    brake_freq: float
    escalation_ticks: int
    curve: np.ndarray  # [T60] busy-server curve shared by rows and members
    jitter_salt: int


def plane_from_config(cfg: dict) -> Plane:
    """Derive the tick model's constants from a configuration file."""
    sc = cfg["scenario"]
    fleet, tel, traffic = sc["fleet"], sc["telemetry"], cfg["traffic_curve"]
    srv, pol = cfg["server"], cfg["policy_constants"]
    dt = float(tel["telemetry_s"])
    n_ticks = int(math.floor(sc["duration_s"] / dt))
    t60 = np.arange(0.0, sc["duration_s"], 60.0)
    stride = max(1, math.ceil(n_ticks / IMPACT_SLOTS))
    oob = max(1, math.ceil(tel["oob_latency_s"] / dt))
    brk = max(1, math.ceil(tel["brake_latency_s"] / dt))
    n_rows = int(fleet["n_rows"])
    budgets = np.full(n_rows, float(cfg["budget_w"]))
    if sc.get("hierarchy"):
        budgets = budgets * leaf_derates(sc["hierarchy"]["shape"],
                                         sc["hierarchy"].get("budget_fracs")
                                         or {})

    # per-server busy power and SLO sensitivity over the workload mix: each
    # class is busy in prefill for t_prefill and in decode for
    # mean_out * t_token; its utilisation maps to watts by the DVFS law
    k_lp = k_hp = lp_share = 0.0
    num_a = {"hp": 0.0, "lp": 0.0}
    num_svc = {"hp": 0.0, "lp": 0.0}
    wgt = {"hp": 0.0, "lp": 0.0}
    for wl in cfg["workload_mix"]:
        mean_out = 0.5 * (wl["out_range"][0] + wl["out_range"][1])
        t_total = wl["t_prefill_s"] + mean_out * wl["t_token_s"]
        f_pre = wl["t_prefill_s"] / t_total
        u_eff = cf_eff = 0.0
        for frac, pt in ((f_pre, wl["prefill"]), (1.0 - f_pre, wl["token"])):
            u = min(1.0, srv["w_compute"] * min(pt["u_compute"], 1.0)
                    + srv["w_memory"] * min(pt["u_memory"], 1.0))
            u_eff += frac * u
            cf_eff += frac * pt["compute_frac"]
        k_srv = srv["n_devices"] * (srv["p_peak_w"] - srv["idle_w"]) * u_eff
        share, mix = wl["share"], wl["priority_mix"]
        k_hp += share * mix * k_srv
        k_lp += share * (1.0 - mix) * k_srv
        lp_share += share * (1.0 - mix)
        for prio, w in (("hp", share * mix), ("lp", share * (1.0 - mix))):
            wgt[prio] += w
            num_a[prio] += w * cf_eff
            num_svc[prio] += w * t_total
    return Plane(
        n_rows=n_rows, n_provisioned=int(fleet["n_provisioned"]),
        n_ticks=n_ticks, dt=dt, n60=len(t60), stride=stride,
        n_slots=math.ceil(n_ticks / stride), oob_ticks=oob, brake_ticks=brk,
        ring=max(oob, brk) + 1, row_budget=budgets,
        power_scale=float(sc.get("power_scale", 1.0)),
        p0_srv_w=srv["n_devices"] * srv["idle_w"] + srv["other_w"],
        k_lp_w=k_lp, k_hp_w=k_hp, lp_share=lp_share, gamma=srv["gamma"],
        a_hp=num_a["hp"] / wgt["hp"], a_lp=num_a["lp"] / wgt["lp"],
        svc_hp=num_svc["hp"] / wgt["hp"], svc_lp=num_svc["lp"] / wgt["lp"],
        t1=pol["t1"], t2=pol["t2"], t1_buffer=pol["t1_buffer"],
        t2_buffer=pol["t2_buffer"], lp_freq_t1=pol["lp_freq_t1"],
        lp_freq_t2=pol["lp_freq_t2"], hp_freq_t2=pol["hp_freq_t2"],
        brake_freq=pol["brake_freq"],
        escalation_ticks=int(pol["escalation_ticks"]),
        curve=diurnal_curve(t60, peak=traffic["peak"],
                            trough=traffic["trough"], noise=traffic["noise"],
                            curve_seed=traffic["curve_seed"]),
        jitter_salt=int(traffic["jitter_salt"]))


def n_servers_at(plane: Plane, added_frac: float) -> int:
    return int(round(plane.n_provisioned * (1.0 + added_frac)))


def member_occupancy(plane: Plane, seeds: Sequence[int],
                     n_servers: int) -> np.ndarray:
    """[M, R, T60] occupancy: the shared curve plus each member's CLT
    jitter of the busy fraction, sigma = sqrt(occ (1 - occ) / servers)."""
    base = plane.curve
    sigma = np.sqrt(np.clip(base * (1.0 - base), 0.0, None) / n_servers)
    occ = np.empty((len(seeds), plane.n_rows, plane.n60))
    for m, seed in enumerate(seeds):
        for r in range(plane.n_rows):
            rng = np.random.default_rng([int(seed), r, plane.jitter_salt])
            occ[m, r] = np.clip(
                base + rng.standard_normal(plane.n60) * sigma, 0.0, 1.0)
    return occ


def simulate(plane: Plane, occ60: np.ndarray, n_servers: int,
             dtype=np.float64) -> Dict[str, np.ndarray]:
    """Run M members x R rows x T ticks. Returns per-member brake counts,
    peak and mean power over the total budget, and the [M, R, S] impact
    samples of each priority."""
    f = np.dtype(dtype).type
    M, R, T, D = occ60.shape[0], plane.n_rows, plane.n_ticks, plane.ring
    occ60 = occ60.astype(dtype)
    tick_t = (np.arange(T, dtype=np.float64) + 1.0) * plane.dt
    g = tick_t / 60.0
    left = np.clip(np.floor(g).astype(np.int64), 0, plane.n60 - 2)
    w_right = np.clip(g - left, 0.0, 1.0).astype(dtype)
    budget = plane.row_budget.astype(dtype)
    total_budget = f(plane.row_budget.sum())
    scale = f(plane.power_scale * n_servers)
    p0, k_lp, k_hp = f(plane.p0_srv_w), f(plane.k_lp_w), f(plane.k_hp_w)
    gamma, dt = f(plane.gamma), f(plane.dt)
    t1, t2 = f(plane.t1), f(plane.t2)
    t1_off, t2_off = f(plane.t1 - plane.t1_buffer), f(plane.t2 - plane.t2_buffer)
    one = f(1.0)

    f_lp = np.ones((M, R), dtype)
    f_hp = np.ones((M, R), dtype)
    ring = np.full((M, R, D, 2), np.nan, dtype)
    t1c = np.zeros((M, R), bool)
    t2c = np.zeros((M, R), bool)
    hpc = np.zeros((M, R), bool)
    braked = np.zeros((M, R), bool)
    since = np.zeros((M, R), np.int64)
    n_brakes = np.zeros((M, R), np.int64)
    back_hp = np.zeros((M, R), dtype)
    back_lp = np.zeros((M, R), dtype)
    imp_hp = np.zeros((M, R, plane.n_slots), dtype)
    imp_lp = np.zeros((M, R, plane.n_slots), dtype)
    peak = np.zeros(M, dtype)
    fsum = np.zeros(M, dtype)
    a_hp, a_lp = f(plane.a_hp), f(plane.a_lp)

    for k in range(T):
        # 1. due commands take effect
        slot = k % D
        due = ring[:, :, slot, :]
        f_lp = np.where(np.isnan(due[..., 0]), f_lp, due[..., 0])
        f_hp = np.where(np.isnan(due[..., 1]), f_hp, due[..., 1])
        ring[:, :, slot, :] = np.nan
        # 2.-3. occupancy and power
        i = left[k]
        occ = occ60[:, :, i] * (one - w_right[k]) + occ60[:, :, i + 1] * w_right[k]
        busy = k_lp * f_lp ** gamma + k_hp * f_hp ** gamma
        row_w = scale * (p0 + occ * busy)
        frac = row_w.sum(axis=1) / total_budget
        peak = np.maximum(peak, frac)
        fsum = fsum + frac
        p = row_w / budget
        # 4. Algorithm 1, in the order of its branches
        lp_cmd = np.full((M, R), np.nan, dtype)
        hp_cmd = np.full((M, R), np.nan, dtype)
        over = p > one
        fire = over & ~braked
        n_brakes += fire
        calm = ~over
        leave = calm & braked
        lp_cmd[leave] = plane.lp_freq_t2
        hp_cmd[leave] = plane.hp_freq_t2
        above_t2 = calm & (p > t2)
        cap_t2 = above_t2 & ~t2c
        wait = above_t2 & t2c & ~hpc
        since = np.where(cap_t2, 0, np.where(wait, since + 1, since))
        cap_hp = wait & (since >= plane.escalation_ticks)
        cap_t1 = calm & ~(p > t2) & (p > t1) & ~t1c
        t2c = t2c | cap_t2 | over
        t1c = t1c | cap_t2 | cap_t1 | over
        hpc = hpc | cap_hp | over
        braked = over
        lp_cmd[cap_t2] = plane.lp_freq_t2
        hp_cmd[cap_hp] = plane.hp_freq_t2
        lp_cmd[cap_t1] = plane.lp_freq_t1
        off_t2 = calm & t2c & (p < t2_off)
        t2c = t2c & ~off_t2
        hpc = hpc & ~off_t2
        lp_cmd[off_t2] = plane.lp_freq_t1
        hp_cmd[off_t2] = 1.0
        off_t1 = calm & t1c & ~t2c & (p < t1_off)
        t1c = t1c & ~off_t1
        lp_cmd[off_t1] = 1.0
        # commands fall due after the out-of-band or the powerbrake latency
        s_cap = (k + plane.oob_ticks) % D
        ring[:, :, s_cap, 0] = np.where(np.isnan(lp_cmd), ring[:, :, s_cap, 0],
                                        lp_cmd)
        ring[:, :, s_cap, 1] = np.where(np.isnan(hp_cmd), ring[:, :, s_cap, 1],
                                        hp_cmd)
        s_brk = (k + plane.brake_ticks) % D
        ring[:, :, s_brk, :] = np.where(fire[..., None], f(plane.brake_freq),
                                        ring[:, :, s_brk, :])
        # 5. fluid SLO proxy
        slow_hp = a_hp / np.maximum(f_hp, f(1e-3)) + (one - a_hp)
        slow_lp = a_lp / np.maximum(f_lp, f(1e-3)) + (one - a_lp)
        back_hp = np.maximum(f(0.0), back_hp + (occ * slow_hp - one) * dt)
        back_lp = np.maximum(f(0.0), back_lp + (occ * slow_lp - one) * dt)
        if k % plane.stride == 0:
            s = k // plane.stride
            imp_hp[:, :, s] = (slow_hp - one) + back_hp / f(plane.svc_hp)
            imp_lp[:, :, s] = (slow_lp - one) + back_lp / f(plane.svc_lp)

    return dict(n_brakes=n_brakes.sum(axis=1), peak=peak.astype(np.float64),
                mean=(fsum / f(T)).astype(np.float64),
                imp_hp=imp_hp.astype(np.float64),
                imp_lp=imp_lp.astype(np.float64))


def member_slo_miss(imp_hp: np.ndarray, imp_lp: np.ndarray,
                    slo: dict) -> np.ndarray:
    """[M] bool: the member misses a percentile gate of the SLO (Table 5),
    powerbrakes left out."""
    hp = imp_hp.reshape(len(imp_hp), -1)
    lp = imp_lp.reshape(len(imp_lp), -1)
    ok = ((np.percentile(hp, 50, axis=1) < slo["hp_p50"])
          & (np.percentile(hp, 99, axis=1) < slo["hp_p99"])
          & (np.percentile(lp, 50, axis=1) < slo["lp_p50"])
          & (np.percentile(lp, 99, axis=1) < slo["lp_p99"]))
    return ~ok


def plan(plane: Plane, slo: dict, *, n_seeds: int, seed0: int,
         max_added_frac: float, dtype=np.float64) -> Tuple[int, List[dict]]:
    """The largest added-server count whose ensemble has no powerbrake and
    no SLO miss in any member, by bisection over ``[0, n * max_added_frac]``.
    Returns ``(added servers, probes)``: each probe's verdict and its
    members' outputs."""
    seeds = [seed0 + k for k in range(n_seeds)]
    n_prov = plane.n_provisioned
    probes: List[dict] = []

    def probe(k: int) -> bool:
        n_servers = n_servers_at(plane, k / n_prov)
        out = simulate(plane, member_occupancy(plane, seeds, n_servers),
                       n_servers, dtype)
        brake_prob = float(np.mean(out["n_brakes"] > 0))
        slo_prob = float(np.mean(member_slo_miss(out["imp_hp"],
                                                 out["imp_lp"], slo)))
        feasible = brake_prob <= 1e-12 and slo_prob <= 1e-12
        probes.append(dict(added=k, feasible=feasible, brake_prob=brake_prob,
                           slo_prob=slo_prob, members=out))
        return feasible

    hi = max(1, int(math.floor(n_prov * max_added_frac)))
    if probe(hi):
        return hi, probes
    if not probe(0):
        return 0, probes
    lo = 0
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if probe(mid):
            lo = mid
        else:
            hi = mid
    return lo, probes
