"""Plain numpy reference of a POLCA site: rows under a rated power tree.

Each row runs as ``ticksim`` runs it (POLCA, arXiv:2308.12908: Algorithm 1,
Tables 1, 3 and 4), under its own row budget. Every tick, each member's row
watts are summed up the tree the configuration states
(``scenario.hierarchy``: the root-down fan-outs, and each interior level's
rating in watts, after Wu et al., Dynamo, ISCA 2016 section 2), and each
interior node keeps its peak watts and its count of ticks whose load
exceeds its rating. Nothing brakes on a node: POLCA controls each row alone,
and the ratings are read, not enforced.

Like ``ticksim`` it imports nothing of the system under test and takes every
constant from the configuration. The tick loop is ``ticksim.simulate``'s,
with the actuation ring laid out ``[D, 2, M, R]`` so that a due slot is one
contiguous block (``tests`` hold its row answers to ``ticksim``'s, bit for
bit). ``dtype`` sets the precision of every floating-point array, so the
same code in float32 is the lower-precision control.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from chipbench.reference import ticksim


@dataclass(frozen=True)
class Tree:
    """A regular power tree over the rows: interior nodes children first
    (the deepest level first, the root last), each with the half-open range
    of rows beneath it and its rating in watts."""

    names: Tuple[str, ...]
    spans: Tuple[Tuple[int, int], ...]
    rating_w: np.ndarray  # [nodes]


def tree_from_config(cfg: dict) -> Tree:
    """The rated tree of ``scenario.hierarchy``. A node is named by its
    level and its root-down path (``sb0.1``: the second SB of the first
    MSB); the root by its level alone. Every level states its rating."""
    h = cfg["scenario"]["hierarchy"]
    shape = [int(s) for s in h["shape"]]
    levels, ratings = h["level_names"], h["level_capacity_w"]
    names: List[str] = []
    spans: List[Tuple[int, int]] = []
    rating: List[float] = []
    for d in reversed(range(len(shape))):
        n_nodes = int(np.prod(shape[:d], dtype=np.int64))
        rows_each = int(np.prod(shape[d:], dtype=np.int64))
        for j in range(n_nodes):
            digits, rest = [], j
            for fan in reversed(shape[:d]):
                digits.append(rest % fan)
                rest //= fan
            path = ".".join(str(x) for x in reversed(digits))
            names.append(levels[d] + path)
            spans.append((j * rows_each, (j + 1) * rows_each))
            rating.append(float(ratings[d]))
    return Tree(tuple(names), tuple(spans), np.asarray(rating))


def node_watts(tree: Tree, row_w: np.ndarray) -> np.ndarray:
    """[M, R] row watts -> [M, nodes]: each node's rows summed."""
    return np.stack([row_w[:, lo:hi].sum(axis=1) for lo, hi in tree.spans],
                    axis=1)


def simulate(plane: ticksim.Plane, tree: Tree, occ60: np.ndarray,
             n_servers: int, dtype=np.float64) -> Dict[str, np.ndarray]:
    """Run M members x R rows x T ticks. Returns ``ticksim.simulate``'s
    answers and, per member and interior node, ``node_peak`` (watts) and
    ``node_over`` (ticks over the node's rating)."""
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
    rating = tree.rating_w.astype(dtype)

    f_lp = np.ones((M, R), dtype)
    f_hp = np.ones((M, R), dtype)
    ring = np.full((D, 2, M, R), np.nan, dtype)
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
    node_peak = np.zeros((M, len(tree.names)), dtype)
    node_over = np.zeros((M, len(tree.names)), np.int64)
    a_hp, a_lp = f(plane.a_hp), f(plane.a_lp)

    for k in range(T):
        # 1. due commands take effect
        due = ring[k % D]
        f_lp = np.where(np.isnan(due[0]), f_lp, due[0])
        f_hp = np.where(np.isnan(due[1]), f_hp, due[1])
        due[...] = np.nan
        # 2.-3. occupancy and power, then the tree above the rows
        i = left[k]
        occ = occ60[:, :, i] * (one - w_right[k]) + occ60[:, :, i + 1] * w_right[k]
        busy = k_lp * f_lp ** gamma + k_hp * f_hp ** gamma
        row_w = scale * (p0 + occ * busy)
        frac = row_w.sum(axis=1) / total_budget
        peak = np.maximum(peak, frac)
        fsum = fsum + frac
        node_w = node_watts(tree, row_w)
        node_peak = np.maximum(node_peak, node_w)
        node_over += node_w > rating
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
        cap = ring[(k + plane.oob_ticks) % D]
        cap[0] = np.where(np.isnan(lp_cmd), cap[0], lp_cmd)
        cap[1] = np.where(np.isnan(hp_cmd), cap[1], hp_cmd)
        brk = ring[(k + plane.brake_ticks) % D]
        brk[...] = np.where(fire[None], f(plane.brake_freq), brk)
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
                imp_lp=imp_lp.astype(np.float64),
                node_peak=node_peak.astype(np.float64), node_over=node_over)


def simulate_seeds(seeds: Sequence[int], cfg: dict,
                   dtype: str = "float64") -> Dict[str, np.ndarray]:
    """The members of ``seeds`` of the configuration's site, drawn and run
    from the configuration alone."""
    plane = ticksim.plane_from_config(cfg)
    n_servers = ticksim.n_servers_at(plane,
                                     cfg["scenario"]["fleet"]["added_frac"])
    occ = ticksim.member_occupancy(plane, seeds, n_servers)
    return simulate(plane, tree_from_config(cfg), occ, n_servers,
                    np.dtype(dtype))
