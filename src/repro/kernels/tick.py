"""POLCA tick inner loop: shared vectorized step math + a Pallas kernel.

The batched ensemble engine (``provisioning.batched``, DESIGN.md §15-16)
advances N members x T ticks of the POLCA state machine. Its inner loop is
three fused pieces: the closed-form power fold over rows, the
:class:`~repro.core.policy.PolcaPolicy` latch/escalation update, and the
NaN-sentinel actuation-delay ring. This module is the single home of that
math, with three consumers:

* ``provisioning.batched._jax_runner`` — the ``lax.scan``/``vmap`` engine
  calls :func:`polca_latch_step` per tick with traced scalars (its power
  plane reads each row's ``f ** gamma`` from the scenario's clock factors);
* :func:`polca_tick_loop` — the same step inside one ``pl.pallas_call``:
  grid over member blocks, ``fori_loop`` over ticks, frequency/ring/latch
  state carried in-kernel, per-tick loads/stores against the block refs.
  Interpret mode on CPU (float64, the oracle-contract dtype); a TPU
  deployment would run float32 blocks with lanes on the member axis and
  accept the looser tolerance documented in DESIGN.md §16;
* :func:`~repro.kernels.ref.polca_tick_reference` — a plain scan+vmap
  reference harness for the kernel shell (padding, ring indexing, stores).

Semantics are *not* re-derived here twice: the genuine oracle is the numpy
tick backend driving the real policy objects
(``tests/test_batched_parity.py``), and every consumer above is
differentially gated against it — brake-tick sets bit-identical, power
series <= 1e-6 relative.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK_MEMBERS = 8
# ticks per grid step: each [TB, C, R] plane block is TB (8, 128) tiles
DEFAULT_BLOCK_TICKS = 256


class TickConsts(NamedTuple):
    """Per-scenario scalar constants of the tick program (policy thresholds
    + the closed-form power plane). Plain floats make it hashable (a static
    jit key for the kernel wrapper); the scan engine passes the same field
    names as traced leaves."""

    t1: float
    t2: float
    t1_buf: float
    t2_buf: float
    lp_t1: float
    lp_t2: float
    hp_t2: float
    brake_freq: float
    p0_srv_w: float
    k_lp_w: float
    k_hp_w: float
    lp_share: float
    gamma: float
    n_servers: float
    power_scale: float


class PolcaLatches(NamedTuple):
    """The boolean cap/brake state machine of one policy instance,
    vectorized over arbitrary leading shape (rows, or members x rows)."""

    t1c: jnp.ndarray  # T1 cap active
    t2c: jnp.ndarray  # T2 cap active
    hpc: jnp.ndarray  # HP cap active (escalated)
    brk: jnp.ndarray  # braking right now
    t2s: jnp.ndarray  # escalation tick counter (int32)


def row_power_w(c, occ, f_lp, f_hp):
    """Per-row watts at occupancy + frequency state — the expression
    ``provisioning.batched._row_power_w`` evaluates on ``f ** gamma`` (kept
    in lockstep by the differential parity gates)."""
    busy = c.k_lp_w * f_lp ** c.gamma + c.k_hp_w * f_hp ** c.gamma
    return c.power_scale * c.n_servers * (c.p0_srv_w + occ * busy)


def lp_power_w(c, occ, f_lp):
    return (c.power_scale * c.n_servers
            * (c.lp_share * c.p0_srv_w + occ * c.k_lp_w * f_lp ** c.gamma))


def polca_latch_step(latches: PolcaLatches, p_obs, p_raw, lp_frac, c, *,
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
        t2s = jnp.where(informed, esc, t2s)
    hi2 = p_obs > c.t2
    cap_t2 = ~over & hi2 & ~t2c
    esc_tick = ~over & hi2 & t2c & ~hpc
    t2s = jnp.where(cap_t2, 0, jnp.where(esc_tick, t2s + 1, t2s))
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
    nanv = jnp.full(p_obs.shape, jnp.nan, dtype=p_obs.dtype)
    lp_cmd = nanv
    hp_cmd = nanv
    lp_cmd = jnp.where(rel_brake, c.lp_t2, lp_cmd)
    hp_cmd = jnp.where(rel_brake, c.hp_t2, hp_cmd)
    lp_cmd = jnp.where(cap_t2, c.lp_t2, lp_cmd)
    hp_cmd = jnp.where(cap_hp, c.hp_t2, hp_cmd)
    lp_cmd = jnp.where(cap_t1, c.lp_t1, lp_cmd)
    lp_cmd = jnp.where(rel_t2, c.lp_t1, lp_cmd)
    hp_cmd = jnp.where(rel_t2, 1.0, hp_cmd)
    lp_cmd = jnp.where(rel_t1, 1.0, lp_cmd)
    return (PolcaLatches(t1c=t1c, t2c=t2c, hpc=hpc, brk=over, t2s=t2s),
            fire, lp_cmd, hp_cmd)


def _ring_get(ring, slot):
    """``ring[slot]`` as a select chain over the ``D`` static slots: Mosaic
    cannot lower a dynamic slice of a loop-carried value, and a select is
    exact, so the scan reference and the kernel read identical bits."""
    out = ring[0]
    for d in range(1, ring.shape[0]):
        out = jnp.where(slot == d, ring[d], out)
    return out


def _ring_set(ring, slot, val):
    """``ring.at[slot].set(val)`` as a per-slot select (see :func:`_ring_get`)."""
    return jnp.stack([jnp.where(slot == d, val, ring[d])
                      for d in range(ring.shape[0])])


def apply_ring_tick(ring, f_lp, f_hp, k, *, ring_depth: int):
    """Pop the actuation ring at tick k: apply any due command per frequency
    field, clear the slot. ``ring`` is ``[D, 2, ...]`` (NaN = no command).
    Returns ``(ring', f_lp', f_hp')``."""
    slot = k % ring_depth
    pend = _ring_get(ring, slot)
    has = ~jnp.isnan(pend)
    f_lp = jnp.where(has[0], pend[0], f_lp)
    f_hp = jnp.where(has[1], pend[1], f_hp)
    ring = _ring_set(ring, slot, jnp.full(ring.shape[1:], jnp.nan, ring.dtype))
    return ring, f_lp, f_hp


def push_ring_commands(ring, fire, lp_cmd, hp_cmd, brake_freq, k, *,
                       oob_ticks: int, brake_ticks: int, ring_depth: int):
    """Queue this tick's commands: OOB cap/release commands land
    ``oob_ticks`` ahead, brake commands ``brake_ticks`` ahead and overwrite
    both frequency fields (issued last, the DES same-due-time rule)."""
    D = ring_depth
    s_oob = (k + oob_ticks) % D
    s_brk = (k + brake_ticks) % D
    oob_slot = _ring_get(ring, s_oob)
    oob_slot = jnp.stack([
        jnp.where(jnp.isnan(lp_cmd), oob_slot[0], lp_cmd),
        jnp.where(jnp.isnan(hp_cmd), oob_slot[1], hp_cmd)], axis=0)
    ring = _ring_set(ring, s_oob, oob_slot)
    brk_slot = _ring_get(ring, s_brk)
    brk_val = jnp.where(fire[None], jnp.full_like(brk_slot, brake_freq),
                        brk_slot)
    return _ring_set(ring, s_brk, brk_val)


def _tick_init(C: int, R: int, D: int, dtype):
    f_lp = jnp.ones((C, R), dtype)
    f_hp = jnp.ones((C, R), dtype)
    ring = jnp.full((D, 2, C, R), jnp.nan, dtype)
    lat = PolcaLatches(
        t1c=jnp.zeros((C, R), bool), t2c=jnp.zeros((C, R), bool),
        hpc=jnp.zeros((C, R), bool), brk=jnp.zeros((C, R), bool),
        t2s=jnp.zeros((C, R), jnp.int32))
    return f_lp, f_hp, ring, lat


def _tick_body(k, carry, occ_k, bscale_k, row_budget, c: TickConsts, *,
               oob_ticks, brake_ticks, ring_depth, esc):
    """One tick on a ``[C, R]`` member block — shared verbatim between the
    Pallas kernel body and the scan reference, so the kernel test isolates
    the pallas shell (blocking, loads/stores) rather than re-proving the
    state machine. Returns ``(carry', row_w, fire)``."""
    f_lp, f_hp, ring, lat = carry
    ring, f_lp, f_hp = apply_ring_tick(ring, f_lp, f_hp, k,
                                       ring_depth=ring_depth)
    rw = row_power_w(c, occ_k, f_lp, f_hp)
    tick_budget = row_budget * bscale_k  # [R] broadcast over members
    p_raw = rw / tick_budget
    lp_frac = lp_power_w(c, occ_k, f_lp) / tick_budget
    lat, fire, lp_cmd, hp_cmd = polca_latch_step(
        lat, p_raw, p_raw, lp_frac, c, esc=esc, predictive=False)
    ring = push_ring_commands(ring, fire, lp_cmd, hp_cmd, c.brake_freq, k,
                              oob_ticks=oob_ticks, brake_ticks=brake_ticks,
                              ring_depth=ring_depth)
    return (f_lp, f_hp, ring, lat), rw, fire


def _tick_kernel(occ_ref, bscale_ref, rb_ref,
                 roww_ref, fire_ref, flp_ref, fhp_ref, nbr_ref,
                 f_s, ring_s, lat_s, nbr_s, *,
                 T, TB, oob_ticks, brake_ticks, ring_depth, esc,
                 c: TickConsts):
    """Pallas kernel body: one ``[TB, C, R]`` (ticks x members x rows) block
    of one member block. The grid walks member blocks (parallel) and, within
    each, time blocks in order; the frequency/ring/latch state and the brake
    count persist across time blocks in VMEM scratch. Ticks are the leading
    axis so every per-tick load and store is one ``[C, R]`` tile."""
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _():
        f_lp, f_hp, ring, lat = _tick_init(f_s.shape[1], f_s.shape[2],
                                           ring_depth, f_s.dtype)
        f_s[...] = jnp.stack([f_lp, f_hp])
        ring_s[...] = ring
        lat_s[...] = jnp.stack(_latches_i32(lat))
        nbr_s[...] = jnp.zeros(nbr_s.shape, jnp.int32)

    rb = rb_ref[...]

    # Mosaic cannot carry boolean vectors through a loop: the latches ride
    # the fori_loop carry as int32 0/1 and are unpacked per tick
    def body(j, state):
        f_lp, f_hp, ring, lat, nbr = state
        k = tb * TB + j
        lat = PolcaLatches(*(a != 0 for a in lat[:4]), t2s=lat[4])
        (f_lp, f_hp, ring, lat), rw, fire = _tick_body(
            k, (f_lp, f_hp, ring, lat), occ_ref[j], bscale_ref[j], rb, c,
            oob_ticks=oob_ticks, brake_ticks=brake_ticks,
            ring_depth=ring_depth, esc=esc)
        roww_ref[j] = rw
        fire_ref[j] = fire.astype(jnp.int32)
        flp_ref[j] = f_lp
        fhp_ref[j] = f_hp
        # ticks past T only pad the last time block: never count them
        nbr = nbr + jnp.where(fire & (k < T), 1, 0).astype(jnp.int32)
        return f_lp, f_hp, ring, _latches_i32(lat), nbr

    lat0 = lat_s[...]
    f_lp, f_hp, ring, lat, nbr = lax.fori_loop(
        0, TB, body, (f_s[0], f_s[1], ring_s[...],
                      tuple(lat0[i] for i in range(5)), nbr_s[...]))
    f_s[...] = jnp.stack([f_lp, f_hp])
    ring_s[...] = ring
    lat_s[...] = jnp.stack(lat)
    nbr_s[...] = nbr
    nbr_ref[...] = nbr


def _latches_i32(lat: PolcaLatches):
    return tuple(a.astype(jnp.int32) for a in lat)


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def polca_tick_loop(occ, bscale, row_budget, consts: TickConsts, *,
                    oob_ticks: int, brake_ticks: int, ring_depth: int,
                    esc: int, block_members: int = DEFAULT_BLOCK_MEMBERS,
                    block_ticks: int = DEFAULT_BLOCK_TICKS, interpret=None):
    """The non-predictive POLCA tick loop as one ``pallas_call``.

    ``occ`` is the *effective* per-tick occupancy ``[N, T, R]`` (60 s-grid
    interpolation x row-alive mask, precomputed by the lowering — the
    kernel owns the power fold + latch/ring update that dominates the scan
    body). ``bscale`` is the ``[T, R]`` fault budget scale, ``row_budget``
    the ``[R]`` static budgets. Members are padded to a multiple of
    ``block_members`` and ticks to a multiple of ``block_ticks``; the grid
    walks member blocks x time blocks, and each member block carries its
    state through its time blocks in order.

    Returns ``dict(row_w=[N, T, R], fire=[N, T, R] bool,
    f_lp=[N, T, R], f_hp=[N, T, R], n_brakes=[N, R] int32)`` — the
    frequency planes let the SLO fluid proxy run as a cheap post-pass.
    """
    from jax.experimental.pallas import tpu as pltpu

    N, T, R = occ.shape
    C = max(1, min(int(block_members), N))
    TB = max(1, min(int(block_ticks), T))
    n_pad = (-N) % C
    t_pad = (-T) % TB
    occ = jnp.pad(jnp.moveaxis(occ, 1, 0), ((0, t_pad), (0, n_pad), (0, 0)),
                  mode="edge")  # [Tp, Np, R]
    bscale = jnp.pad(bscale, ((0, t_pad), (0, 0)), mode="edge")[:, None, :]
    B, NT = (N + n_pad) // C, (T + t_pad) // TB
    D = int(ring_depth)
    dtype = occ.dtype
    kernel = functools.partial(
        _tick_kernel, T=T, TB=TB, oob_ticks=int(oob_ticks),
        brake_ticks=int(brake_ticks), ring_depth=D, esc=int(esc), c=consts)
    plane = pl.BlockSpec((TB, C, R), lambda b, t: (t, b, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, NT),
        in_specs=[
            plane,
            pl.BlockSpec((TB, 1, R), lambda b, t: (t, 0, 0)),
            pl.BlockSpec((1, R), lambda b, t: (0, 0)),
        ],
        out_specs=[plane, plane, plane, plane,
                   pl.BlockSpec((C, R), lambda b, t: (b, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(occ.shape, dtype),
            jax.ShapeDtypeStruct(occ.shape, jnp.int32),
            jax.ShapeDtypeStruct(occ.shape, dtype),
            jax.ShapeDtypeStruct(occ.shape, dtype),
            jax.ShapeDtypeStruct((B * C, R), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, C, R), dtype),
            pltpu.VMEM((D, 2, C, R), dtype),
            pltpu.VMEM((5, C, R), jnp.int32),
            pltpu.VMEM((C, R), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_auto_interpret(interpret),
    )(occ, bscale, row_budget[None, :])
    row_w, fire, f_lp, f_hp = (jnp.moveaxis(a[:T, :N], 0, 1) for a in out[:4])
    return dict(row_w=row_w, fire=fire != 0, f_lp=f_lp, f_hp=f_hp,
                n_brakes=out[4][:N])
