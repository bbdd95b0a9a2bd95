"""Batched ensemble engine: oracle parity, grid lowering, dense-tail
throughput (DESIGN.md §15-16).

Validates the subsystem's claims:
  * the jax jit/vmap/`lax.scan` device program reproduces the numpy tick
    oracle exactly — brake-tick sets bit-identical, power series within
    1e-6 relative (the differential contract tier-1 drills property-style
    in tests/test_batched_parity.py and tests/test_grid_engine.py);
  * a grid of >= 4 scenarios x 10^3 members runs as ONE scenario-vmapped,
    once-traced device program, bit-identical to the per-scenario loop,
    with per-member throughput >= the flat-vmap engine it grew out of
    (auto member chunking keeps the ~2 KB/member scan carry
    cache-resident); at planner-probe shapes one grid dispatch is strictly
    faster than M sequential jit calls;
  * a 10^5-member tail completes under bounded memory via chunked member
    scans (bit-identical statistics to the unchunked program at 10^4), and
    the member axis shards across host devices without changing a bit;
  * those tails make CVaR meaningful: the `RiskConstraints.slo_cvar_alpha`
    statistic is finite, monotone in alpha, and degenerates to the worst
    member as alpha -> 1 on a 10^4-member tail.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import Bench, module_main, seeded
from repro.experiments.scenario import FleetSpec, Scenario, TrafficSpec
from repro.launch.mesh import data_mesh
from repro.provisioning import (
    EnsembleSpec,
    jax_trace_count,
    lower_ensemble,
    run_batched_ensemble,
    run_ensemble,
    run_tick_model,
    run_tick_models,
)

GRID_GENERATORS = ("diurnal", "bursty", "colocated", "nighttime")


def _scenario(occ_peak: float = 0.97, power_scale: float = 1.15,
              generator: str = "diurnal") -> Scenario:
    import repro.provisioning  # noqa: F401  (registers generator families)
    return seeded(Scenario(
        name=f"batched-bench-{generator}", duration_s=1800.0,
        fleet=FleetSpec(n_provisioned=20, added_frac=0.30, n_rows=2,
                        rows_per_rack=2),
        traffic=TrafficSpec(occ_peak=occ_peak, generator=generator),
        budget="nominal", power_scale=power_scale,
        compare_to_reference=False))


def _same_stats(a, b) -> bool:
    return (np.array_equal(a.brake_counts, b.brake_counts)
            and np.array_equal(a.peak_fracs, b.peak_fracs)
            and np.array_equal(a.mean_fracs, b.mean_fracs))


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def run(quick: bool = False) -> Bench:
    b = Bench()
    sc = _scenario()

    # ---- differential parity: numpy tick oracle vs jax device program -----
    # hot enough (scale 1.30) that the brake path is demonstrably covered
    hot = _scenario(occ_peak=0.99, power_scale=1.30)
    model, members, _ = lower_ensemble(EnsembleSpec(hot, n_seeds=4, seed0=77))
    t0 = time.perf_counter()
    oracle = run_tick_model(model, members, engine="numpy")
    jaxed = run_tick_model(model, members, engine="jax")
    us = (time.perf_counter() - t0) * 1e6
    brake_ok = bool(np.array_equal(oracle.brake_fire, jaxed.brake_fire))
    rel = float(np.max(np.abs(jaxed.total_frac - oracle.total_frac)
                       / np.maximum(np.abs(oracle.total_frac), 1e-12)))
    n_brakes = int(oracle.n_brakes.sum())
    b.add("batched/oracle_parity_4members",
          f"brake_sets_identical={brake_ok} ({n_brakes} brakes exercised) "
          f"power_rel_err={rel:.1e} (bound 1e-6)",
          us, brake_ok and rel <= 1e-6 and n_brakes > 0)

    # ---- grid: M scenarios as ONE scenario-vmapped program ----------------
    # Engine-layer measurements on pre-lowered models, so the measured
    # object is exactly the jit call. Two regimes, two gates:
    #  * 4 x 10^3 members — bit-identity vs the per-scenario loop, ONE
    #    trace, and per-member throughput >= the flat (unchunked) vmap the
    #    auto member_chunk replaces, on the same 4000-member workload: the
    #    scan carry is ~2 KB/member, so the flat program falls off the L2
    #    cliff that cache-sized member blocks avoid;
    #  * 4 x 50 members (a plan_capacity probe shape) — one dispatch
    #    strictly faster than M sequential jit calls. On a single-core
    #    host the big-N regime ties (identical member-tick work, nothing
    #    to parallelize), so dispatch amortization carries this gate.
    n_grid = 1000
    lowered = [lower_ensemble(EnsembleSpec(_scenario(generator=g),
                                           n_seeds=n_grid, seed0=11))
               for g in GRID_GENERATORS]
    g_models = [m for m, _, _ in lowered]
    M = len(g_models)
    tr0 = jax_trace_count()
    grid_runs = run_tick_models(g_models, keep_series=False)
    one_trace = jax_trace_count() - tr0 == 1
    loop_runs = [run_tick_model(m, mem, engine="jax", keep_series=False)
                 for m, mem, _ in lowered]
    grid_identical = all(
        np.array_equal(g.n_brakes, l.n_brakes)
        and np.array_equal(g.peak_frac, l.peak_frac)
        and np.array_equal(g.mean_frac, l.mean_frac)
        for g, l in zip(grid_runs, loop_runs))
    t_grid = min(_timed(lambda: run_tick_models(g_models, keep_series=False))
                 for _ in range(3))
    t_loop = min(_timed(lambda: [
        run_tick_model(m, mem, engine="jax", keep_series=False)
        for m, mem, _ in lowered]) for _ in range(3))
    mps_grid = M * n_grid / t_grid
    flat_model, flat_mem, _ = lower_ensemble(
        EnsembleSpec(_scenario(), n_seeds=M * n_grid, seed0=11))
    kw_flat = dict(engine="jax", keep_series=False, member_chunk=0)
    run_tick_model(flat_model, flat_mem, **kw_flat)
    t_flat = min(_timed(lambda: run_tick_model(flat_model, flat_mem,
                                               **kw_flat))
                 for _ in range(2))
    mps_flat = M * n_grid / t_flat
    b.add(f"batched/grid_{M}x{n_grid}_members",
          f"bit_identical_to_loop={grid_identical} one_trace={one_trace} "
          f"grid={t_grid * 1e3:.0f}ms ({mps_grid:.0f} members/s, "
          f"auto-chunked) vs flat-vmap engine {mps_flat:.0f} members/s on "
          f"the same {M * n_grid} members; {M} sequential jit calls: "
          f"{t_loop * 1e3:.0f}ms (ties within noise on 1 core)",
          t_grid * 1e6,
          grid_identical and one_trace and mps_grid >= mps_flat)

    n_small = 50
    sm_lowered = [lower_ensemble(EnsembleSpec(_scenario(generator=g),
                                              n_seeds=n_small, seed0=17))
                  for g in GRID_GENERATORS]
    sm_models = [m for m, _, _ in sm_lowered]
    run_tick_models(sm_models, keep_series=False)
    [run_tick_model(m, mem, engine="jax", keep_series=False)
     for m, mem, _ in sm_lowered]
    t_sm_grid = min(_timed(lambda: run_tick_models(sm_models,
                                                   keep_series=False))
                    for _ in range(5))
    t_sm_loop = min(_timed(lambda: [
        run_tick_model(m, mem, engine="jax", keep_series=False)
        for m, mem, _ in sm_lowered]) for _ in range(5))
    b.add(f"batched/grid_one_dispatch_vs_{M}_calls",
          f"{M} scenarios x {n_small} members (planner probe shape): "
          f"grid={t_sm_grid * 1e3:.0f}ms vs {M} sequential jit "
          f"calls={t_sm_loop * 1e3:.0f}ms "
          f"({t_sm_loop / t_sm_grid:.2f}x)",
          t_sm_grid * 1e6, t_sm_grid < t_sm_loop)

    # ---- dense-tail throughput: 10^4 members vs the DES fork pool ---------
    n_tail = 10_000
    t0 = time.perf_counter()
    tail = run_batched_ensemble(EnsembleSpec(sc, n_seeds=n_tail, seed0=1),
                                engine="jax", keep_series=False)
    t_jax = time.perf_counter() - t0
    mps_jax = n_tail / t_jax
    n_ref = 2 if quick else 4  # DES members measured, extrapolated linearly
    t0 = time.perf_counter()
    run_ensemble(EnsembleSpec(sc, n_seeds=n_ref, seed0=1), engine="numpy")
    mps_pool = n_ref / (time.perf_counter() - t0)
    speedup = mps_jax / max(1e-9, mps_pool)
    b.add(f"batched/throughput_{n_tail}_members",
          f"jax={mps_jax:.0f} members/s vs fork_pool={mps_pool:.1f} "
          f"members/s (est from {n_ref}) speedup={speedup:.0f}x on the same "
          f"scenario ({model.n_ticks} ticks x {model.n_rows} rows)",
          t_jax * 1e6, tail.n_members == n_tail and speedup > 1.0)

    # ---- chunked member scan: identical bits, bounded memory --------------
    # the 10^4 tail re-run in member_chunk blocks must be bit-identical to
    # the flat vmap above, then the big tail (10^5 full / 2x10^4 quick)
    # rides the same chunked program — live state per block stays
    # chunk-sized regardless of N
    chunk = 2048
    chunked = run_batched_ensemble(EnsembleSpec(sc, n_seeds=n_tail, seed0=1),
                                   engine="jax", keep_series=False,
                                   member_chunk=chunk)
    chunk_identical = _same_stats(tail, chunked)
    n_big = 20_000 if quick else 100_000
    t0 = time.perf_counter()
    big = run_batched_ensemble(EnsembleSpec(sc, n_seeds=n_big, seed0=1),
                               engine="jax", keep_series=False,
                               keep_brake_fire=False, member_chunk=4096)
    t_big = time.perf_counter() - t0
    b.add(f"batched/chunked_tail_{n_big}_members",
          f"chunk={chunk}_bit_identical_at_{n_tail}={chunk_identical}; "
          f"{n_big}-member tail in {t_big:.1f}s "
          f"({n_big / t_big:.0f} members/s, chunk=4096, series+brake-plane "
          f"dropped, dense member stats) brake_prob={big.brake_prob():.4f}",
          t_big * 1e6,
          chunk_identical and big.n_members == n_big
          and bool(np.isfinite(big.peak_fracs).all()))

    # ---- sharded member axis (host devices) -------------------------------
    import jax as _jax
    n_dev = len(_jax.devices())
    sharded = run_batched_ensemble(EnsembleSpec(sc, n_seeds=n_tail, seed0=1),
                                   engine="jax", keep_series=False,
                                   mesh=data_mesh())
    b.add(f"batched/sharded_{n_tail}_members",
          f"data_mesh over {n_dev} device(s) bit-identical to single-device "
          f"program: {_same_stats(tail, sharded)} (tests force 8 host CPU "
          "devices; smoke sets XLA_FLAGS for this module)",
          0.0, _same_stats(tail, sharded))

    # ---- CVaR on the dense tail -------------------------------------------
    alphas = (0.0, 0.9, 0.99, 0.999)
    t0 = time.perf_counter()
    slo = [tail.slo_cvar("low", a) for a in alphas]
    brk = [tail.brake_cvar(a) for a in alphas]
    us = (time.perf_counter() - t0) * 1e6
    monotone = (all(y >= x - 1e-12 for x, y in zip(slo, slo[1:]))
                and all(y >= x - 1e-12 for x, y in zip(brk, brk[1:])))
    worst = float(tail.brake_counts.max())
    degenerate = abs(brk[-1] - worst) <= max(1e-9, 0.05 * max(worst, 1.0))
    b.add("batched/cvar_tail_10k",
          f"slo_cvar(lp,p99)@a={{{','.join(f'{a:g}:{v:.3f}' for a, v in zip(alphas, slo))}}} "
          f"brake_cvar@0.999={brk[-1]:.1f} (max={worst:.0f}) "
          f"monotone={monotone}",
          us, monotone and degenerate and all(np.isfinite(slo)))
    return b


if __name__ == "__main__":
    module_main(run)
