"""Bring-up smoke run of the system's two device paths on a TPU.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # four chips: phase (b) sharded, only

One chip drives three phases through the entry points a user calls:

(a) planner    — ``plan_capacity`` on the ``cluster-2rack`` scenario with
                 ``engine="jax"``, and the same search on the in-process
                 tick oracle (``engine="batched-numpy"``); the decisions must
                 be identical.
(b) dense tail — a 12-row, 6-hour ensemble of 4,096 members on the device
                 engine with per-tick series kept; its first 64 members
                 against the tick oracle: brake-tick sets identical, power
                 within 1e-6 relative.
(c) serve      — ``ServeEngine`` for ``llama3.2-1b`` at full width (random
                 weights from a seed) answers 8 requests of 128 prompt
                 tokens and 32 output tokens: finite logits, identical greedy
                 tokens on a repeated call, prefill and decode logits in
                 agreement.

``--four-chips`` runs phase (b)'s ensemble sharded over a ``("data",)`` mesh
of four chips and the same ensemble on one of them, and requires identical
bits; it runs nothing else.

Everything runs in this one process: a chip belongs to one process at a
time. A failed check raises, and the script then exits non-zero without
its last line. With no TPU it exits non-zero before any work. The wall times
it prints are bring-up timings, not benchmark figures. The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED0 = 1000
POWER_RTOL = 1e-6  # the oracle contract: power series within 1e-6 relative
PREFILL_DECODE_RTOL = 0.06  # tests/test_system.py::test_prefill_decode_consistency


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# (a) planner
# ---------------------------------------------------------------------------

def phase_planner(*, n_seeds: int = 8, duration_s: float | None = None) -> dict:
    """``plan_capacity`` on the device engine and on the tick oracle; the
    probe path and the decision must be identical."""
    from repro.experiments import get_scenario
    from repro.provisioning.planner import plan_capacity

    base = get_scenario("cluster-2rack")
    if duration_s is not None:
        base = base.with_(duration_s=float(duration_s))
    t0 = time.perf_counter()
    dev = plan_capacity(base, engine="jax", n_seeds=n_seeds, seed0=SEED0)
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = plan_capacity(base, engine="batched-numpy", n_seeds=n_seeds,
                        seed0=SEED0)
    t_ref = time.perf_counter() - t0
    path_dev = [(p.added_servers, p.feasible) for p in dev.probes]
    path_ref = [(p.added_servers, p.feasible) for p in ref.probes]
    _check(path_dev == path_ref,
           f"planner probes differ: device {path_dev} vs oracle {path_ref}")
    _check(dev.safe_added_servers == ref.safe_added_servers,
           f"planner decision differs: device {dev.safe_added_servers} vs "
           f"oracle {ref.safe_added_servers} added servers")
    return dict(scenario=base.name, n_seeds=n_seeds,
                safe_added_servers=dev.safe_added_servers,
                n_probes=len(dev.probes), t_device_s=t_dev, t_oracle_s=t_ref)


# ---------------------------------------------------------------------------
# (b) dense tail
# ---------------------------------------------------------------------------

def tail_scenario(*, n_rows: int = 12, duration_s: float = 6 * 3600.0):
    """A non-routed row fleet under bursty near-peak traffic, hot enough
    that brakes fire in most members (the differential harness's family)."""
    from repro.experiments.scenario import FleetSpec, Scenario, TrafficSpec

    return Scenario(
        name=f"smoke-tail-{n_rows}row", duration_s=float(duration_s),
        fleet=FleetSpec(n_provisioned=20, added_frac=0.30, n_rows=n_rows,
                        rows_per_rack=max(1, n_rows // 2)),
        traffic=TrafficSpec(occ_peak=0.97, generator="bursty"),
        budget="nominal", power_scale=1.2, compare_to_reference=False)


def run_tail(n_members: int, *, mesh=None, n_rows: int = 12,
             duration_s: float = 6 * 3600.0):
    """Lower the tail ensemble and run it on the device engine, keeping the
    per-tick power series and the brake plane. Returns (model, run)."""
    from repro.provisioning.batched import lower_ensemble, run_tick_model
    from repro.provisioning.montecarlo import EnsembleSpec

    spec = EnsembleSpec(tail_scenario(n_rows=n_rows, duration_s=duration_s),
                        n_seeds=n_members, seed0=SEED0)
    model, members, _ = lower_ensemble(spec)
    run = run_tick_model(model, members, engine="jax", keep_series=True,
                         keep_brake_fire=True, mesh=mesh)
    return model, run


def _rel_err(got, want) -> float:
    import numpy as np

    scale = np.maximum(np.abs(want), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(got - want) / scale))


def phase_dense_tail(*, n_members: int = 4096, n_oracle: int = 64,
                     n_rows: int = 12, duration_s: float = 6 * 3600.0) -> dict:
    """The device engine on a dense tail; its first ``n_oracle`` members
    against the numpy tick oracle under the oracle contract."""
    import numpy as np

    from repro.provisioning.batched import lower_ensemble, run_tick_model
    from repro.provisioning.montecarlo import EnsembleSpec

    t0 = time.perf_counter()
    model, run = run_tail(n_members, n_rows=n_rows, duration_s=duration_s)
    t_dev = time.perf_counter() - t0
    N, T, R = n_members, model.n_ticks, n_rows
    _check(run.row_w.shape == (N, T, R) and run.total_frac.shape == (N, T),
           f"series shapes {run.row_w.shape}, {run.total_frac.shape}")
    _check(bool(np.isfinite(run.row_w).all()
                and np.isfinite(run.total_frac).all()),
           "device power series hold non-finite values")

    t0 = time.perf_counter()
    sc = tail_scenario(n_rows=n_rows, duration_s=duration_s)
    omodel, omembers, _ = lower_ensemble(
        EnsembleSpec(sc, n_seeds=n_oracle, seed0=SEED0))
    _check(np.array_equal(omodel.occ60, model.occ60[:n_oracle]),
           "oracle members are not the device run's first members")
    oracle = run_tick_model(omodel, omembers, engine="numpy", keep_series=True)
    t_ref = time.perf_counter() - t0

    fire = run.brake_fire[:n_oracle]
    flips = fire != oracle.brake_fire
    n_flips = int(flips.sum())
    # distance from the brake threshold (p > 1.0) of each disagreeing tick
    p = oracle.row_w / (omodel.row_budget_w * omodel.budget_scale)[None]
    dist = np.abs(p[flips] - 1.0)
    rel_row = _rel_err(run.row_w[:n_oracle], oracle.row_w)
    rel_total = _rel_err(run.total_frac[:n_oracle], oracle.total_frac)
    out = dict(n_members=N, n_ticks=T, n_rows=R, n_oracle=n_oracle,
               oracle_brake_ticks=int(oracle.brake_fire.sum()),
               device_brake_ticks=int(run.brake_fire.sum()),
               disagreeing_brake_ticks=n_flips,
               flip_dist_min=float(dist.min()) if n_flips else None,
               flip_dist_max=float(dist.max()) if n_flips else None,
               power_rel_err_row=rel_row, power_rel_err_total=rel_total,
               t_device_s=t_dev, t_oracle_s=t_ref)
    _say(f"[b] brake ticks: oracle {out['oracle_brake_ticks']} in "
         f"{n_oracle} members, device {out['device_brake_ticks']} in {N}; "
         f"disagreeing {n_flips} (distance from threshold: "
         f"{out['flip_dist_min']} .. {out['flip_dist_max']}); power rel err "
         f"row {rel_row!r} total {rel_total!r}")
    _check(n_flips == 0, f"{n_flips} brake ticks disagree with the oracle")
    _check(np.array_equal(run.n_brakes[:n_oracle], oracle.n_brakes),
           "brake counts disagree with the oracle")
    _check(rel_row <= POWER_RTOL and rel_total <= POWER_RTOL,
           f"power outside {POWER_RTOL} relative: row {rel_row}, "
           f"total {rel_total}")
    return out


def phase_sharded_tail(*, n_devices: int = 4, n_members: int = 4096,
                       n_rows: int = 12,
                       duration_s: float = 6 * 3600.0) -> dict:
    """Phase (b)'s ensemble sharded over ``n_devices`` against the same
    ensemble on one device (``mesh=None``): every output bit-identical, and
    every device held a share of the sharded run."""
    import jax
    import numpy as np

    from repro.launch.mesh import data_mesh

    devices = jax.devices()[:n_devices]
    t0 = time.perf_counter()
    _, sharded = run_tail(n_members, mesh=data_mesh(n_devices), n_rows=n_rows,
                          duration_s=duration_s)
    t_sharded = time.perf_counter() - t0
    # peaks are read before the one-device run, which touches device 0 only
    stats = [d.memory_stats() for d in devices]
    peaks = ([int(s["peak_bytes_in_use"]) for s in stats]
             if all(s and "peak_bytes_in_use" in s for s in stats) else None)
    t0 = time.perf_counter()
    _, single = run_tail(n_members, n_rows=n_rows, duration_s=duration_s)
    t_single = time.perf_counter() - t0
    fields = ("brake_fire", "n_brakes", "peak_frac", "mean_frac",
              "impacts_hp", "impacts_lp", "total_frac", "row_w")
    differ = [f for f in fields
              if not np.array_equal(getattr(sharded, f), getattr(single, f))]
    _say(f"[b4] sharded over {n_devices} devices vs one device: "
         f"{'bit-identical' if not differ else 'DIFFER in ' + ', '.join(differ)}"
         f"; peak bytes per device {peaks}")
    _check(not differ, f"sharded run differs from one device in {differ}")
    if peaks is not None:
        _check(min(peaks) >= 0.5 * max(peaks),
               f"shards are not spread over the devices: peaks {peaks}")
    return dict(n_devices=n_devices, n_members=n_members, peak_bytes=peaks,
                t_sharded_s=t_sharded, t_single_s=t_single)


# ---------------------------------------------------------------------------
# (c) serve
# ---------------------------------------------------------------------------

def phase_serve(*, arch: str = "llama3.2-1b", smoke: bool = False,
                n_requests: int = 8, prompt: int = 128,
                out_tokens: int = 32, seed: int = 0) -> dict:
    """``ServeEngine`` answers a batch of requests; greedy tokens repeat,
    logits are finite, and prefill agrees with prefill-then-decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, smoke_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import ServeEngine

    cfg = smoke_config(arch) if smoke else get_config(arch)
    mesh = make_local_mesh(1, 1)
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, mesh, max_len=prompt + out_tokens, batch=n_requests)
    jax.block_until_ready(eng.params)
    t_init = time.perf_counter() - t0
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n_requests, prompt)).astype(np.int32)
    t0 = time.perf_counter()
    out1 = eng.generate(tokens, out_tokens)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out2 = eng.generate(tokens, out_tokens)
    t_second = time.perf_counter() - t0
    _check(out1.shape == (n_requests, out_tokens),
           f"output shape {out1.shape}")
    _check(bool(((out1 >= 0) & (out1 < cfg.vocab_size)).all()),
           "generated token ids outside the vocabulary")
    _check(bool((out1 == out2).all()), "greedy tokens differ between calls")

    with jax.set_mesh(mesh):
        full, _ = eng.prefill(eng.params, {"tokens": jnp.asarray(tokens)})
        _, cache = eng.prefill(eng.params,
                               {"tokens": jnp.asarray(tokens[:, :-1])})
        dec, _ = eng.decode(eng.params, jnp.asarray(tokens[:, -1:]),
                            jnp.asarray(prompt - 1, jnp.int32), cache)
    a = np.asarray(full[:, -1, :], np.float32)
    b = np.asarray(dec[:, -1, :], np.float32)
    _check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
           "non-finite logits")
    rel = float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-6))
    _say(f"[c] {cfg.name}: {n_requests} requests x {prompt} prompt + "
         f"{out_tokens} output tokens; prefill/decode logits rel diff {rel!r}")
    _check(rel < PREFILL_DECODE_RTOL,
           f"prefill/decode logits differ: rel {rel} >= {PREFILL_DECODE_RTOL}")
    return dict(arch=cfg.name, n_layers=cfg.num_layers, d_model=cfg.d_model,
                vocab=cfg.vocab_size, prefill_decode_rel=rel,
                t_init_s=t_init, t_first_generate_s=t_first,
                t_second_generate_s=t_second)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

class _CacheEvents:
    """Counts JAX's persistent-cache hit and miss events."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _timed(label: str, fn, **kw) -> dict:
    t0 = time.perf_counter()
    out = fn(**kw)
    wall = time.perf_counter() - t0
    _say(f"{label}: {json.dumps(out)}")
    _say(f"{label} wall {wall:.3f} s (bring-up timing, not a benchmark "
         f"figure)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dense tail sharded over four chips "
                         "against one chip")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    n_need = 4 if args.four_chips else 1
    if len(devices) < n_need:
        print(f"chip_smoke: needs {n_need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = Path(enable_compile_cache())
    n_entries = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    events = _CacheEvents()
    jax.monitoring.register_event_listener(events)
    _say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"compile cache {cache_dir} holds {n_entries} entries")

    if args.four_chips:
        _timed("[b4] sharded dense tail", phase_sharded_tail, n_devices=4)
    else:
        _timed("[a] planner", phase_planner)
        _timed("[b] dense tail", phase_dense_tail)
        _timed("[c] serve", phase_serve)
    _say(f"compile cache: {events.hits} hits, {events.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
