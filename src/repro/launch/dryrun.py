import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder devices; never claim a chip

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST stay first (before any jax import): jax locks the
device count at first init, and the production meshes need 512 placeholder
host devices. Smoke tests and benchmarks never import this module.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --out results/dryrun.jsonl
  PYTHONPATH=src python -m repro.launch.dryrun ... --multi-pod   # 2x16x16 mesh

Each cell: jit(step).lower(**input_specs).compile() under the production mesh,
then memory_analysis() (proves it fits) and cost_analysis() + HLO collective
parse (feeds EXPERIMENTS.md §Roofline).
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import assigned_archs, get_config
from repro.launch.inputs import input_specs, make_rules, split_seq
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import abstract_state, build_serve_step
from repro.models.config import SHAPES_BY_NAME, shape_applicable
from repro.obs.log import get_logger
from repro.optim import Optimizer
from repro.parallel.roofline import HBM_BYTES, build_roofline_extrapolated

log = get_logger(__name__)


def _lower_compile(cfg, shape, mesh, rules):
    step, opt = build_serve_step(cfg, shape, mesh, rules)
    specs = input_specs(cfg, shape, mesh, rules)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state = abstract_state(cfg, mesh, rules, opt)
            lowered = jax.jit(step).lower(state, specs)
        elif shape.kind == "prefill":
            state = abstract_state(cfg, mesh, rules, None)
            lowered = jax.jit(step).lower(state["params"], specs)
        else:
            state = abstract_state(cfg, mesh, rules, None)
            lowered = jax.jit(step).lower(state["params"], specs["token"],
                                          specs["pos"], specs["cache"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
    return compiled, t_lower, time.time() - t0 - t_lower


def run_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if overrides:
        rec["overrides"] = overrides
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rules = make_rules(cfg, shape, mesh)

    # Compile 1 (scan form): deployment artifact — memory_analysis ("fits")
    # reflects real loop-form buffer liveness.
    compiled, t_lower, t_compile = _lower_compile(cfg, shape, mesh, rules)
    mem = compiled.memory_analysis()

    # Compiles 2+3 (G=1 and G=2 fully unrolled): XLA cost analysis counts
    # while-loop bodies once, and fully unrolling 61-group models is
    # prohibitive — so we compile 1-group and 2-group variants (loops elide)
    # and extrapolate linearly: cost(G) = cost1 + (G-1) * (cost2 - cost1).
    # Exact because groups are computationally identical; cross-checked
    # against the full unroll on llama3.2-1b x train_4k (within 2%).
    def grouped(k):
        over = {"num_layers": k * len(cfg.pattern), "unroll_layers": True}
        if cfg.is_encoder_decoder:
            assert cfg.num_encoder_layers == cfg.num_groups, cfg.name
            over["num_encoder_layers"] = k
        return cfg.replace(**over)

    comp1, _, t_u1 = _lower_compile(grouped(1), shape, mesh, rules)
    comp2, _, t_u2 = _lower_compile(grouped(2), shape, mesh, rules)
    t_compile_u = t_u1 + t_u2

    enc_S, dec_S = split_seq(cfg, shape.seq_len)
    roof = build_roofline_extrapolated(comp1, comp2, cfg, shape, n_dev, enc_S, dec_S)
    bytes_per_dev = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        compile_unrolled_s=round(t_compile_u, 1),
        arg_bytes=mem.argument_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        out_bytes=mem.output_size_in_bytes,
        alias_bytes=mem.alias_size_in_bytes,
        bytes_per_device=bytes_per_dev,
        fits_hbm=bool(bytes_per_dev <= HBM_BYTES),
        roofline=roof.to_dict(),
    )
    if verbose:
        log.info(f"[{rec['mesh']}] {arch} x {shape_name}: "
                 f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
                 f"{bytes_per_dev/2**30:.2f} GiB/dev (fits={rec['fits_hbm']}) | "
                 f"bottleneck={roof.bottleneck} "
                 f"[C={roof.t_compute*1e3:.2f}ms M={roof.t_memory*1e3:.2f}ms "
                 f"X={roof.t_collective*1e3:.2f}ms] mfu_bound={roof.mfu_bound:.3f}")
        log.info("  memory_analysis: %s", mem)
        log.info("  analytic flops/device: %.3e bytes/device: %.3e | "
                 "hlo flops/device: %.3e bytes/device: %.3e",
                 roof.flops_per_device, roof.hbm_bytes_per_device,
                 roof.hlo_flops_per_device, roof.hlo_bytes_per_device)
        log.info("  collectives: %s %s", roof.collectives.ops,
                 {k: f"{v/2**20:.1f}MiB"
                  for k, v in roof.collectives.bytes_by_kind.items()})
    return rec


def run_all(out_path: str, multi_pod: bool, archs=None, shapes=None) -> int:
    """Run every cell in a subprocess (isolation: one bad cell can't sink the
    fleet run) appending JSONL records."""
    archs = archs or assigned_archs()
    shapes = shapes or list(SHAPES_BY_NAME)
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name, "--out", out_path]
            if multi_pod:
                cmd.append("--multi-pod")
            try:
                r = subprocess.run(cmd, env={**os.environ, "PYTHONPATH": "src"},
                                   timeout=1800)
                rc = r.returncode
            except subprocess.TimeoutExpired:
                rc = -1
            if rc != 0:
                failures += 1
                with open(out_path, "a") as f:
                    f.write(json.dumps({"arch": arch, "shape": shape_name,
                                        "mesh": "2x16x16" if multi_pod else "16x16",
                                        "status": "error"}) + "\n")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (hillclimb experiments)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    if args.arch == "all":
        assert args.out, "--all requires --out"
        n_fail = run_all(args.out, args.multi_pod,
                         shapes=None if args.shape == "all" else [args.shape])
        sys.exit(1 if n_fail else 0)

    shapes = list(SHAPES_BY_NAME) if args.shape == "all" else [args.shape]
    for shape_name in shapes:
        try:
            rec = run_cell(args.arch, shape_name, args.multi_pod,
                           overrides=overrides or None)
        except Exception:
            traceback.print_exc()
            sys.exit(1)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
