"""Training launcher: end-to-end driver over the local device set.

Example (the (b) deliverable's end-to-end run — ~100M-class model, a few
hundred steps on CPU/small TPU):

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \\
      --steps 300 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a pod this same driver runs under the production mesh; here the mesh spans
whatever jax.devices() offers.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline, device_put_batch
from repro.launch.inputs import make_rules
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_train_step
from repro.models import model as model_mod
from repro.models.config import ShapeConfig
from repro.models.param import init_params
from repro.obs.log import get_logger
from repro.optim import make_optimizer
from repro.runtime.fault_tolerance import StragglerMonitor, TrainSupervisor

log = get_logger(__name__)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-par", type=int, default=0, help="data axis size (0=n_devices)")
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    n_dev = len(jax.devices())
    dp = args.data_par or max(1, n_dev // args.model_par)
    mesh = make_local_mesh(dp, args.model_par)
    shape = ShapeConfig("cli_train", args.seq, args.batch, "train")
    rules = make_rules(cfg, shape, mesh)

    pspecs = model_mod.model_specs(cfg, mesh.shape["model"])
    opt = make_optimizer(cfg.optimizer)
    with jax.set_mesh(mesh):
        params = init_params(pspecs, jax.random.key(0))
        opt_state = init_params(opt.init_specs(pspecs), jax.random.key(1))
    state = {"params": params, "opt": opt_state}
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    log.info(f"arch={cfg.name} params={n_params/1e6:.1f}M mesh={dict(mesh.shape)}")

    pipeline = SyntheticTokenPipeline(cfg, DataConfig(args.batch, args.seq))
    step_fn = jax.jit(build_train_step(cfg, mesh, rules, opt))

    def wrapped_step(state, batch):
        with jax.set_mesh(mesh):
            new_state, metrics = step_fn(state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
        return new_state, metrics

    sup = TrainSupervisor(wrapped_step, pipeline, args.ckpt_dir,
                          ckpt_interval=args.ckpt_interval,
                          straggler=StragglerMonitor())
    t0 = time.time()
    state, last = sup.run(state, args.steps,
                          place_batch=lambda b: device_put_batch(b, mesh, rules))
    dt = time.time() - t0
    losses = [h["loss"] for h in sup.history]
    log.info(f"done: {last} steps in {dt:.1f}s "
             f"({dt/max(1,len(sup.history)):.3f}s/step) "
             f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
             f"restarts={sup.n_restarts} stragglers={len(sup.straggler.flagged_steps)}")
    assert losses[-1] < losses[0], "training should reduce loss"
    with open("/tmp/train_history.json", "w") as f:
        json.dump(sup.history, f)


if __name__ == "__main__":
    main()
