"""End-to-end training driver: a ~100M-parameter llama-family model for a few
hundred steps with checkpoint/restart and straggler monitoring.

  PYTHONPATH=src python examples/train_100m.py                # ~100M, 300 steps
  PYTHONPATH=src python examples/train_100m.py --small        # ~20M, 200 steps (fast CPU)

Resume after interruption is automatic: rerun the same command and the
supervisor restores the newest checkpoint.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.checkpoint import checkpointer
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline, device_put_batch
from repro.launch.inputs import make_rules
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_train_step
from repro.models import model as model_mod
from repro.models.config import ShapeConfig
from repro.models.param import init_params
from repro.optim import make_optimizer
from repro.runtime.fault_tolerance import TrainSupervisor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()

    base = get_config("llama3.2-1b")
    if args.small:
        cfg = base.replace(name="llama-20m", num_layers=4, d_model=256, num_heads=8,
                           num_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=32000)
        batch, seq, steps = 4, 128, args.steps or 200
    else:
        # ~100M-class: 8L x d=512 + 50k vocab (tied) ~ 51M blocks + 26M embed
        cfg = base.replace(name="llama-100m", num_layers=8, d_model=768, num_heads=12,
                           num_kv_heads=4, head_dim=64, d_ff=3072, vocab_size=50304)
        batch, seq, steps = 8, 256, args.steps or 300

    mesh = make_local_mesh(len(jax.devices()), 1)
    shape = ShapeConfig("e2e", seq, batch, "train")
    rules = make_rules(cfg, shape, mesh)
    opt = make_optimizer(cfg.optimizer, lr=1e-3)
    pspecs = model_mod.model_specs(cfg, mesh.shape["model"])
    with jax.set_mesh(mesh):
        state = {"params": init_params(pspecs, jax.random.key(0)),
                 "opt": init_params(opt.init_specs(pspecs), jax.random.key(1))}
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state["params"]))
    print(f"model={cfg.name} params={n_params/1e6:.1f}M steps={steps} "
          f"batch={batch} seq={seq}")

    start, state = checkpointer.restore_latest(args.ckpt_dir, state)
    start = start or 0
    if start:
        print(f"resuming from checkpoint at step {start}")

    pipe = SyntheticTokenPipeline(cfg, DataConfig(batch, seq))
    jit_step = jax.jit(build_train_step(cfg, mesh, rules, opt))

    def step_fn(st, b):
        with jax.set_mesh(mesh):
            st, m = jit_step(st, b)
        return st, {k: float(v) for k, v in m.items()}

    sup = TrainSupervisor(step_fn, pipe, args.ckpt_dir, ckpt_interval=50)
    state, last = sup.run(state, steps, start_step=start,
                          place_batch=lambda b: device_put_batch(b, mesh, rules))
    losses = [h["loss"] for h in sup.history]
    print(f"finished at step {last}: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(stragglers flagged: {len(sup.straggler.flagged_steps)})")
    assert losses[-1] < losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
