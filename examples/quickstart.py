"""Quickstart: the three layers of the framework in one minute on CPU.

  1. train a reduced llama config for a few steps (data -> step -> checkpoint);
  2. serve it (prefill + decode engine);
  3. run the POLCA power plane: characterize the model's phases, then
     oversubscribe a simulated row by +30% under Algorithm 1.

  PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.power_model import A100, ServerPower
from repro.core.workload import request_timing
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline, device_put_batch
from repro.experiments import get_scenario, run_experiment
from repro.launch.inputs import make_rules
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import ServeEngine
from repro.launch.steps import build_train_step
from repro.models import model as model_mod
from repro.models.config import ShapeConfig
from repro.models.param import init_params
from repro.optim import make_optimizer

# ---------------------------------------------------------------- 1. train
cfg = smoke_config("llama3.2-1b")
mesh = make_local_mesh(1, 1)
shape = ShapeConfig("quickstart", 64, 4, "train")
rules = make_rules(cfg, shape, mesh)
opt = make_optimizer(cfg.optimizer)
pspecs = model_mod.model_specs(cfg, 1)
with jax.set_mesh(mesh):
    state = {"params": init_params(pspecs, jax.random.key(0)),
             "opt": init_params(opt.init_specs(pspecs), jax.random.key(1))}
pipe = SyntheticTokenPipeline(cfg, DataConfig(4, 64))
step = jax.jit(build_train_step(cfg, mesh, rules, opt))
losses = []
with jax.set_mesh(mesh):
    for i in range(10):
        state, metrics = step(state, device_put_batch(pipe.batch_at(i), mesh, rules))
        losses.append(float(metrics["loss"]))
print(f"[train] loss {losses[0]:.3f} -> {losses[-1]:.3f} over {len(losses)} steps")
assert losses[-1] < losses[0]

# ---------------------------------------------------------------- 2. serve
eng = ServeEngine(cfg, mesh, max_len=48, batch=2)
prompts = np.arange(2 * 32, dtype=np.int32).reshape(2, 32) % cfg.vocab_size
out = eng.generate(prompts, 8)
print(f"[serve] generated tokens: {out[0].tolist()}")

# ---------------------------------------------------------------- 3. POLCA
server = ServerPower(A100)
t = request_timing(get_config("llama3.2-1b"), 2048, 8, server)
print(f"[power] llama3.2-1b x8batch: prompt {t.prefill_point.power_at(server,1.0):.0f}W "
      f"(compute-bound) | token {t.token_point.power_at(server,1.0):.0f}W (memory-bound)")

o = run_experiment(get_scenario("quickstart-plus30"))
s = o.stats.summary()
print(f"[polca] +30% servers: meets_SLO={o.meets} powerbrakes={o.result.n_brakes} "
      f"HP_p99={s['hp_p99']:.2%} LP_p99={s['lp_p99']:.2%} "
      f"peak_power={o.result.peak_power_frac:.1%} of provisioned")
print("OK")
