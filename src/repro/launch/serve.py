"""Serving launcher: batched prefill+decode engine with POLCA in the loop.

The engine exposes exactly the two phases the paper characterizes (prompt =
compute-spike, token = flat memory-bound draw) and reports the per-phase
roofline/power operating points from the same analytic model POLCA's
simulator uses — so `--report-power` prints the Figure-4-style phase profile
of the model being served.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --requests 8 --prompt 64 --out-tokens 32 --report-power
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.power_model import A100, ServerPower
from repro.core.workload import request_timing
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.inputs import make_rules
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_decode_step, build_prefill_step
from repro.models import model as model_mod
from repro.models.config import ShapeConfig
from repro.models.param import init_params
from repro.obs.log import get_logger

log = get_logger(__name__)


class ServeEngine:
    def __init__(self, cfg, mesh, max_len: int, batch: int):
        self.cfg, self.mesh = cfg, mesh
        shape = ShapeConfig("serve", max_len, batch, "prefill")
        self.rules = make_rules(cfg, shape, mesh)
        with jax.set_mesh(mesh):
            self.params = init_params(model_mod.model_specs(cfg, mesh.shape["model"]),
                                      jax.random.key(0))
        self.prefill = jax.jit(build_prefill_step(cfg, shape, mesh, self.rules))
        self.decode = jax.jit(build_decode_step(cfg, mesh, self.rules))

    def generate(self, tokens: np.ndarray, n_out: int, extra_inputs=None):
        """Greedy decode. tokens: [B, S]. Returns [B, n_out]."""
        batch = {"tokens": jnp.asarray(tokens)}
        if extra_inputs:
            batch.update(extra_inputs)
        outs = []
        with jax.set_mesh(self.mesh):
            logits, cache = self.prefill(self.params, batch)
            pos = tokens.shape[1]
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            for i in range(n_out):
                outs.append(np.asarray(tok)[:, 0])
                logits, cache = self.decode(self.params, tok,
                                            jnp.asarray(pos + i, jnp.int32), cache)
                tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        return np.stack(outs, axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--out-tokens", type=int, default=32)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--report-power", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(max(1, len(jax.devices()) // args.model_par), args.model_par)
    max_len = args.prompt + args.out_tokens
    eng = ServeEngine(cfg, mesh, max_len, args.requests)

    rng = np.random.default_rng(0)
    extra = {}
    if cfg.is_encoder_decoder:
        from repro.launch.inputs import split_seq
        enc_S, _ = split_seq(cfg, max_len)
        extra["enc_embeds"] = jnp.asarray(
            rng.standard_normal((args.requests, enc_S, cfg.d_model)), jnp.bfloat16)
    elif cfg.frontend == "vision_stub":
        extra["image_embeds"] = jnp.asarray(
            rng.standard_normal((args.requests, cfg.num_image_embeds, cfg.d_model)),
            jnp.bfloat16)
    tokens = rng.integers(0, cfg.vocab_size, (args.requests, args.prompt)).astype(np.int32)

    t0 = time.time()
    out = eng.generate(tokens, args.out_tokens, extra)
    dt = time.time() - t0
    log.info(f"served batch={args.requests} prompt={args.prompt} out={args.out_tokens} "
             f"in {dt:.2f}s ({dt/args.out_tokens*1e3:.1f} ms/token step)")
    log.info("sample output tokens: %s", out[0, :16])

    if args.report_power:
        # Figure-4-style phase profile from the shared workload/power model
        server = ServerPower(A100)
        full = get_config(args.arch)
        t = request_timing(full, args.prompt, args.requests, server)
        log.info(f"[power] {full.name}: prompt phase {t.t_prefill:.3f}s @ "
                 f"{t.prefill_point.power_at(server, 1.0):.0f}W (compute-bound "
                 f"u_c={t.prefill_point.u_compute:.2f}) | token phase "
                 f"{t.t_token*1e3:.1f}ms/tok @ {t.token_point.power_at(server, 1.0):.0f}W "
                 f"(memory-bound u_m={t.token_point.u_memory:.2f})")


if __name__ == "__main__":
    main()
