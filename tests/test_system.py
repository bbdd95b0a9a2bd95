"""End-to-end behaviour tests: every assigned arch (reduced config) runs a
train step and, where applicable, a prefill->decode cycle with exact
consistency between the two paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro.configs import ALL, ASSIGNED, smoke_config
from repro.launch.inputs import make_rules, split_seq
from repro.launch.steps import build_decode_step, build_prefill_step, build_train_step
from repro.models import model as model_mod
from repro.models.config import ShapeConfig
from repro.models.param import init_params
from repro.optim import make_optimizer

B, S = 2, 32


def _setup(name, mesh, kind="train"):
    cfg = smoke_config(name)
    shape = ShapeConfig("t", S, B, kind)
    rules = make_rules(cfg, shape, mesh)
    params = init_params(model_mod.model_specs(cfg, mesh.shape["model"]),
                         jax.random.key(0))
    return cfg, shape, rules, params


@pytest.mark.parametrize("name", sorted(ALL))
def test_train_step_all_archs(name, mesh1):
    cfg, shape, rules, params = _setup(name, mesh1)
    opt = make_optimizer(cfg.optimizer)
    opt_state = init_params(opt.init_specs(model_mod.model_specs(cfg, 1)),
                            jax.random.key(1))
    state = {"params": params, "opt": opt_state}
    batch = make_batch(cfg, B, S)
    step = jax.jit(build_train_step(cfg, mesh1, rules, opt))
    with jax.set_mesh(mesh1):
        state2, metrics = step(state, batch)
        state3, metrics3 = step(state2, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually moved and second step stays finite
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(state2["params"])))
    assert moved
    assert np.isfinite(float(metrics3["loss"]))


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_prefill_decode_consistency(name, mesh1):
    cfg = smoke_config(name)
    if cfg.is_encoder_only:
        pytest.skip("encoder-only: no decode step")
    shape = ShapeConfig("t", S, B, "prefill")
    rules = make_rules(cfg, shape, mesh1)
    params = init_params(model_mod.model_specs(cfg, 1), jax.random.key(0))
    batch = make_batch(cfg, B, S, seed=3)
    _, dec_S = split_seq(cfg, S)
    n_txt = batch["tokens"].shape[1]

    pf = jax.jit(build_prefill_step(cfg, shape, mesh1, rules))
    dc = jax.jit(build_decode_step(cfg, mesh1, rules))
    b_part = dict(batch)
    b_part["tokens"] = batch["tokens"][:, :-1]
    img = cfg.num_image_embeds if cfg.frontend == "vision_stub" else 0
    pos = jnp.asarray(n_txt - 1 + img, jnp.int32)
    with jax.set_mesh(mesh1):
        logits_full, _ = pf(params, batch)
        _, cache = pf(params, b_part)
        logits_dec, new_cache = dc(params, batch["tokens"][:, -1:], pos, cache)
    a = np.asarray(logits_full[:, -1, :], np.float32)
    b = np.asarray(logits_dec[:, -1, :], np.float32)
    rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-6)
    assert rel < 0.06, f"{name}: decode/prefill mismatch rel={rel}"
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_output_shapes_and_no_nans(name, mesh1):
    cfg, shape, rules, params = _setup(name, mesh1, "prefill")
    if cfg.is_encoder_only:
        pytest.skip("encoder-only")
    batch = make_batch(cfg, B, S)
    pf = jax.jit(build_prefill_step(cfg, shape, mesh1, rules))
    with jax.set_mesh(mesh1):
        logits, cache = pf(params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    for leaf in jax.tree.leaves(cache):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


def test_greedy_generation_deterministic(mesh1):
    """Serving engine produces identical greedy tokens across runs."""
    from repro.launch.serve import ServeEngine

    cfg = smoke_config("llama3.2-1b")
    eng = ServeEngine(cfg, mesh1, max_len=24, batch=2)
    toks = np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % cfg.vocab_size
    out1 = eng.generate(toks, 8)
    out2 = eng.generate(toks, 8)
    assert (out1 == out2).all()
    assert out1.shape == (2, 8)


def test_attention_init_uses_contracted_fan_in():
    """q/k/v/o projections are [D, H, hd] / [H, hd, D]; their init std must
    follow the contracted size (D in, H*hd out) like the MLP's, or random
    weights make attention a hard argmax that amplifies every rounding."""
    cfg = smoke_config("llama3.2-1b")
    p = init_params(model_mod.model_specs(cfg, 1), jax.random.key(0))
    layer = p["decoder"]["b0"]
    D, H, hd = cfg.d_model, cfg.num_heads, cfg.head_dim

    def unit_std(w, fan_in):
        return float(np.std(np.asarray(w))) * np.sqrt(fan_in)

    ref = unit_std(layer["mlp"]["w_gate"], D)
    for name in ("wq", "wk", "wv"):
        assert unit_std(layer["attn"][name], D) == pytest.approx(ref, rel=0.1)
    assert unit_std(layer["attn"]["wo"], H * hd) == pytest.approx(ref, rel=0.1)
