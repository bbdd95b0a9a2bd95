"""MoE expert-parallel correctness: shard_map + ragged_dot dispatch vs a dense
reference (every expert applied to every token, combined by router weight)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import moe
from repro.models.param import init_params
from repro.launch.mesh import make_local_mesh

KEY = jax.random.key(0)


def dense_moe_reference(cfg, p, x):
    """O(T*E) reference: compute all experts densely, combine by top-k weight.
    Reconstructs the logical [E, D, F] weights from the slot layout."""
    E, k, D, F = cfg.moe_num_experts, cfg.moe_top_k, cfg.d_model, cfg.moe_d_ff
    slots = p["wg"].shape[0]
    f_shards = slots // E
    Fc = F // f_shards

    def unslot(w, transpose=False):
        # slot s = (expert s//f_shards, chunk s%f_shards)
        if not transpose:  # [slots, D, Fc] -> [E, D, F]
            return np.concatenate(
                [np.concatenate([np.asarray(w[e * f_shards + c]) for c in range(f_shards)],
                                axis=-1)[None] for e in range(E)], axis=0)
        # wd_: [slots, Fc, D] -> [E, F, D]
        return np.concatenate(
            [np.concatenate([np.asarray(w[e * f_shards + c]) for c in range(f_shards)],
                            axis=0)[None] for e in range(E)], axis=0)

    wg, wu = unslot(p["wg"]), unslot(p["wu"])
    wd = unslot(p["wd_"], transpose=True)
    T = x.shape[0] * x.shape[1]
    xf = np.asarray(x, np.float32).reshape(T, D)
    logits = xf @ np.asarray(p["router"], np.float32)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    topi = np.argsort(-probs, axis=-1)[:, :k]
    topw = np.take_along_axis(probs, topi, axis=-1)
    topw /= topw.sum(-1, keepdims=True)
    out = np.zeros((T, D), np.float32)
    for e in range(E):
        h = xf @ wg[e]
        u = xf @ wu[e]
        y = (h * (1 / (1 + np.exp(-h)))) * u @ wd[e]
        w_e = np.where(topi == e, topw, 0.0).sum(-1)
        out += w_e[:, None] * y
    return out.reshape(x.shape)


@pytest.mark.parametrize("n_model,E", [(1, 4), (2, 4), (2, 8), (2, 2)])
def test_moe_matches_dense_reference(n_model, E):
    n_dev = len(jax.devices())
    if n_model > n_dev:
        pytest.skip(f"needs {n_model} devices")
    cfg = smoke_config("mixtral-8x7b").replace(
        moe_num_experts=E, moe_top_k=2, moe_capacity_factor=8.0,  # no drops
        dtype="float32", param_dtype="float32")
    mesh = make_local_mesh(1, n_model)
    p = init_params(moe.moe_specs(cfg, n_model), KEY)
    x = jax.random.normal(jax.random.fold_in(KEY, E), (2, 8, cfg.d_model)) * 0.5
    with jax.set_mesh(mesh):
        got = jax.jit(lambda pp, xx: moe.moe_apply(
            cfg, pp, xx, mesh=mesh, batch_spec=None, gather_axes=()))(p, x)
    want = dense_moe_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-3)


def test_moe_capacity_drops_bounded():
    """With cf=1.0 some tokens may drop, but output must stay finite and the
    kept fraction must be >= 1/k (the top-1 expert at least mostly kept)."""
    cfg = smoke_config("mixtral-8x7b").replace(
        moe_num_experts=4, moe_top_k=2, moe_capacity_factor=1.0,
        dtype="float32", param_dtype="float32")
    mesh = make_local_mesh(1, 1)
    p = init_params(moe.moe_specs(cfg, 1), KEY)
    x = jax.random.normal(KEY, (4, 16, cfg.d_model))
    with jax.set_mesh(mesh):
        out = moe.moe_apply(cfg, p, x, mesh=mesh, batch_spec=None, gather_axes=())
    assert np.isfinite(np.asarray(out)).all()


def test_moe_layout():
    assert moe.moe_layout(smoke_config("mixtral-8x7b").replace(moe_num_experts=8), 16) \
        == (8, 2, 1, 16)
    assert moe.moe_layout(smoke_config("kimi-k2-1t-a32b").replace(moe_num_experts=384), 16) \
        == (16, 1, 24, 384)
    assert moe.moe_layout(smoke_config("mixtral-8x7b").replace(moe_num_experts=16), 16) \
        == (16, 1, 1, 16)


def test_aux_loss_balanced_router_is_minimal():
    """A uniform router gives aux loss ~= 1 (the Switch lower bound)."""
    cfg = smoke_config("mixtral-8x7b").replace(
        moe_num_experts=4, moe_top_k=2, dtype="float32", param_dtype="float32")
    p = init_params(moe.moe_specs(cfg, 1), KEY)
    p = dict(p)
    p["router"] = jnp.zeros_like(p["router"])  # perfectly uniform
    x = jax.random.normal(KEY, (2, 64, cfg.d_model))
    loss = float(moe.moe_aux_loss(cfg, p, x))
    assert abs(loss - 1.0) < 0.05


@pytest.mark.parametrize("n_dev_needed,batch_sharded", [(1, False), (2, True), (2, False)])
def test_token_routed_matches_dense_reference(n_dev_needed, batch_sharded):
    """Serve-time token-routed EP (experts resident mesh-wide) == dense ref."""
    if n_dev_needed > len(jax.devices()):
        pytest.skip("needs more devices")
    cfg = smoke_config("mixtral-8x7b").replace(
        moe_num_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
        dtype="float32", param_dtype="float32")
    # EP domain = data x model
    mesh = make_local_mesh(n_dev_needed, 1) if batch_sharded else \
        make_local_mesh(1, n_dev_needed)
    ep = n_dev_needed
    p = init_params(moe.moe_specs(cfg, ep), KEY)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 8, cfg.d_model)) * 0.5
    bspec = ("data",) if batch_sharded else None
    with jax.set_mesh(mesh):
        got = jax.jit(lambda pp, xx: moe.moe_apply_token_routed(
            cfg, pp, xx, mesh=mesh, batch_spec=bspec))(p, x)
    want = dense_moe_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-3)
