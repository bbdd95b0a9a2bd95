"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def _qkv(i, B, Sq, Skv, H, KV, hd, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, i), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, hd), dtype)
    return q, k, v


FLASH_CASES = [
    # (B, Sq, Skv, H, KV, hd, dtype, causal, window, softcap, bq, bk)
    (2, 128, 128, 4, 2, 64, jnp.bfloat16, True, 0, 0.0, 64, 64),
    (2, 128, 128, 4, 2, 64, jnp.float32, True, 0, 0.0, 64, 64),
    (1, 256, 256, 8, 8, 64, jnp.bfloat16, True, 64, 0.0, 64, 64),
    (1, 256, 256, 8, 4, 64, jnp.bfloat16, True, 100, 0.0, 64, 32),
    (1, 128, 128, 4, 1, 128, jnp.bfloat16, True, 0, 50.0, 64, 64),
    (1, 128, 128, 4, 1, 128, jnp.float32, True, 0, 30.0, 32, 64),
    (2, 64, 192, 4, 2, 64, jnp.bfloat16, True, 0, 0.0, 64, 64),  # q_offset
    (1, 128, 128, 2, 2, 32, jnp.float32, False, 0, 0.0, 64, 64),  # bidir
    (1, 64, 64, 16, 2, 64, jnp.bfloat16, True, 0, 0.0, 64, 64),  # G=8
    (1, 256, 256, 4, 4, 256, jnp.bfloat16, True, 128, 30.0, 128, 128),  # gemma2-like
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=lambda c: f"{c[1]}x{c[2]}h{c[3]}kv{c[4]}d{c[5]}{np.dtype(c[6]).name}c{int(c[7])}w{c[8]}s{c[9]}")
def test_flash_attention_vs_ref(case):
    B, Sq, Skv, H, KV, hd, dtype, causal, window, softcap, bq, bk = case
    q, k, v = _qkv(hash(case[:6]) % 1000, B, Sq, Skv, H, KV, hd, dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=Skv - Sq,
                              block_q=bq, block_k=bk, interpret=True)
    want = ref.mha_reference(q, k, v, causal=causal, window=window, softcap=softcap)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.float32(got), np.float32(want), atol=tol, rtol=tol)


DECODE_CASES = [
    # (B, T, H, KV, hd, valid_len, softcap, bk)
    (2, 512, 8, 2, 64, 300, 0.0, 128),
    (1, 1024, 4, 4, 128, 1024, 0.0, 256),
    (3, 512, 16, 8, 64, 17, 0.0, 128),
    (1, 256, 4, 1, 64, 128, 50.0, 64),
    (2, 512, 2, 2, 256, 511, 0.0, 512),
    (1, 128, 32, 4, 64, 1, 0.0, 128),  # single valid slot
]


@pytest.mark.parametrize("case", DECODE_CASES, ids=lambda c: f"T{c[1]}h{c[2]}kv{c[3]}vl{c[5]}")
def test_decode_attention_vs_ref(case):
    B, T, H, KV, hd, vl, softcap, bk = case
    ks = jax.random.split(jax.random.fold_in(KEY, T + B + H), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, KV, hd), jnp.bfloat16)
    got = ops.decode_attention(q, k, v, vl, softcap=softcap, block_k=bk, interpret=True)
    want = ref.decode_attention_reference(q, k, v, vl, softcap=softcap)
    np.testing.assert_allclose(np.float32(got), np.float32(want), atol=3e-2, rtol=3e-2)


TICK_CONSTS = dict(t1=0.90, t2=0.97, t1_buf=0.02, t2_buf=0.02,
                   lp_t1=0.85, lp_t2=0.70, hp_t2=0.85, brake_freq=0.50,
                   p0_srv_w=180.0, k_lp_w=300.0, k_hp_w=150.0,
                   lp_share=0.6, gamma=1.6, n_servers=24.0,
                   power_scale=1.10)

TICK_CASES = [
    # (N, T, R, block_members, oob, brake, esc, power_scale, block_ticks)
    (8, 96, 2, 8, 20, 3, 25, 1.10, 32),   # three time blocks
    (5, 96, 2, 8, 20, 3, 25, 1.10, 40),   # N, T not block multiples (padding)
    (13, 64, 3, 4, 20, 3, 25, 1.18, 64),  # hot: brakes fire
    (3, 48, 1, 8, 5, 2, 4, 1.05, 7),      # short ring, fast escalation
    (16, 32, 2, 16, 20, 3, 25, 0.95, 256),  # cool: mostly uncapped
]


@pytest.mark.parametrize("case", TICK_CASES,
                         ids=lambda c: f"n{c[0]}t{c[1]}r{c[2]}b{c[3]}ps{c[7]}")
def test_polca_tick_vs_ref(case):
    """Pallas tick kernel vs the shared-step lax.scan reference: power plane
    to 1e-6 relative, brake/frequency planes bit-identical (float64)."""
    from repro.kernels.tick import TickConsts

    N, T, R, bm, oob, brake, esc, ps, tb = case
    ring_depth = max(oob, brake) + 1
    consts = TickConsts(**{**TICK_CONSTS, "power_scale": ps})
    with jax.enable_x64(True):
        rng = np.random.default_rng(N * 1000 + T)
        occ = jnp.asarray(rng.uniform(0.3, 1.0, (N, T, R)))
        bscale = jnp.asarray(rng.uniform(0.9, 1.0, (T, R)))
        row_budget = jnp.asarray(
            consts.n_servers * (consts.p0_srv_w + 0.8 * consts.k_lp_w)
            * np.ones(R))
        got = ops.polca_tick(occ, bscale, row_budget, consts=consts,
                             oob_ticks=oob, brake_ticks=brake,
                             ring_depth=ring_depth, esc=esc,
                             block_members=bm, block_ticks=tb,
                             interpret=True)
        want = ref.polca_tick_reference(occ, bscale, row_budget, consts,
                                        oob_ticks=oob, brake_ticks=brake,
                                        ring_depth=ring_depth, esc=esc)
    np.testing.assert_array_equal(np.asarray(got["fire"]),
                                  np.asarray(want["fire"]))
    np.testing.assert_array_equal(np.asarray(got["n_brakes"]),
                                  np.asarray(want["n_brakes"]))
    for k in ("f_lp", "f_hp"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)
    np.testing.assert_allclose(np.asarray(got["row_w"]),
                               np.asarray(want["row_w"]),
                               rtol=1e-6, atol=0.0)


def test_polca_tick_brakes_actually_fire():
    """The hot case must exercise the brake path (otherwise the parity above
    proves nothing about rings/latches)."""
    from repro.kernels.tick import TickConsts

    consts = TickConsts(**{**TICK_CONSTS, "power_scale": 1.30})
    with jax.enable_x64(True):
        occ = jnp.ones((4, 64, 2)) * 0.98
        out = ops.polca_tick(occ, jnp.ones((64, 2)),
                             jnp.full(2, consts.n_servers * 250.0),
                             consts=consts, oob_ticks=20, brake_ticks=3,
                             ring_depth=21, esc=25, interpret=True)
    assert int(np.asarray(out["n_brakes"]).sum()) > 0


def test_flash_matches_model_xla_path():
    """Kernel and the model's XLA attention path agree on identical inputs."""
    from repro.models.attention import _chunk_scores, _make_mask
    from repro.configs import smoke_config

    cfg = smoke_config("llama3.2-1b")
    B, S, H, KV, hd = 2, 128, 4, 2, 16
    q, k, v = _qkv(99, B, S, S, H, KV, hd, jnp.float32)
    mask = _make_mask(jnp.arange(S, dtype=jnp.int32), S, causal=True, window=0)
    xla = _chunk_scores(cfg, q, k, v, mask)
    kern = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True)
    np.testing.assert_allclose(np.float32(xla), np.float32(kern), atol=3e-5, rtol=3e-5)
