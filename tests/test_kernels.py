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
