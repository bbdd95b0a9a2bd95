"""Attention: GQA, sliding-window, logit softcap, qk-norm, cross-attention.

XLA path (used for lowering/dry-run and CPU tests) with query-chunked scores so
long-context prefill never materializes the full [S, T] score matrix. The
Pallas flash kernels in ``repro.kernels`` implement the same contract for the
TPU target (``cfg.use_pallas``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import rmsnorm, rope, softcap
from repro.models.param import ParamSpec

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# query-chunk length for the chunked XLA attention path
Q_CHUNK = 1024


def attn_specs(cfg: ModelConfig, cross: bool = False) -> dict:
    D, KV, hd = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    H = cfg.padded_heads  # zero-padded wo rows: exact outputs, clean sharding
    wd = cfg.weight_dtype
    # fan-in is the contracted size (D in, H*hd out), not shape[-2]: a
    # per-head fan-in inflates q.k by ~sqrt(D/H) * sqrt(D/KV) and turns
    # random-weight attention into a hard argmax that amplifies rounding
    p = {
        "wq": ParamSpec((D, H, hd), ("embed", "heads", "head_dim"), dtype=wd, fan_in=D),
        "wk": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim"), dtype=wd, fan_in=D),
        "wv": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim"), dtype=wd, fan_in=D),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "embed"),
                        init="zeros" if H != cfg.num_heads else "normal", dtype=wd,
                        fan_in=cfg.num_heads * hd),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones", dtype=wd)
        p["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones", dtype=wd)
    return p


def _project_q(cfg, p, x, positions):
    dt = cfg.activation_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    if cfg.use_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
    return q


def _project_kv(cfg, p, x, positions):
    dt = cfg.activation_dtype
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "k_norm" in p:
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope and positions is not None:
        k = rope(k, positions, cfg.rope_theta)
    return k, v


def _chunk_scores(cfg, q_chunk, k, v, mask):
    """One query chunk of attention. q_chunk [B,Qc,H,hd]; k/v [B,T,KV,hd];
    mask [Qc,T] bool (True = attend) or None (full)."""
    B, Qc, H, hd = q_chunk.shape
    KV = k.shape[2]
    G = H // KV
    q = q_chunk.reshape(B, Qc, KV, G, hd)
    # NOTE (EXPERIMENTS §Perf G6): the dot outputs the activation dtype and is
    # upcast afterwards. TPU MXUs accumulate bf16 dots in fp32 regardless, and
    # a fp32-preferred dot here makes every backward activation gradient (and
    # its tensor-parallel all-reduce) fp32 — measured 2x collective bytes.
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32)
    s = s * (hd ** -0.5)
    if cfg.attn_logit_softcap:
        s = softcap(s, cfg.attn_logit_softcap)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(q_chunk.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v)
    return out.reshape(B, Qc, H, hd)


def _make_mask(q_pos, t_len, *, causal, window, t_offset=0, valid_len=None):
    """Boolean attend-mask [Qc, T]. q_pos: [Qc] absolute query positions."""
    t_pos = jnp.arange(t_len, dtype=jnp.int32) + t_offset
    m = jnp.ones((q_pos.shape[0], t_len), dtype=bool)
    if causal:
        m &= t_pos[None, :] <= q_pos[:, None]
    if window:
        m &= t_pos[None, :] > q_pos[:, None] - window
    if valid_len is not None:
        m &= t_pos[None, :] < valid_len
    return m


def self_attention(
    cfg: ModelConfig,
    p: dict,
    x,
    *,
    positions,
    causal: bool,
    window: int = 0,
    return_kv: bool = False,
):
    """Full-sequence self attention (train/prefill/encoder)."""
    B, S, D = x.shape
    q = _project_q(cfg, p, x, positions if cfg.use_rope else None)
    k, v = _project_kv(cfg, p, x, positions if cfg.use_rope else None)

    n_chunks = max(1, S // Q_CHUNK) if S % Q_CHUNK == 0 else 1
    if n_chunks > 1 and (causal or window):
        Qc = S // n_chunks
        qs = q.reshape(B, n_chunks, Qc, q.shape[2], q.shape[3]).transpose(1, 0, 2, 3, 4)
        pos_c = positions.reshape(n_chunks, Qc) if positions.ndim == 1 else None

        def body(carry, inp):
            qc, pc = inp
            mask = _make_mask(pc, S, causal=causal, window=window)
            return carry, _chunk_scores(cfg, qc, k, v, mask)

        _, outs = jax.lax.scan(body, None, (qs, pos_c))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, q.shape[2], q.shape[3])
    else:
        mask = None
        if causal or window:
            qpos = positions if positions.ndim == 1 else jnp.arange(S, dtype=jnp.int32)
            mask = _make_mask(qpos, S, causal=causal, window=window)
        out = _chunk_scores(cfg, q, k, v, mask)

    dt = cfg.activation_dtype
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    if return_kv:
        return y, (k, v)
    return y


def cross_attention(cfg: ModelConfig, p: dict, x, enc_kv):
    """Decoder cross-attention over encoder outputs (no mask, no rope)."""
    dt = cfg.activation_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k, v = enc_kv
    out = _chunk_scores(cfg, q, k, v, None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def project_cross_kv(cfg: ModelConfig, p: dict, enc_out):
    dt = cfg.activation_dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    return k, v


def decode_self_attention(
    cfg: ModelConfig,
    p: dict,
    x,
    cache_k,
    cache_v,
    pos,
    *,
    window: int = 0,
):
    """Single-token decode against a KV cache.

    x: [B, 1, D]; cache_k/v: [B, T, KV, hd]; pos: scalar int32 (tokens 0..pos-1
    are valid; the new token is written at index pos).
    Returns (y [B,1,D], cache_k', cache_v').
    """
    B, _, D = x.shape
    T = cache_k.shape[1]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q = _project_q(cfg, p, x, positions if cfg.use_rope else None)
    k_new, v_new = _project_kv(cfg, p, x, positions if cfg.use_rope else None)

    cache_k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype), (0, pos, 0, 0))

    qpos = jnp.full((1,), pos, dtype=jnp.int32)
    mask = _make_mask(qpos, T, causal=True, window=window, valid_len=pos + 1)
    out = _chunk_scores(cfg, q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cfg.activation_dtype))
    return y, cache_k, cache_v


def decode_ring_attention(cfg: ModelConfig, p: dict, x, cache_k, cache_v, pos, window: int):
    """Decode against a ring-buffer KV cache of size ``window``.

    Slot i holds the KV of absolute position ``pos - ((pos - i) mod W)`` once
    the new token has been written at slot ``pos mod W``. RoPE is applied at
    absolute positions before caching, so ring rotation is transparent.
    """
    B = x.shape[0]
    W = window
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q = _project_q(cfg, p, x, positions if cfg.use_rope else None)
    k_new, v_new = _project_kv(cfg, p, x, positions if cfg.use_rope else None)

    slot = jnp.mod(pos, W)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype), (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype), (0, slot, 0, 0))

    i = jnp.arange(W, dtype=jnp.int32)
    t_pos = pos - jnp.mod(pos - i, W)  # absolute position stored in slot i
    mask = ((t_pos >= 0) & (t_pos <= pos))[None, :]  # [1, W]
    out = _chunk_scores(cfg, q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cfg.activation_dtype))
    return y, cache_k, cache_v
