"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` auto-selects: compiled on TPU, Pallas interpreter on CPU
(correctness validation path used by the test suite).
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "q_offset",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0, q_offset=0,
                    block_q=_fa.DEFAULT_BLOCK_Q, block_k=_fa.DEFAULT_BLOCK_K,
                    interpret=None):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("softcap", "block_k", "interpret"))
def decode_attention(q, k, v, valid_len, *, softcap=0.0,
                     block_k=_dec.DEFAULT_BLOCK_K, interpret=None):
    return _dec.decode_attention(
        q, k, v, valid_len, softcap=softcap, block_k=block_k,
        interpret=_auto_interpret(interpret))

