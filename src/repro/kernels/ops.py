"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` auto-selects: compiled on TPU, Pallas interpreter on CPU
(correctness validation path used by the test suite).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import tick as _tick


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


@partial(jax.jit, static_argnames=("consts", "oob_ticks", "brake_ticks",
                                   "ring_depth", "esc", "block_members",
                                   "block_ticks", "interpret"))
def polca_tick(occ, bscale, row_budget, *, consts, oob_ticks, brake_ticks,
               ring_depth, esc, block_members=_tick.DEFAULT_BLOCK_MEMBERS,
               block_ticks=_tick.DEFAULT_BLOCK_TICKS, interpret=None):
    """Non-predictive POLCA tick loop (power fold + latch/ring update) as a
    Pallas kernel. ``consts`` is a hashable :class:`~repro.kernels.tick.
    TickConsts` — per-scenario scalars are compile-time here (the scan
    engine in ``provisioning.batched`` is the probe-sweep path; this kernel
    recompiles per scenario by design)."""
    return _tick.polca_tick_loop(
        occ, bscale, row_budget, consts, oob_ticks=oob_ticks,
        brake_ticks=brake_ticks, ring_depth=ring_depth, esc=esc,
        block_members=block_members, block_ticks=block_ticks,
        interpret=_auto_interpret(interpret))
