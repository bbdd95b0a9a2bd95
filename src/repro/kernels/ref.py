"""Pure-jnp oracles for every Pallas kernel (and the SSD sequential oracle).

These are deliberately naive: full score matrices, exact softmax, sequential
recurrences. Kernel tests sweep shapes/dtypes and assert_allclose against
these references.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def mha_reference(q, k, v, *, causal=True, window=0, softcap=0.0, valid_len=None):
    """q: [B,Sq,H,D]; k/v: [B,Skv,KV,D]; GQA by head grouping.

    ``q_offset`` is implied: query i sits at absolute position
    Skv - Sq + i (decode-style alignment) when Sq != Skv, else i.
    Returns [B,Sq,H,D] in q.dtype.
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qf, kf) * (D ** -0.5)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = jnp.arange(Sq) + (Skv - Sq)
    t_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= t_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= t_pos[None, :] > q_pos[:, None] - window
    if valid_len is not None:
        mask &= (t_pos < valid_len)[None, :]
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, vf)
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention_reference(q, k, v, valid_len, *, softcap=0.0):
    """Single-token decode. q: [B,H,D]; k/v: [B,T,KV,D]; valid_len scalar."""
    o = mha_reference(q[:, None], k, v, causal=False, softcap=softcap,
                      valid_len=valid_len)
    return o[:, 0]


def ssd_reference(x, dt, A, B, C, D_skip, init_state=None):
    """Sequential SSD recurrence (the oracle for the chunked form).

    x: [Bt,S,H,P]; dt: [Bt,S,H] (post-softplus); A: [H] (negative);
    B/C: [Bt,S,G,N]; D_skip: [H]. Returns (y [Bt,S,H,P], final_state).
    """
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B.astype(jnp.float32), rep, axis=2)  # [Bt,S,H,N]
    Ch = jnp.repeat(C.astype(jnp.float32), rep, axis=2)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, Bt_, Ct_ = inp  # [Bt,H,P], [Bt,H], [Bt,H,N], [Bt,H,N]
        decay = jnp.exp(dtt * A[None, :])  # [Bt,H]
        state = state * decay[:, :, None, None] + jnp.einsum(
            "bhn,bh,bhp->bhnp", Bt_, dtt, xt)
        y = jnp.einsum("bhn,bhnp->bhp", Ct_, state)
        return state, y

    init = jnp.zeros((Bt, H, N, P), jnp.float32) if init_state is None else init_state
    final, ys = jax.lax.scan(
        step, init,
        (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
         jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1) + D_skip.astype(jnp.float32)[None, None, :, None] * xf
    return y.astype(x.dtype), final


def rmsnorm_reference(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def polca_tick_reference(occ, bscale, row_budget, consts, *, oob_ticks,
                         brake_ticks, ring_depth, esc):
    """Plain ``lax.scan`` form of the POLCA tick loop — the shell oracle for
    :func:`repro.kernels.tick.polca_tick_loop`.

    Shares ``tick._tick_body`` with the kernel on purpose: this reference
    isolates the Pallas plumbing (member blocking, ring/scratch indexing,
    per-tick loads/stores, padding) rather than re-deriving the state
    machine. Semantic ground truth for the step itself is the numpy tick
    oracle driving the *real* policy objects (``tests/test_batched_parity``
    runs ``engine="pallas"`` through that differential harness).

    occ: [N,T,R] effective occupancy; bscale: [T,R]; row_budget: [R].
    """
    from repro.kernels import tick as _tick

    N, T, R = occ.shape
    init = _tick._tick_init(N, R, ring_depth, occ.dtype)

    def step(carry, x):
        k, occ_k, bs_k = x
        carry, rw, fire = _tick._tick_body(
            k, carry, occ_k, bs_k, row_budget, consts,
            oob_ticks=oob_ticks, brake_ticks=brake_ticks,
            ring_depth=ring_depth, esc=esc)
        return carry, (rw, fire, carry[0], carry[1])

    xs = (jnp.arange(T, dtype=jnp.int32), jnp.moveaxis(occ, 1, 0), bscale)
    _, (rw, fire, f_lp, f_hp) = jax.lax.scan(step, init, xs)
    return dict(row_w=jnp.moveaxis(rw, 0, 1), fire=jnp.moveaxis(fire, 0, 1),
                f_lp=jnp.moveaxis(f_lp, 0, 1),
                f_hp=jnp.moveaxis(f_hp, 0, 1),
                n_brakes=fire.sum(axis=0, dtype=jnp.int32))
