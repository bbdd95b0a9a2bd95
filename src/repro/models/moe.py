"""Mixture-of-Experts with expert parallelism via shard_map + lax.ragged_dot.

Design (DESIGN.md §5): experts are sharded over the ``model`` mesh axis. When
E >= n_model we shard whole experts (kimi-k2: 384/16 = 24 per shard); when
E < n_model each expert's FFN dim is additionally split into ``f_shards``
chunks so that every device owns exactly one (expert, ffn-chunk) "slot"
(mixtral: 8 experts x 2 chunks over 16 devices). Dispatch is sort-based and
capacity-bounded: no [T, E, C] one-hot dispatch tensors are ever materialized;
each shard gathers only the rows routed to its local experts and runs a
grouped matmul (``lax.ragged_dot``). The combine is a scatter-add followed by
a psum over ``model`` — which coincides with the tensor-parallel reduction the
surrounding dense layers already pay, so EP adds no extra collective steps.

Expert weights may additionally be ZeRO-sharded over the FSDP axes
(``gather_axes``); they are all-gathered just-in-time inside the shard_map
(re-gathered in backward under remat), which is what makes the 1T-param
kimi-k2 optimizer state fit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.param import ParamSpec


def moe_layout(cfg: ModelConfig, n_shards: int) -> Tuple[int, int, int, int]:
    """(e_shards, f_shards, n_local_experts, slots) for an EP domain of
    ``n_shards`` devices. Works for any (E, n): e_shards = gcd(E, n) expert
    groups of n_local_e experts; each group's FFN dim is split into f_shards
    chunks. Device i owns (group i // f_shards, chunk i % f_shards) — i.e.
    slot s maps to expert ((s // n_local_e) // f_shards) * n_local_e
    + (s % n_local_e), chunk (s // n_local_e) % f_shards. All slots on one
    device are DISTINCT experts (same chunk), so ragged_dot groups never
    overlap."""
    E = cfg.moe_num_experts
    e_shards = math.gcd(E, n_shards)
    f_shards = n_shards // e_shards
    n_local_e = E // e_shards
    slots = n_shards * n_local_e
    return e_shards, f_shards, n_local_e, slots


def moe_specs(cfg: ModelConfig, n_model: int) -> dict:
    D, E, F = cfg.d_model, cfg.moe_num_experts, cfg.moe_d_ff
    _, f_shards, _, slots = moe_layout(cfg, n_model)
    Fc = F // f_shards
    wd = cfg.weight_dtype
    assert F % f_shards == 0
    logical = ("expert_slot", "expert_embed", "expert_mlp")
    p = {
        "router": ParamSpec((D, E), (None, None), dtype=jnp.float32),
        "wg": ParamSpec((slots, D, Fc), logical, dtype=wd),
        "wu": ParamSpec((slots, D, Fc), logical, dtype=wd),
        "wd_": ParamSpec((slots, Fc, D), ("expert_slot", "expert_mlp", "expert_embed"), dtype=wd),
    }
    return p


def _capacity(n_rows_local: int, e_shards: int, cf: float) -> int:
    c = int(math.ceil(n_rows_local * cf / e_shards))
    return max(8, min(n_rows_local, (c + 7) // 8 * 8))


def _grouped_ffn(cfg, xs, wg, wu, wd_, group_sizes):
    """xs: [C, D]; wg/wu: [n_le, D, Fc]; wd_: [n_le, Fc, D]."""
    act = cfg.activation_dtype
    n_le = wg.shape[0]
    if n_le == 1:
        g = xs @ wg[0]
        u = xs @ wu[0]
        h = jax.nn.silu(g) * u
        return h @ wd_[0]
    g = jax.lax.ragged_dot(xs, wg, group_sizes)
    u = jax.lax.ragged_dot(xs, wu, group_sizes)
    h = jax.nn.silu(g) * u
    return jax.lax.ragged_dot(h, wd_, group_sizes)


def moe_apply(
    cfg: ModelConfig,
    p: dict,
    x,
    *,
    mesh,
    batch_spec,  # PartitionSpec entry for the batch dim (e.g. ("data",) or None)
    gather_axes: Tuple[str, ...] = (),  # FSDP axes to all-gather expert weights over
    model_axis: str = "model",
):
    """x: [B, S, D] -> [B, S, D]. Pure-functional; shard_map inside."""
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    n_model = mesh.shape[model_axis]
    e_shards, f_shards, n_local_e, slots = moe_layout(cfg, n_model)

    x_spec = P(batch_spec, None, None)
    w_spec = P(model_axis, tuple(gather_axes) if gather_axes else None, None)
    wd_spec = P(model_axis, None, tuple(gather_axes) if gather_axes else None)
    r_spec = P(None, None)

    # rows per *device* after the data-parallel split of the batch
    def local_fn(x_local, router, wg, wu, wd_):
        B_l, S, D = x_local.shape
        act = cfg.activation_dtype
        T = B_l * S
        x_flat = x_local.reshape(T, D)

        # --- routing (replicated over model axis; fp32) ---------------------
        logits = (x_flat.astype(jnp.float32)) @ router  # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)  # [T, k]
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

        # --- local selection -------------------------------------------------
        m = jax.lax.axis_index(model_axis)
        e_start = (m // f_shards) * n_local_e
        flat_e = topi.reshape(-1)  # [T*k]
        flat_w = topw.reshape(-1)
        is_local = (flat_e >= e_start) & (flat_e < e_start + n_local_e)
        sort_key = jnp.where(is_local, flat_e, E)
        order = jnp.argsort(sort_key, stable=True)
        C = _capacity(T * k, e_shards, cfg.moe_capacity_factor)
        sel = order[:C]
        sel_key = sort_key[sel]
        valid = sel_key < E
        sel_local_e = jnp.clip(sel_key - e_start, 0, n_local_e - 1)
        sel_local_e = jnp.where(valid, sel_local_e, n_local_e - 1)
        sel_tok = sel // k

        counts = jnp.bincount(sel_local_e, length=n_local_e)
        group_sizes = counts.astype(jnp.int32)

        xs = jnp.take(x_flat, sel_tok, axis=0)  # [C, D]

        # --- just-in-time ZeRO gather of expert weights ----------------------
        if gather_axes:
            wg = jax.lax.all_gather(wg, gather_axes, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, gather_axes, axis=1, tiled=True)
            wd_ = jax.lax.all_gather(wd_, gather_axes, axis=2, tiled=True)

        out_rows = _grouped_ffn(cfg, xs, wg.astype(act), wu.astype(act), wd_.astype(act),
                                group_sizes)
        w_row = (flat_w[sel] * valid).astype(out_rows.dtype)
        out_rows = out_rows * w_row[:, None]

        out = jnp.zeros((T, D), out_rows.dtype).at[sel_tok].add(out_rows)
        out = jax.lax.psum(out, model_axis)
        return out.reshape(B_l, S, D)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec, w_spec, wd_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, p["router"], p["wg"], p["wu"], p["wd_"])


def moe_apply_token_routed(
    cfg: ModelConfig,
    p: dict,
    x,
    *,
    mesh,
    batch_spec,  # mesh axes the batch dim is sharded over (or None)
):
    """Serve-time EP with experts RESIDENT across the whole mesh.

    A 1T-param MoE cannot replicate experts over the data axis (125 GB/device
    on a 16x16 pod) and ZeRO-gathering weights per decode step moves GBs to
    process KBs of tokens. Decode inverts the ratio: tokens are tiny, so we
    shard the (expert, ffn-chunk) slots over EVERY mesh axis (1T bf16 -> 8 GB
    resident/device), all-gather the token activations over the batch axes
    (~MBs), let each device compute the rows routed to its resident experts,
    and psum the combined output. Collective bytes per step ~ O(T_global * D),
    independent of expert count.
    """
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    # EP domain: (data, model) — pods hold replicas of the expert shards and
    # serve their own batch halves (expert ranges are per (data, model) id)
    ep_axes = tuple(a for a in mesh.axis_names if a != "pod")
    ep = math.prod(mesh.shape[a] for a in ep_axes)
    e_shards, f_shards, n_local_e, slots = moe_layout(cfg, ep)
    batch_axes = () if batch_spec is None else (
        (batch_spec,) if isinstance(batch_spec, str) else tuple(batch_spec))

    x_spec = P(batch_spec, None, None)
    w_spec = P(ep_axes, None, None)
    wd_spec = P(ep_axes, None, None)

    def local_fn(x_local, router, wg, wu, wd_):
        act = cfg.activation_dtype
        if batch_axes:
            x_all = jax.lax.all_gather(x_local, batch_axes, axis=0, tiled=True)
        else:
            x_all = x_local
        B_g, S, D = x_all.shape
        T = B_g * S
        x_flat = x_all.reshape(T, D)

        logits = x_flat.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

        # flattened device id over the EP axes -> disjoint expert ranges
        dev = jnp.int32(0)
        for a in ep_axes:
            dev = dev * mesh.shape[a] + jax.lax.axis_index(a)
        e_start = (dev // f_shards) * n_local_e

        flat_e = topi.reshape(-1)
        flat_w = topw.reshape(-1)
        is_local = (flat_e >= e_start) & (flat_e < e_start + n_local_e)
        sort_key = jnp.where(is_local, flat_e, E)
        order = jnp.argsort(sort_key, stable=True)
        C = _capacity(T * k, e_shards, cfg.moe_capacity_factor)
        sel = order[:C]
        sel_key = sort_key[sel]
        valid = sel_key < E
        sel_local_e = jnp.where(valid, jnp.clip(sel_key - e_start, 0, n_local_e - 1),
                                n_local_e - 1)
        sel_tok = sel // k
        group_sizes = jnp.bincount(sel_local_e, length=n_local_e).astype(jnp.int32)

        xs = jnp.take(x_flat, sel_tok, axis=0)
        out_rows = _grouped_ffn(cfg, xs, wg.astype(act), wu.astype(act),
                                wd_.astype(act), group_sizes)
        w_row = (flat_w[sel] * valid).astype(out_rows.dtype)
        out = jnp.zeros((T, D), out_rows.dtype).at[sel_tok].add(out_rows * w_row[:, None])
        out = jax.lax.psum(out, ep_axes)
        out = out.reshape(B_g, S, D)
        if batch_axes:
            # back to the local batch shard
            n_b = math.prod(mesh.shape[a] for a in batch_axes)
            b_idx = jnp.int32(0)
            for a in batch_axes:
                b_idx = b_idx * mesh.shape[a] + jax.lax.axis_index(a)
            B_l = B_g // n_b
            out = jax.lax.dynamic_slice_in_dim(out, b_idx * B_l, B_l, axis=0)
        return out

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wd_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, p["router"], p["wg"], p["wu"], p["wd_"])


def moe_aux_loss(cfg: ModelConfig, p: dict, x) -> jax.Array:
    """Switch-style load-balance loss over the global batch (fp32)."""
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    x_flat = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    logits = x_flat @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    _, topi = jax.lax.top_k(probs, k)
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    frac_routed = onehot.mean(axis=0) / k
    mean_prob = probs.mean(axis=0)
    return E * jnp.sum(frac_routed * mean_prob)
