"""Composable model: parameter specs, train forward, prefill and decode.

One stack serves all 10 assigned architectures (plus the paper's own
workloads): the config's ``pattern`` decides the per-group block sequence
(attention / sliding-window attention / mamba), MoE placement, encoder-decoder
wiring and modality stubs. Depth is folded into ``lax.scan`` over
``num_groups`` stacked parameter groups so HLO size is O(pattern), not
O(num_layers).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ATTN, LOCAL, MAMBA, ModelConfig
from repro.models.layers import mlp, mlp_specs, rmsnorm, rmsnorm_spec, softcap
from repro.models.param import (
    ParamSpec,
    Rules,
    is_spec,
    logical_to_spec,
    resolve_spec,
    tree_map_specs,
)


# ---------------------------------------------------------------------------
# Mesh context: sharding constraints from logical axes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshCtx:
    mesh: Any
    rules: Rules

    def spec(self, *logical) -> P:
        return logical_to_spec(tuple(logical), self.rules)

    def shard(self, x, *logical):
        spec = resolve_spec(x.shape, tuple(logical), self.rules, self.mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    @property
    def n_model(self) -> int:
        return self.mesh.shape["model"]

    @property
    def batch_axes(self):
        return self.rules.get("batch")

    @property
    def expert_gather_axes(self) -> Tuple[str, ...]:
        ax = self.rules.get("expert_embed")
        if ax is None:
            return ()
        return tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)


# ---------------------------------------------------------------------------
# Block-level parameter specs
# ---------------------------------------------------------------------------

def _has_ffn(cfg: ModelConfig, kind: str) -> bool:
    return kind != MAMBA or cfg.ffn_every_block


def _is_moe_block(cfg: ModelConfig, idx: int, kind: str) -> bool:
    if not cfg.moe_num_experts or not _has_ffn(cfg, kind):
        return False
    if cfg.moe_layer_period == 1:
        return True
    return idx % cfg.moe_layer_period == cfg.moe_layer_period - 1


def block_specs(cfg: ModelConfig, idx: int, kind: str, moe_shards: int, *, cross: bool) -> dict:
    D = cfg.d_model
    p: Dict[str, Any] = {}
    if kind == MAMBA:
        p["ln"] = rmsnorm_spec(D)
        p["ssm"] = ssm_mod.ssm_specs(cfg)
    else:
        p["ln_attn"] = rmsnorm_spec(D)
        p["attn"] = attn_mod.attn_specs(cfg)
        if cfg.use_post_norm:
            p["post_ln_attn"] = rmsnorm_spec(D)
        if cross:
            p["ln_cross"] = rmsnorm_spec(D)
            p["cross"] = attn_mod.attn_specs(cfg, cross=True)
    if _has_ffn(cfg, kind):
        p["ln_mlp"] = rmsnorm_spec(D)
        if _is_moe_block(cfg, idx, kind):
            p["moe"] = moe_mod.moe_specs(cfg, moe_shards)
            if cfg.moe_shared_expert_ff:
                p["shared_mlp"] = mlp_specs(cfg, cfg.moe_shared_expert_ff)
        else:
            p["mlp"] = mlp_specs(cfg)
        if cfg.use_post_norm:
            p["post_ln_mlp"] = rmsnorm_spec(D)
    return p


def _stack_specs(tree, n: int):
    return tree_map_specs(
        lambda s: replace(s, shape=(n,) + s.shape, logical=("layers",) + s.logical),
        tree,
    )


def model_specs(cfg: ModelConfig, n_model: int, moe_shards: int = 0) -> dict:
    """Full abstract parameter tree. ``moe_shards``: size of the expert-
    parallel domain (defaults to the model axis; the token-routed serve path
    uses data x model)."""
    moe_shards = moe_shards or n_model
    D, V = cfg.d_model, cfg.vocab_size
    wd = cfg.weight_dtype
    specs: Dict[str, Any] = {
        "embed": ParamSpec((V, D), ("vocab", "embed"), scale=1.0, dtype=wd),
        "final_norm": rmsnorm_spec(D),
    }
    if not cfg.tie_embeddings and not cfg.is_encoder_only:
        specs["unembed"] = ParamSpec((D, V), ("embed", "vocab"), dtype=wd)
    cross = cfg.is_encoder_decoder
    group = {
        f"b{i}": block_specs(cfg, i, kind, moe_shards, cross=cross)
        for i, kind in enumerate(cfg.pattern)
    }
    specs["decoder"] = _stack_specs(group, cfg.num_groups)
    if cfg.is_encoder_decoder:
        enc_layer = block_specs(cfg, 0, ATTN, moe_shards, cross=False)
        specs["encoder"] = _stack_specs(enc_layer, cfg.num_encoder_layers)
        specs["enc_norm"] = rmsnorm_spec(D)
    if cfg.is_encoder_only:
        specs["mlm_head"] = ParamSpec((D, V), ("embed", "vocab"), dtype=wd)
    return specs


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _ffn_apply(cfg, bp, h, ctx: MeshCtx, aux_losses):
    y = rmsnorm(h, bp["ln_mlp"], cfg.norm_eps)
    if "moe" in bp:
        if ctx.rules.get("moe_mode") == "token":
            out = moe_mod.moe_apply_token_routed(
                cfg, bp["moe"], y, mesh=ctx.mesh, batch_spec=ctx.batch_axes)
        else:
            out = moe_mod.moe_apply(
                cfg, bp["moe"], y,
                mesh=ctx.mesh,
                batch_spec=ctx.batch_axes,
                gather_axes=ctx.expert_gather_axes,
            )
        if aux_losses is not None:
            aux_losses.append(moe_mod.moe_aux_loss(cfg, bp["moe"], y))
        if "shared_mlp" in bp:
            out = out + mlp(cfg, bp["shared_mlp"], y)
    else:
        out = mlp(cfg, bp["mlp"], y)
    if cfg.use_post_norm:
        out = rmsnorm(out, bp["post_ln_mlp"], cfg.norm_eps)
    return h + out


def _group_forward(cfg, gp, h, *, ctx, positions, causal, enc_out, aux_losses):
    """Run one pattern group at full sequence length."""
    for i, kind in enumerate(cfg.pattern):
        bp = gp[f"b{i}"]
        if kind == MAMBA:
            h = h + ssm_mod.ssm_forward(cfg, bp["ssm"], rmsnorm(h, bp["ln"], cfg.norm_eps))
        else:
            window = cfg.window_size if kind == LOCAL else 0
            a = attn_mod.self_attention(
                cfg, bp["attn"], rmsnorm(h, bp["ln_attn"], cfg.norm_eps),
                positions=positions, causal=causal, window=window,
            )
            if cfg.use_post_norm:
                a = rmsnorm(a, bp["post_ln_attn"], cfg.norm_eps)
            h = h + a
            if enc_out is not None:
                enc_kv = attn_mod.project_cross_kv(cfg, bp["cross"], enc_out)
                c = attn_mod.cross_attention(
                    cfg, bp["cross"], rmsnorm(h, bp["ln_cross"], cfg.norm_eps),
                    enc_kv,
                )
                h = h + c
        if _has_ffn(cfg, kind):
            h = _ffn_apply(cfg, bp, h, ctx, aux_losses)
        h = ctx.shard(h, "batch", "seq", "act_embed")
        if cfg.grad_barrier:
            # Pin the residual stream to bf16 across the TP boundary: without
            # this XLA hoists rmsnorm's fp32 upcast above the all-reduce and
            # every activation collective doubles (EXPERIMENTS §Perf H2).
            (h,) = jax.lax.optimization_barrier((h,))
    return h


def _unroll(cfg, length):
    return length if cfg.unroll_layers else 1


def _remat(cfg, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _run_encoder(cfg, params, enc_embeds, ctx):
    h = enc_embeds.astype(cfg.activation_dtype)
    S = h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    def body(carry, lp):
        out = _group_forward(cfg, {"b0": lp}, carry, ctx=ctx, positions=positions,
                             causal=False, enc_out=None, aux_losses=None)
        return out, None

    h, _ = jax.lax.scan(_remat(cfg, body), h, params["encoder"], unroll=_unroll(cfg, cfg.num_encoder_layers))
    return rmsnorm(h, params["enc_norm"], cfg.norm_eps)


def _embed_inputs(cfg, params, batch, ctx):
    """Token/modality embedding. Returns (h, enc_out)."""
    act = cfg.activation_dtype
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _run_encoder(cfg, params, batch["enc_embeds"], ctx)
    h = jnp.take(params["embed"], batch["tokens"], axis=0).astype(act)
    if cfg.frontend == "vision_stub":
        img = batch["image_embeds"].astype(act)  # [B, Ni, D]
        h = jnp.concatenate([img, h], axis=1)
    h = ctx.shard(h, "batch", "seq", "act_embed")
    if enc_out is not None:
        enc_kv = None  # cross-attn projects enc_out per block
        enc_out = ctx.shard(enc_out, "batch", "seq", "act_embed")
    return h, enc_out


def _decoder_stack(cfg, params, h, *, ctx, positions, causal, enc_out, aux_losses):
    def body(carry, gp):
        out = _group_forward(cfg, gp, carry, ctx=ctx, positions=positions,
                             causal=causal, enc_out=enc_out, aux_losses=None)
        return out, None

    if aux_losses is not None and cfg.moe_num_experts:
        # accumulate aux loss outside the scan (first group only, as a
        # representative sample — the router distribution is what matters)
        first = jax.tree.map(lambda x: x[0], params["decoder"])
        for i, kind in enumerate(cfg.pattern):
            if "moe" in first[f"b{i}"]:
                y = rmsnorm(h, first[f"b{i}"]["ln_mlp"], cfg.norm_eps)
                aux_losses.append(moe_mod.moe_aux_loss(cfg, first[f"b{i}"]["moe"], y))
                break
    h, _ = jax.lax.scan(_remat(cfg, body), h, params["decoder"], unroll=_unroll(cfg, cfg.num_groups))
    return rmsnorm(h, params["final_norm"], cfg.norm_eps)


def _logits(cfg, params, h, ctx):
    act = cfg.activation_dtype
    if cfg.is_encoder_only:
        w = params["mlm_head"].astype(act)
    elif cfg.tie_embeddings:
        w = params["embed"].astype(act).T
    else:
        w = params["unembed"].astype(act)
    logits = jnp.einsum("bsd,dv->bsv", h, w, preferred_element_type=jnp.float32)
    if cfg.final_logit_softcap:
        logits = softcap(logits, cfg.final_logit_softcap)
    return ctx.shard(logits, "batch", "seq", "vocab")


def loss_fn(cfg: ModelConfig, params, batch, ctx: MeshCtx):
    """Next-token (or MLM) cross-entropy loss, fp32."""
    h, enc_out = _embed_inputs(cfg, params, batch, ctx)
    S = h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    causal = not cfg.is_encoder_only
    aux_losses: Optional[list] = [] if cfg.moe_num_experts else None
    h = _decoder_stack(cfg, params, h, ctx=ctx, positions=positions, causal=causal,
                       enc_out=enc_out, aux_losses=aux_losses)
    logits = _logits(cfg, params, h, ctx)

    tokens = batch["tokens"]
    n_txt = tokens.shape[1]
    if cfg.is_encoder_only:
        targets = batch["targets"]
        lg = logits
    else:
        # causal LM: predict token t+1 at text position t
        targets = tokens[:, 1:]
        lg = logits[:, -n_txt:, :][:, :-1, :]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    if aux_losses:
        ce = ce + cfg.moe_aux_loss_weight * sum(aux_losses)
    return ce


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

# KV caches are padded to a multiple of CACHE_PAD so the sequence dim always
# divides the mesh axes (a non-dividing dim silently loses its sharding and
# replicates cache reads — measured 16x flops/bytes on whisper decode_32k).
CACHE_PAD = 512


def cache_len(T: int) -> int:
    return -(-T // CACHE_PAD) * CACHE_PAD


def _cache_shape(cfg: ModelConfig, kind: str, idx: int, B: int, T: int, enc_S: int):
    """Abstract cache entry (shapes + logical axes) for one block kind."""
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    d_in, H, G, N = ssm_mod.ssm_dims(cfg)
    W = cfg.ssm_conv_width
    act = cfg.activation_dtype
    if kind == MAMBA:
        return {
            "state": ParamSpec((B, H, N, cfg.ssm_headdim),
                               ("batch", "ssm_heads", None, None), "zeros", dtype=jnp.float32),
            "conv_x": ParamSpec((B, W - 1, d_in), ("batch", None, "ssm_inner"), "zeros", dtype=act),
            "conv_B": ParamSpec((B, W - 1, G * N), ("batch", None, None), "zeros", dtype=act),
            "conv_C": ParamSpec((B, W - 1, G * N), ("batch", None, None), "zeros", dtype=act),
        }
    Tc = min(T, cfg.window_size) if kind == LOCAL and cfg.window_size else cache_len(T)
    e: Dict[str, Any] = {
        "k": ParamSpec((B, Tc, KV, hd), ("batch", "kv_seq", None, None), "zeros", dtype=act),
        "v": ParamSpec((B, Tc, KV, hd), ("batch", "kv_seq", None, None), "zeros", dtype=act),
    }
    if cfg.is_encoder_decoder:
        e["cross_k"] = ParamSpec((B, enc_S, KV, hd), ("batch", None, "kv_heads", None), "zeros", dtype=act)
        e["cross_v"] = ParamSpec((B, enc_S, KV, hd), ("batch", None, "kv_heads", None), "zeros", dtype=act)
    return e


def cache_specs(cfg: ModelConfig, B: int, T: int, enc_S: int = 0) -> dict:
    group = {
        f"b{i}": _cache_shape(cfg, kind, i, B, T, enc_S)
        for i, kind in enumerate(cfg.pattern)
    }
    return _stack_specs(group, cfg.num_groups)


def prefill_fn(cfg: ModelConfig, params, batch, ctx: MeshCtx, max_len: int):
    """Process the prompt; return (last-position logits, cache)."""
    h, enc_out = _embed_inputs(cfg, params, batch, ctx)
    B, S = h.shape[0], h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    def body(carry, gp):
        hh = carry
        caches = {}
        for i, kind in enumerate(cfg.pattern):
            bp = gp[f"b{i}"]
            if kind == MAMBA:
                y, (state, tails) = ssm_mod.ssm_forward(
                    cfg, bp["ssm"], rmsnorm(hh, bp["ln"], cfg.norm_eps), return_state=True)
                hh = hh + y
                caches[f"b{i}"] = {"state": state, "conv_x": tails["x"],
                                   "conv_B": tails["B"], "conv_C": tails["C"]}
            else:
                window = cfg.window_size if kind == LOCAL else 0
                a, (k, v) = attn_mod.self_attention(
                    cfg, bp["attn"], rmsnorm(hh, bp["ln_attn"], cfg.norm_eps),
                    positions=positions, causal=True, window=window, return_kv=True)
                if cfg.use_post_norm:
                    a = rmsnorm(a, bp["post_ln_attn"], cfg.norm_eps)
                hh = hh + a
                ce = {}
                if kind == LOCAL and cfg.window_size and cfg.window_size <= S:
                    W = cfg.window_size
                    idx = S - W + jnp.mod(jnp.arange(W) - (S - W), W)
                    ce["k"], ce["v"] = k[:, idx], v[:, idx]
                else:
                    Tc = min(max_len, cfg.window_size) if kind == LOCAL and cfg.window_size else max_len
                    pad = [(0, 0), (0, Tc - S), (0, 0), (0, 0)]
                    ce["k"], ce["v"] = jnp.pad(k, pad), jnp.pad(v, pad)
                if enc_out is not None:
                    enc_kv = attn_mod.project_cross_kv(cfg, bp["cross"], enc_out)
                    c = attn_mod.cross_attention(
                        cfg, bp["cross"], rmsnorm(hh, bp["ln_cross"], cfg.norm_eps),
                        enc_kv)
                    hh = hh + c
                    ce["cross_k"], ce["cross_v"] = enc_kv
                caches[f"b{i}"] = ce
            if _has_ffn(cfg, kind):
                hh = _ffn_apply(cfg, bp, hh, ctx, None)
            hh = ctx.shard(hh, "batch", "seq", "act_embed")
        return hh, caches

    h, cache = jax.lax.scan(body, h, params["decoder"], unroll=_unroll(cfg, cfg.num_groups))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, h[:, -1:, :], ctx)
    return logits, cache


def decode_fn(cfg: ModelConfig, params, token, pos, cache, ctx: MeshCtx):
    """One decode step. token: [B,1] int32; pos: scalar int32; cache pytree."""
    act = cfg.activation_dtype
    h = jnp.take(params["embed"], token, axis=0).astype(act)
    h = ctx.shard(h, "batch", None, "act_embed")

    def body(carry, xs):
        hh = carry
        gp, gc = xs
        new_c = {}
        for i, kind in enumerate(cfg.pattern):
            bp, bc = gp[f"b{i}"], gc[f"b{i}"]
            if kind == MAMBA:
                y, (state, tails) = ssm_mod.ssm_decode(
                    cfg, bp["ssm"], rmsnorm(hh, bp["ln"], cfg.norm_eps),
                    bc["state"], {"x": bc["conv_x"], "B": bc["conv_B"], "C": bc["conv_C"]})
                hh = hh + y
                new_c[f"b{i}"] = {"state": state, "conv_x": tails["x"],
                                  "conv_B": tails["B"], "conv_C": tails["C"]}
            else:
                is_ring = kind == LOCAL and cfg.window_size and bc["k"].shape[1] == cfg.window_size
                x_norm = rmsnorm(hh, bp["ln_attn"], cfg.norm_eps)
                if is_ring:
                    y, ck, cv = attn_mod.decode_ring_attention(
                        cfg, bp["attn"], x_norm, bc["k"], bc["v"], pos, cfg.window_size)
                else:
                    window = cfg.window_size if kind == LOCAL else 0
                    y, ck, cv = attn_mod.decode_self_attention(
                        cfg, bp["attn"], x_norm, bc["k"], bc["v"], pos, window=window)
                if cfg.use_post_norm:
                    y = rmsnorm(y, bp["post_ln_attn"], cfg.norm_eps)
                hh = hh + y
                ce = {"k": ck, "v": cv}
                if cfg.is_encoder_decoder:
                    c = attn_mod.cross_attention(
                        cfg, bp["cross"], rmsnorm(hh, bp["ln_cross"], cfg.norm_eps),
                        (bc["cross_k"].astype(act), bc["cross_v"].astype(act)))
                    hh = hh + c
                    ce["cross_k"], ce["cross_v"] = bc["cross_k"], bc["cross_v"]
                new_c[f"b{i}"] = ce
            if _has_ffn(cfg, kind):
                hh = _ffn_apply(cfg, bp, hh, ctx, None)
        return hh, new_c

    h, new_cache = jax.lax.scan(body, h, (params["decoder"], cache), unroll=_unroll(cfg, cfg.num_groups))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, h, ctx)
    return logits, new_cache
