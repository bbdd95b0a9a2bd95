"""Abstract parameter specs with logical sharding axes.

Parameters are described abstractly (shape + logical axes + init scale) so that
the dry-run can build sharded ``jax.ShapeDtypeStruct`` trees without allocating,
while the real launcher materializes them with ``init_params``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]  # one logical axis name (or None) per dim
    init: str = "normal"  # normal | zeros | ones | ssm_a | ssm_dt
    scale: float = 1.0  # stddev multiplier for normal init
    dtype: Any = jnp.float32
    fan_in: int = 0  # contracted input size for normal init; 0 = shape[-2]

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


def init_param(spec: ParamSpec, key) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "ssm_a":
        # A_log in [log(1), log(16)) per head (mamba2 init)
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(spec.dtype)
    if spec.init == "ssm_dt":
        # dt bias ~ softplus^-1(U(1e-3, 1e-1))
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(spec.dtype)
    # truncated-normal fan-in init
    fan_in = spec.fan_in or (spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1])
    std = spec.scale / np.sqrt(max(1, fan_in))
    return (jax.random.truncated_normal(key, -2.0, 2.0, spec.shape, jnp.float32) * std).astype(
        spec.dtype
    )


def init_params(tree, key) -> Any:
    """Materialize a ParamSpec tree into arrays (deterministic per path)."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [init_param(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(tree) -> Any:
    return tree_map_specs(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


# ---------------------------------------------------------------------------
# Logical -> physical sharding rules
# ---------------------------------------------------------------------------

Rules = Dict[str, Any]  # logical axis name -> mesh axis (str | tuple | None)


def train_rules(multi_pod: bool) -> Rules:
    fsdp = ("pod", "data") if multi_pod else ("data",)
    return {
        "embed": fsdp,  # FSDP: shard the d_model dim of weights
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "expert_slot": "model",  # MoE expert(+ffn-chunk) slots
        "expert_embed": fsdp,  # ZeRO-sharded expert d_model dim (gathered in situ)
        "expert_mlp": None,
        "layers": None,
        "ssm_inner": "model",
        "ssm_heads": "model",
        "state": None,
        "conv": None,
        "batch": fsdp,
        "seq": None,
        "act_embed": None,
        "act_heads": "model",
        "kv_seq": None,
        "moe_mode": "gather",
    }


def fsdp_rules(multi_pod: bool) -> Rules:
    """Pure FSDP/ZeRO-3: batch over every axis; params stored sharded on their
    d_model dim over all axes and all-gathered per layer by GSPMD."""
    allax = ("pod", "data", "model") if multi_pod else ("data", "model")
    return {
        "embed": allax,
        "heads": None,
        "kv_heads": None,
        "head_dim": None,
        "mlp": None,
        "vocab": None,
        "expert_slot": "model",
        "expert_embed": ("pod", "data") if multi_pod else ("data",),
        "expert_mlp": None,
        "moe_mode": "gather",
        "layers": None,
        "ssm_inner": None,
        "ssm_heads": None,
        "state": None,
        "conv": None,
        "batch": allax,
        "seq": None,
        "act_embed": None,
        "act_heads": None,
        "kv_seq": None,
    }


def serve_rules(multi_pod: bool, decode_seq_shard: bool = False) -> Rules:
    """Inference: weights TP over model, replicated over data; batch over data.
    Expert weights are ZeRO-sharded over the data axes and gathered in situ
    (prefill amortizes the gather over thousands of tokens); decode switches
    to token-routed EP (make_rules flips moe_mode/expert_* below)."""
    dp = ("pod", "data") if multi_pod else ("data",)
    return {
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "expert_slot": "model",
        "expert_embed": dp,
        "expert_mlp": None,
        "moe_mode": "gather",
        "layers": None,
        "ssm_inner": "model",
        "ssm_heads": "model",
        "state": None,
        "conv": None,
        "batch": dp,
        "seq": None,
        "act_embed": None,
        "act_heads": "model",
        # flash-decoding style: shard the KV cache sequence over the model axis
        "kv_seq": "model" if decode_seq_shard else None,
    }


def logical_to_spec(logical: Tuple[Optional[str], ...], rules: Rules) -> P:
    return P(*(rules.get(ax) if ax is not None else None for ax in logical))


def resolve_spec(shape: Tuple[int, ...], logical, rules: Rules, mesh) -> P:
    """Shape-aware spec: per dim, keep the longest prefix of the rule's mesh
    axes whose size product divides the dim (e.g. 8 KV heads on a 16-way model
    axis degrade to replication — the standard GQA fallback)."""
    entries = []
    for dim, ax in zip(shape, logical):
        axes = rules.get(ax) if ax is not None else None
        if axes is None:
            entries.append(None)
            continue
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        keep, prod = [], 1
        for a in axes:
            if dim % (prod * mesh.shape[a]) == 0:
                keep.append(a)
                prod *= mesh.shape[a]
            else:
                break
        entries.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*entries)


def param_pspecs(tree, rules: Rules, mesh=None):
    """PartitionSpec pytree for a ParamSpec tree."""
    if mesh is None:
        return tree_map_specs(lambda s: logical_to_spec(s.logical, rules), tree)
    return tree_map_specs(lambda s: resolve_spec(s.shape, s.logical, rules, mesh), tree)


def param_shardings(tree, mesh, rules: Rules):
    return tree_map_specs(
        lambda s: NamedSharding(mesh, resolve_spec(s.shape, s.logical, rules, mesh)), tree
    )
