"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Nothing runs: each test lowers a main-path program for one chip of a
described ``v5e:2x2`` topology and compiles it with the TPU compiler, which
refuses what the chip would refuse (unsupported ops, VMEM overflow, a
program larger than the chip's 16 GB). The topology is described only inside
the ``topo`` fixture, which skips where it cannot be described; the
persistent compilation cache is off around these compiles, since an entry
compiled for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from conftest import parity_scenario

V5E_HBM_BYTES = 16 * 2**30
N_ROWS, N_TICKS, N_MEMBERS = 12, 10_800, 4096  # 12 rows x 6 h at 2 s ticks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_scan_engine_compiles_for_v5e(one_chip):
    """The float64 ``lax.scan`` engine at the dense-tail cell of
    ``chip_smoke.py``: 4,096 members, per-tick series and brake plane kept."""
    from repro.provisioning import batched
    from repro.provisioning.montecarlo import EnsembleSpec

    sc = parity_scenario(generator="bursty", n_rows=N_ROWS,
                         duration_s=N_TICKS * 2.0)
    model, _, _ = batched.lower_ensemble(
        EnsembleSpec(sc, n_seeds=N_MEMBERS, seed0=1000))
    assert (model.n_ticks, model.n_rows) == (N_TICKS, N_ROWS)
    cfg, _, idx = batched._plan_bucket([model], keep_series=True,
                                       keep_fire=True, member_chunk=None,
                                       mesh=None)
    operands = batched._bucket_operands([model], idx)
    with jax.enable_x64(True):
        compiled = batched._jax_runner(cfg, None).lower(
            *_shapes(operands, one_chip)).compile()
    assert _program_bytes(compiled) < V5E_HBM_BYTES


def test_tick_kernel_compiles_for_v5e_float32(one_chip):
    """The Pallas tick kernel lowers through Mosaic in float32 at 12 rows,
    10,800 ticks and 8-member blocks. Members are cut to 64: rows sit on the
    lane axis, so each [T, N, R] plane pads 12 lanes to 128 in HBM, and
    4,096 members would need 23 GB per plane."""
    from repro.kernels import ops
    from repro.kernels.tick import TickConsts

    consts = TickConsts(t1=0.90, t2=0.97, t1_buf=0.02, t2_buf=0.02,
                        lp_t1=0.85, lp_t2=0.70, hp_t2=0.85, brake_freq=0.50,
                        p0_srv_w=180.0, k_lp_w=300.0, k_hp_w=150.0,
                        lp_share=0.6, gamma=1.6, n_servers=24.0,
                        power_scale=1.10)
    n = 64
    args = (jax.ShapeDtypeStruct((n, N_TICKS, N_ROWS), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((N_TICKS, N_ROWS), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((N_ROWS,), jnp.float32, sharding=one_chip))
    compiled = ops.polca_tick.lower(
        *args, consts=consts, oob_ticks=20, brake_ticks=3, ring_depth=21,
        esc=25, block_members=8, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _program_bytes(compiled) < V5E_HBM_BYTES


def test_llama_prefill_compiles_for_v5e(topo):
    """The serve path's prefill step for llama3.2-1b at full width (16
    layers, d_model 2048, vocab 128,256) on a one-chip mesh with Auto axes:
    8 requests of 128 prompt tokens in a 160-token cache."""
    from repro.configs import get_config
    from repro.launch.inputs import make_rules
    from repro.launch.steps import build_prefill_step
    from repro.models import model as model_mod
    from repro.models.config import ShapeConfig
    from repro.models.param import init_params

    cfg = get_config("llama3.2-1b")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    shape = ShapeConfig("serve", 160, 8, "prefill")
    rules = make_rules(cfg, shape, mesh)
    params = jax.eval_shape(
        lambda: init_params(model_mod.model_specs(cfg, 1), jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((8, 128), jnp.int32)
    step = jax.jit(build_prefill_step(cfg, shape, mesh, rules))
    with jax.set_mesh(mesh):
        compiled = step.lower(_shapes(params, sharding),
                              {"tokens": _shapes(tokens, sharding)}).compile()
    assert _program_bytes(compiled) < V5E_HBM_BYTES
