"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model and the batched
    engine place values with ``with_sharding_constraint`` / ``shard_map``,
    which under ``Explicit`` axes (the JAX 0.9 default) would assert the
    spec instead of steering the partitioner."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_mesh(data: int = 0):
    """1-D ``("data",)`` mesh for member-axis sharding (batched engine).

    ``data=0`` spans every visible device. With
    ``--xla_force_host_platform_device_count=8`` (pinned in
    ``tests/conftest.py``) this exercises the real sharded path on CPU CI."""
    if data <= 0:
        data = len(jax.devices())
    return _make_mesh((data,), ("data",))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many devices are actually present (tests/smoke)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
