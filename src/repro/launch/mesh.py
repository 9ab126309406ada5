"""Mesh factories (assignment-mandated shapes).

``make_production_mesh`` is a FUNCTION (never module-level state) so
importing this module touches no jax device state.  Single-pod: 16x16
(data, model) = 256 chips.  Multi-pod: 2x16x16 (pod, data, model) = 512
chips; the ``pod`` axis composes with ``data`` for batch/FSDP sharding
and carries the hierarchical (DCN) gradient reduction.
"""

from __future__ import annotations

from typing import Tuple

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _make_mesh((data, model), ("data", "model"))


def make_shard_mesh(n: int | None = None):
    """1-D ``("shard",)`` mesh over the host's devices — the cluster
    scatter (``repro.cluster.mesh_scatter``) lays K store shards out
    along this axis, ``ceil(K / n)`` per device.  ``n`` caps the device
    count (default: all devices, including
    ``xla_force_host_platform_device_count``-virtualized ones)."""
    n_dev = len(jax.devices())
    n = n_dev if n is None else max(1, min(int(n), n_dev))
    return _make_mesh((n,), ("shard",))


def mesh_axes(mesh) -> Tuple[Tuple[str, ...], str]:
    """Returns (batch/FSDP axes, tensor axis) for a mesh from this module."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data"), "model"
    return ("data",), "model"
