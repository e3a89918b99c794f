"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS --xla_force_host_platform_device_count=512 before *its* first
jax import, while smoke tests and benchmarks see the single real device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_mesh", "batch_axes_for"]


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)}; the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count before importing jax")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axis order is (pod,) data, model — "pod" is the slowest (DCN-connected)
    dimension, so only data-parallel collectives cross pods.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes_for(mesh: Mesh, global_batch: int) -> Tuple[str, ...]:
    """Physical axes for the logical "batch" dimension.

    Uses ("pod", "data") when both exist and divide the batch; degrades to
    ("data",) or () for small-batch (e.g. batch-1 long-context decode)
    shapes where batch sharding is impossible.
    """
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    while axes:
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if global_batch % size == 0:
            return tuple(axes)
        axes.pop(0)         # drop "pod" first
    return ()
