"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Defined as functions so importing this module never touches jax device
state (dryrun.py must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — dryrun.py "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import"
        )
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices[:n],
    )


def batch_axes(mesh) -> Tuple[str, ...]:
    """Axes the global batch shards over (everything except 'model')."""
    return tuple(n for n in mesh.axis_names if n != "model")


def batch_shards(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def tp_size(mesh) -> int:
    return mesh.shape["model"]
