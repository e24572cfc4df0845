"""Production meshes.

Single pod: 16×16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the pod axis is
pure data parallelism so only gradient all-reduces cross the (slower) DCN
boundary; growing the fleet means growing `pod`.

Defined as functions (never module-level constants) so importing this file
touches no JAX device state — the dry-run must set XLA_FLAGS before the
first device query.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)} — the "
            "dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import"
        )
    return _auto_mesh(shape, axes, devs[:need])


def make_smoke_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over however many (fake) devices the test process has."""
    need = 1
    for s in shape:
        need *= s
    return _auto_mesh(shape, axes, jax.devices()[:need])
