"""Production mesh construction.

A FUNCTION, not a module constant: importing this module never touches jax
device state (device count is locked at first backend init; dryrun.py sets
XLA_FLAGS before any jax import).

Single pod: (data=16, model=16) — 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the `pod` axis extends
data parallelism across the DCN/ICI-superpod boundary (gradient all-reduce
crosses it once per step; compressed when --compress-grads).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np

    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"production mesh needs {n} devices, found {len(devices)} — "
            "run under launch/dryrun.py (sets xla_force_host_platform_device_count)"
        )
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, have {n}")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
