"""Elastic scaling: re-mesh a training job onto a different device count.

When a pod loses hosts (or gains them back), the job restarts on a new mesh.
Checkpoints store FULL arrays (repro.checkpoint), so elasticity reduces to:

    1. build the new mesh from the surviving devices (largest (data, model)
       grid that divides the workload),
    2. re-derive PartitionSpecs against it (repro.runtime.sharding sanitizes
       non-divisible axes automatically),
    3. load the checkpoint with the new shardings,
    4. re-jit the step (executable cache keyed by mesh).

tests/test_fault.py round-trips 4 -> 2 -> 4 devices with bitwise-identical
params.
"""
from __future__ import annotations

import jax

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def best_mesh_shape(n_devices: int, prefer_model: int = 16) -> tuple[int, int]:
    """Largest (data, model) grid for the available devices: model axis as
    close to `prefer_model` as divisibility allows, rest data."""
    model = min(prefer_model, n_devices)
    while n_devices % model:
        model -= 1
    return n_devices // model, model


def remesh(devices=None, prefer_model: int = 16) -> jax.sharding.Mesh:
    devices = list(devices if devices is not None else jax.devices())
    data, model = best_mesh_shape(len(devices), prefer_model)
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=devices[: data * model])


def reshard_tree(tree, specs, mesh: jax.sharding.Mesh):
    """Re-place a (host or device) pytree onto `mesh` under `specs`,
    sanitizing non-divisible axes (see runtime.sharding.sanitize_tree)."""
    from repro.runtime.sharding import sanitize_spec

    sizes = {a: mesh.shape[a] for a in mesh.axis_names}

    def place(x, spec):
        arr = np.asarray(jax.device_get(x))
        sp = sanitize_spec(arr.shape, spec, sizes)
        return jax.device_put(arr, NamedSharding(mesh, sp))

    return jax.tree.map(place, tree, specs)
