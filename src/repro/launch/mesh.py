"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations


import jax

from repro.core.types import MULTI_POD, SINGLE_POD, MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present; "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before any "
            "jax import")
    import numpy as np

    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(shape), axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_smoke_mesh(shape=(1, 1), axes=("data", "model")):
    """A mesh over the first ``prod(shape)`` devices; raises when the host
    has fewer, never builds a smaller mesh."""
    import numpy as np

    n = shape[0] * shape[1]
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"mesh {tuple(shape)} needs {n} devices but "
                           f"only {len(devices)} present")
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape), axes)
