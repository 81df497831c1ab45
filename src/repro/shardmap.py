"""``jax.shard_map`` helpers — one import site for the repo.

``repro.model.moe``, ``repro.optim.compress`` and the multi-device tests
write ``jax.shard_map`` through this module: ``axis_names=`` (partial-manual
mode) and ``check_vma=`` are forwarded only when given, and ``pvary``
annotates a value as varying over manual axes for the VMA checker.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pvary(x, axis_names):
    """Mark ``x`` as varying over the manual ``axis_names``."""
    return jax.lax.pcast(x, axis_names, to="varying")


axis_size = jax.lax.axis_size
