"""Optional multi-device sharding of large serving batches.

A farm dispatch is one ``(B, L, F)`` batch through one compiled program;
on a host with several devices (or forced host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) the batch axis is
embarrassingly parallel — every template is batch-row independent, the
same property that makes micro-batching bit-exact. This module wraps an
:class:`~repro.rtl.backend.RTLExecutable` so each dispatch shards the
batch over a 1-D device mesh with :func:`repro.shardmap.shard_map` (the
repo's one jax-version-portable import site) on a mesh built the
:mod:`repro.launch.mesh` way.

:class:`ShardedExecutable` keeps the Deployment duck type the farm needs:
callable on float windows, ``holds_program`` for router affinity, a
``trace_count`` observable, and bit-exactness — outputs are integer-
identical to the unsharded executable because every device runs the same
integer graph walk on its batch slice.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_smoke_mesh
from repro.quant.fixedpoint import fxp_to_int
from repro.rtl.program_cache import ProgramLRU
from repro.shardmap import shard_map


def make_serving_mesh(n_devices: Optional[int] = None):
    """A 1-D ``("batch", "model")`` mesh over the host's devices (model
    axis fixed at 1 — serving shards only the batch)."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return make_smoke_mesh(shape=(n, 1), axes=("batch", "model"))


class ShardedExecutable:
    """An ``RTLExecutable`` whose dispatches shard the batch over a mesh.

    ``__call__`` pads the batch up to a multiple of the mesh's batch axis,
    splits it across devices with ``shard_map`` over the emulator's staged
    graph walk (``_execute`` is pure and traceable — the same function the
    per-shape program LRU jits), and slices the padding back off. Programs
    are cached per padded ``(shape, dtype)`` exactly like the unsharded
    executor, so :meth:`holds_program` keeps router affinity meaningful.
    """

    def __init__(self, exe, mesh=None, *, max_programs: int = 8):
        self.exe = exe
        self.mesh = mesh if mesh is not None else make_serving_mesh()
        self.n_shards = int(self.mesh.shape["batch"])
        # the same locked LRU the emulator uses — farm worker threads hit
        # this cache concurrently, and an unlocked pop/insert/evict dance
        # can drop or duplicate entries under contention
        self._programs = ProgramLRU(max_programs)
        self.trace_count = 0

    @property
    def emulator(self):
        return self.exe.emulator

    @property
    def graph(self):
        return self.exe.graph

    def holds_program(self, shape, dtype) -> bool:
        # programs are keyed on the padded int32 batch the dispatch actually
        # runs, not the caller's float dtype
        b = self._padded_b(int(shape[0]))
        key = ((b,) + tuple(int(d) for d in shape[1:]),
               jnp.dtype(jnp.int32).name)
        return key in self._programs

    def _padded_b(self, b: int) -> int:
        n = self.n_shards
        return ((b + n - 1) // n) * n

    def _program(self, shape: Tuple[int, ...], dtype):
        def build():
            emu = self.exe.emulator
            out_edge = emu.graph.outputs[0]

            def walk(x_int):
                self.trace_count += 1        # python side effect: trace-time
                return emu._execute(x_int, mode=emu.mode)[out_edge]

            from jax.sharding import PartitionSpec as P

            sharded = shard_map(walk, mesh=self.mesh,
                                in_specs=P("batch"), out_specs=P("batch"),
                                check_vma=False)
            return jax.jit(sharded)

        prog, _hit, _evicted = self._programs.get_or_build(
            (tuple(shape), jnp.dtype(dtype).name), build)
        return prog

    def __call__(self, x) -> jax.Array:
        emu = self.exe.emulator
        in_fmt = emu.graph.edges[emu.graph.inputs[0]].fmt
        out_fmt = emu.graph.edges[emu.graph.outputs[0]].fmt
        x_int = jnp.asarray(fxp_to_int(jnp.asarray(x), in_fmt), jnp.int32)
        b = int(x_int.shape[0])
        pb = self._padded_b(b)
        if pb > b:                           # pad rows to a shard multiple
            filler = jnp.zeros((pb - b,) + x_int.shape[1:], x_int.dtype)
            x_int = jnp.concatenate([x_int, filler], axis=0)
        y_int = self._program(x_int.shape, x_int.dtype)(x_int)
        return y_int[:b].astype(jnp.float32) / out_fmt.scale

    def run_many(self, xs):
        """List-of-batches entry matching ``RTLExecutable.run_many``."""
        if not isinstance(xs, (list, tuple)):
            return self(xs)
        sizes = [int(np.asarray(x).shape[0]) for x in xs]
        out = self(jnp.concatenate([jnp.asarray(x) for x in xs], axis=0))
        res, off = [], 0
        for s in sizes:
            res.append(out[off:off + s])
            off += s
        return res
