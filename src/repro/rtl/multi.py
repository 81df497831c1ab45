"""Batched multi-design emulation — vmap the Elastic Node (DESIGN.md §15).

Design-space search evaluates K candidate accelerators that differ only in
their trained values: same node kinds, shapes, LUT sizes and Q-formats,
different weights. After the PR-10 executor refactor those candidates are
*program-isomorphic* (:func:`repro.rtl.ir.iso_key`) — the staged graph walk
traces to one program taking the array constants as arguments — so the
whole candidate set can be emulated as ONE dispatch: stack every design's
params along a leading design axis and ``jax.vmap`` the shared walk over
it. Toolflow turnaround, not per-run latency, bounds embedded DSE
throughput; this turns K sequential trace+compile+run cycles into one.

The design-axis program runs the pure-``jnp`` walk — the one execution
path whose primitives all carry batching rules, and bit-exact against
``fused``/``pallas`` by the §4 contract (re-pinned per design by the
multi-emulation tests and :func:`repro.verify.conformance.run_conformance_batch`).
On a multi-device host (`XLA_FLAGS=--xla_force_host_platform_device_count`
counts) ``shard=True`` additionally splits the design axis across a 1-D
mesh with :func:`repro.shardmap.shard_map` — candidates are independent,
so the partitioning is embarrassing. It raises where the device count does
not divide K rather than fall back to the unsharded vmap.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import get_metrics, get_tracer
from repro.quant.fixedpoint import fxp_to_int
from repro.rtl.emulator import EmulationResult, RTLEmulator
from repro.rtl.ir import Graph, iso_key
from repro.rtl.program_cache import ProgramLRU


def assert_isomorphic(graphs: Sequence[Graph]) -> str:
    """The shared iso key of ``graphs``; raises listing every mismatch."""
    if not graphs:
        raise ValueError("need at least one graph")
    keys = [iso_key(g) for g in graphs]
    bad = [(i, graphs[i].name, k)
           for i, k in enumerate(keys) if k != keys[0]]
    if bad:
        lines = ", ".join(f"#{i} {name!r} ({k})" for i, name, k in bad)
        raise ValueError(
            f"graphs are not program-isomorphic to #0 "
            f"{graphs[0].name!r} ({keys[0]}): {lines} — same node "
            "kinds/shapes/LUT sizes and Q-formats are required; only "
            "weight/bias values may differ")
    return keys[0]


def stack_params(emulators: Sequence[RTLEmulator]):
    """Stack K isomorphic emulators' traced-param pytrees along a new
    leading design axis (the axis the shared program is vmapped over)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[em.params() for em in emulators])


class MultiDesignEmulator:
    """K isomorphic candidate designs behind one vmapped compiled program.

    Construction validates isomorphism, stages every candidate's constants
    (one :class:`RTLEmulator` per design, all sharing one
    :class:`ProgramLRU` — so even their *single*-design dispatches compile
    once), and stacks the params. :meth:`run_int` then emulates all K
    designs in one dispatch:

    * ``per_design=False`` (default) — one shared stimulus ``(B, ...)``
      broadcast to every design (the conformance-sweep shape);
    * ``per_design=True`` — stacked stimulus ``(K, B, ...)``, row k to
      design k.

    Outputs carry a leading design axis: ``result.outputs[k]`` is
    bit-identical to ``self.emulators[k].run_int(x).outputs`` (and, by the
    §4 contract, to the ``fused``/``pallas`` paths of a per-design
    emulator — the acceptance check of DESIGN.md §15).
    """

    def __init__(self, graphs: Sequence[Graph], *, max_programs: int = 4,
                 shard: bool = False,
                 programs: Optional[ProgramLRU] = None):
        self.graphs: List[Graph] = list(graphs)
        self.iso_key = assert_isomorphic(self.graphs)
        self.k = len(self.graphs)
        self.programs = programs if programs is not None \
            else ProgramLRU(max_programs)
        self.emulators = [RTLEmulator(g, mode="jnp", programs=self.programs)
                          for g in self.graphs]
        self._base = self.emulators[0]
        self._params = stack_params(self.emulators)
        self.mesh = self._design_mesh() if shard else None
        self.sharded = self.mesh is not None
        self.trace_count = 0

    def _design_mesh(self):
        """A 1-D ``("design", "model")`` mesh over every device of the
        host; raises where the devices cannot split K evenly."""
        n = len(jax.devices())
        if self.k % n != 0:
            raise ValueError(f"shard=True needs the device count to divide "
                             f"K: {n} devices, K={self.k}")
        from repro.launch.mesh import make_smoke_mesh

        return make_smoke_mesh(shape=(n, 1), axes=("design", "model"))

    # -- the shared program -------------------------------------------------
    def _program(self, shape: Tuple[int, ...], dtype, per_design: bool):
        key = ("multi", self.iso_key, self.k, per_design, self.sharded,
               self._base.interpret, tuple(int(d) for d in shape),
               jnp.dtype(dtype).name)

        def build():
            def walk(x_int, params):
                self.trace_count += 1    # python side effect: trace-time
                return self._base._execute(x_int, mode="jnp", params=params)

            fn = jax.vmap(walk, in_axes=(0 if per_design else None, 0))
            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P

                from repro.shardmap import shard_map

                fn = shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(P("design") if per_design else P(),
                              P("design")),
                    out_specs=P("design"), check_vma=False)
            return jax.jit(fn)

        prog, hit, _ = self.programs.get_or_build(key, build)
        return prog, hit

    # -- dispatch -----------------------------------------------------------
    def run_int(self, x_int, *, per_design: bool = False) -> EmulationResult:
        """Emulate all K designs in one compiled dispatch; every array in
        the result gains a leading design axis of size K."""
        x_int = jnp.asarray(x_int)
        if per_design and int(x_int.shape[0]) != self.k:
            raise ValueError(
                f"per_design stimulus must lead with the design axis "
                f"(K={self.k}), got shape {tuple(x_int.shape)}")
        prog, hit = self._program(x_int.shape, x_int.dtype, per_design)
        get_metrics().counter("rtl.multi.dispatch").inc()
        trc = get_tracer()
        if trc.enabled:
            with trc.span("rtl.multi.dispatch", k=self.k,
                          shape=str(tuple(x_int.shape)), cached=hit,
                          sharded=self.sharded,
                          design=self._base.graph.name):
                env = prog(x_int, self._params)
        else:
            env = prog(x_int, self._params)
        g = self._base.graph
        fmt = g.edges[g.outputs[0]].fmt
        y = env[g.outputs[0]]
        return EmulationResult(outputs=y,
                               outputs_f=y.astype(jnp.float32) / fmt.scale,
                               trace=env)

    def run(self, x, *, per_design: bool = False) -> EmulationResult:
        g = self._base.graph
        in_fmt = g.edges[g.inputs[0]].fmt
        return self.run_int(jnp.asarray(fxp_to_int(jnp.asarray(x), in_fmt),
                                        jnp.int32),
                            per_design=per_design)

    # -- the sequential cross-check path ------------------------------------
    def run_int_sequential(self, x_int) -> np.ndarray:
        """Per-design dispatches through the shared LRU (one trace total);
        the reference the vmapped axis must match integer-for-integer."""
        return np.stack([np.asarray(em.run_int(x_int).outputs, np.int64)
                         for em in self.emulators])
