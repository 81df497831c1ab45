"""Bit-exact integer emulator of the emitted RTL — the backend's verifier.

Every IR node's integer semantics (DESIGN.md §4) are implemented twice, on
the node's registered :class:`~repro.rtl.oplib.HWTemplate`:

* ``HWTemplate.reference`` — the float oracle, built *only* from
  ``fxp_quantize`` / the hard activations, i.e. the semantics the QAT stage
  trains against (driven here by :func:`reference_apply`);
* ``HWTemplate.execute`` — vectorized int32 arithmetic (what the DSP slices
  compute), with a fused Pallas kernel for the LSTM-cell window (driven
  here by :class:`RTLEmulator`).

The contract is exact equality, integer for integer, not a tolerance:
``emulator.run(x)`` must satisfy ``y_int == round(reference_apply(x) * 2**f)``
for every sample. This holds by construction for the LUTs (tables are
generated from the float reference) and by the round-half-even shift
(``fxp_requant_int``) everywhere else, provided formats pass
``ir.validate_formats`` — the same envelope that keeps int32 from
overflowing keeps the f32 oracle exact.

Execution model (DESIGN.md §7, §15): the emulator is a *staged executor*.
``__init__`` hoists every weight/bias/LUT conversion to a device constant
once (``HWTemplate.prepare``); the graph walk is traced into a single
``jax.jit``-compiled program per ``(iso_key, mode, input shape, dtype)``
(and the deployment call's float-in program, :meth:`RTLEmulator.forward`,
under its own tag), held in a small
:class:`~repro.rtl.program_cache.ProgramLRU` — so repeated
verification/measurement calls never retrace and never re-upload. The
prepared *array* constants (weights, biases, ROM tables) are passed to the
compiled program as traced arguments, not closed over, so designs with
isomorphic graphs (:func:`repro.rtl.ir.iso_key` — same structure, shapes
and Q-formats, different trained values) share one program: hand several
emulators one shared ``ProgramLRU`` and only the first traces. Requant
shifts and kernel specs stay jit-static (they select code paths), which is
exactly why they are part of the isomorphism key. Three execution paths
share the bit-exactness contract:

* ``mode="fused"`` (default) — one :mod:`repro.kernels.lstm_cell_int`
  dispatch per cell per window (weights + both ROMs VMEM-resident);
* ``mode="pallas"`` — one :func:`~repro.rtl.oplib.mac_int_pallas` dispatch
  per timestep (the PR-1 schedule, kept as a cross-check);
* ``mode="jnp"`` — plain-jnp per-step reference.

The emulator itself is op-agnostic: it owns staging, the program cache and
batching, and exposes ``prepared``/``lookup``/``interpret`` as the execution
context templates run against. Per-op math lives in :mod:`repro.rtl.oplib`.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import use_interpret
from repro.obs import get_metrics, get_tracer
from repro.quant.fixedpoint import fxp_to_int
from repro.rtl.ir import Graph, iso_key
# mac primitives live in the op library now; re-exported for compatibility
from repro.rtl.oplib import (_mac_int_jnp, get_template,  # noqa: F401
                             mac_int, mac_int_pallas)
from repro.rtl.program_cache import ProgramLRU

# --------------------------------------------------------------------------- #
# Integer emulator
# --------------------------------------------------------------------------- #


@dataclass
class EmulationResult:
    outputs: jax.Array               # int codes of the design's output edge
    outputs_f: jax.Array             # dequantized
    trace: Dict[str, jax.Array]      # per-edge int codes


class _ExecCtx:
    """The execution context a *traced* graph walk hands the templates.

    Templates run against three attributes of their executor —
    ``prepared(name)``, ``lookup(lut, codes)`` and ``interpret`` — so a
    traced walk can substitute this lightweight view in which the array
    constants are the walk's traced ``params`` argument (per-node dicts of
    int32 operands) while jit-static values (kernel specs) come from the
    owning emulator's prepared store. Isomorphic designs have identical
    statics by construction (specs/shifts derive from shapes and formats,
    which the iso key pins), so a program traced through one emulator's
    context replays correctly for any emulator with the same key.
    """

    __slots__ = ("_params", "_static", "_lut_lo", "interpret")

    def __init__(self, em: "RTLEmulator", params: Dict[str, Dict]):
        self._params = params
        self._static = em._static
        self._lut_lo = {name: n.lo for name, n in em._lut_nodes.items()}
        self.interpret = em.interpret

    def prepared(self, name: str) -> Dict:
        merged = dict(self._static.get(name, ()))
        merged.update(self._params.get(name, ()))
        return merged

    def lookup(self, lut_name: str, codes: jax.Array) -> jax.Array:
        return jnp.take(self._params[lut_name]["table"],
                        codes - self._lut_lo[lut_name])


class RTLEmulator:
    """Runs the emitted design on integer inputs, batch-vectorized.

    A staged executor: all parameters live on device from construction, and
    each distinct ``(input shape, dtype)`` compiles exactly once into the
    program LRU (``trace_count`` observes this; see the retrace test).
    """

    MODES = ("fused", "pallas", "jnp")

    def __init__(self, graph: Graph, use_pallas: bool = True,
                 mode: str = None, max_programs: int = 8,
                 programs: Optional[ProgramLRU] = None):
        self.graph = graph
        self.use_pallas = use_pallas
        self.mode = mode if mode is not None else \
            ("fused" if use_pallas else "jnp")
        if self.mode not in self.MODES:
            raise ValueError(f"mode must be one of {self.MODES}, "
                             f"got {self.mode!r}")
        if max_programs < 1:
            raise ValueError(f"max_programs must be >= 1, got {max_programs}")
        self.interpret = use_interpret()
        self.iso_key = iso_key(graph)
        # ---- stage 0: hoist every host->device conversion, once ----------
        # each template declares its constants (weights, biases, ROM tables,
        # jit-static specs); ndarray values become device int32 residents
        # (the traced operands of the compiled walk), non-arrays stay
        # jit-static.
        self._lut_nodes = graph.act_luts()
        self._prep: Dict[str, Dict] = {}
        self._param_keys: Dict[str, tuple] = {}   # node -> its array fields
        self._static: Dict[str, Dict] = {}        # node -> jit-static fields
        for n in graph.nodes:
            raw = get_template(n.op).prepare(n, graph)
            self._prep[n.name] = {
                k: (jnp.asarray(v, jnp.int32)
                    if isinstance(v, np.ndarray) else v)
                for k, v in raw.items()}
            self._param_keys[n.name] = tuple(
                sorted(k for k, v in raw.items()
                       if isinstance(v, np.ndarray)))
            self._static[n.name] = {
                k: v for k, v in raw.items()
                if not isinstance(v, np.ndarray)}
        # ---- compiled-program cache ---------------------------------------
        # (iso_key, mode, interpret, shape, dtype) -> jitted graph walk.
        # Per-instance by default; pass a shared ProgramLRU to let
        # isomorphic emulators reuse each other's programs (DESIGN.md §15).
        self._programs = programs if programs is not None \
            else ProgramLRU(max_programs)
        self._max_programs = self._programs.max_programs
        self.trace_count = 0             # how many times the walk was traced
        # observability (DESIGN.md §11): cache behavior + dispatch counts
        # are plain int attrs (always on, ~free) mirrored into the process
        # metrics registry; per-dispatch spans only fire when a tracer is
        # enabled (one attribute check on the hot path).
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_evictions = 0
        self.dispatch_counts: Dict[str, int] = {}
        self.seu_flips = 0               # injected bit-flips (resilience)
        # pooled serving calls run_many from worker threads; the program
        # cache locks itself (ProgramLRU); this lock covers the remaining
        # shared mutable state — dispatch counts and the prepared memories.
        self._lock = threading.Lock()

    # -- execution context handed to the templates ---------------------------
    def prepared(self, name: str) -> Dict:
        """The hoisted device constants of node ``name``."""
        return self._prep[name]

    def lookup(self, lut_name: str, codes: jax.Array) -> jax.Array:
        """Shared-ROM gather: table is indexed by ``code - lo``."""
        return jnp.take(self._prep[lut_name]["table"],
                        codes - self._lut_nodes[lut_name].lo)

    def params(self) -> Dict[str, Dict[str, jax.Array]]:
        """The traced-operand pytree: per-node dicts of the prepared array
        constants (weights, biases, ROM tables), keyed by node name. This
        is what every compiled program takes as its second argument — and
        what :class:`~repro.rtl.multi.MultiDesignEmulator` stacks across
        isomorphic candidates."""
        with self._lock:
            return {name: {k: self._prep[name][k] for k in keys}
                    for name, keys in self._param_keys.items() if keys}

    # -- graph walk (traced once per shape, then replayed) -------------------
    def _execute(self, x_int: jax.Array, *, mode: str,
                 params: Optional[Dict[str, Dict]] = None
                 ) -> Dict[str, jax.Array]:
        g = self.graph
        em = self if params is None else _ExecCtx(self, params)
        env: Dict[str, jax.Array] = {g.inputs[0]: x_int}
        for n in g.nodes:
            get_template(n.op).execute(n, env, em, mode)
        return env

    def _cache_key(self, shape, dtype, float_io: bool = False):
        # keyed on everything the traced program depends on besides the
        # array arguments: the design's isomorphism class, execution mode,
        # pallas interpret flag, and the input aval; the float-in program
        # of :meth:`forward` adds a tag, so it never aliases the int walk
        key = (self.iso_key, self.mode, self.interpret,
               tuple(int(d) for d in shape), jnp.dtype(dtype).name)
        return key + ("float_io",) if float_io else key

    def _program(self, shape, dtype, float_io: bool = False):
        """The compiled program for one (shape, dtype), LRU-cached.

        Returns ``(program, cache_hit)`` and keeps the cache observable:
        ``cache_hits``/``cache_misses``/``cache_evictions`` on the instance
        plus the matching ``rtl.emulator.cache_*`` process counters. The
        program signature is ``prog(x, params)`` — array constants are
        traced arguments, so any emulator whose graph shares this
        emulator's iso key can replay the program with its own params.
        The graph walk takes int codes and returns every edge; with
        ``float_io`` the program takes the float input and returns only
        the dequantized output edge (:meth:`forward`).
        """
        mx = get_metrics()

        def build():
            g = self.graph
            in_fmt = g.edges[g.inputs[0]].fmt
            out_fmt = g.edges[g.outputs[0]].fmt

            def walk(x_int, params):
                self.trace_count += 1    # python side effect: trace-time
                return self._execute(x_int, mode=self.mode, params=params)

            def forward(x, params):
                x_int = fxp_to_int(x, in_fmt).astype(jnp.int32)
                y = walk(x_int, params)[g.outputs[0]]
                return y.astype(jnp.float32) / out_fmt.scale

            return jax.jit(forward if float_io else walk)

        prog, hit, evicted = self._programs.get_or_build(
            self._cache_key(shape, dtype, float_io), build)
        if hit:
            self.cache_hits += 1
            mx.counter("rtl.emulator.cache_hit").inc()
        else:
            self.cache_misses += 1
            mx.counter("rtl.emulator.cache_miss").inc()
            if evicted:
                self.cache_evictions += evicted
                mx.counter("rtl.emulator.cache_evict").inc(evicted)
        return prog, hit

    def lower(self, x, params: Optional[Dict[str, Dict]] = None, *,
              float_io: bool = False):
        """Lower the compiled program for input ``x`` — an array or a
        ``jax.ShapeDtypeStruct`` — with this emulator's params (or the
        given pytree of the same structure): the graph walk, or with
        ``float_io`` the float-in program of :meth:`forward`.
        ``.compile().as_text()`` of the result is the program a dispatch
        of that shape runs."""
        prog, _ = self._program(x.shape, x.dtype, float_io)
        return prog.lower(x, self.params() if params is None else params)

    def has_program(self, shape, dtype, *, float_io: bool = False) -> bool:
        """Whether the LRU already holds a compiled program for this
        input (the graph walk, or with ``float_io`` the float-in program
        of :meth:`forward`) — the serving router's affinity probe
        (:mod:`repro.serving.router`). Read-only: does not touch LRU
        order, so probing every pool member is side-effect free. Keys
        include the design's iso key, so with a shared ProgramLRU a
        replica counts as warm for any isomorphic sibling's program."""
        return self._cache_key(shape, dtype, float_io) in self._programs

    def cache_stats(self) -> Dict[str, int]:
        """Program-cache behavior + per-mode dispatch counts, one dict."""
        with self._lock:
            return {"hits": self.cache_hits, "misses": self.cache_misses,
                    "evictions": self.cache_evictions,
                    "retraces": self.trace_count,
                    "dispatches": dict(self.dispatch_counts)}

    # -- SEU model (repro.resilience): the prepared device constants ARE
    # -- the design's BRAM/ROM memories; flipping one bit of one word
    # -- models a single-event upset in the flashed accelerator. ----------
    def memories(self) -> List[tuple]:
        """Addressable (node, key) pairs: every sized array constant a
        fault plan may target — weights, biases, LUT tables."""
        out = []
        for name in sorted(self._prep):
            for key in sorted(self._prep[name]):
                v = self._prep[name][key]
                if hasattr(v, "shape") and np.asarray(v).size > 0:
                    out.append((name, key))
        return out

    def flip_bit(self, node: str, key: str, word: int, bit: int) -> int:
        """Flip ``bit`` of flat ``word`` in memory ``node.key``; returns the
        corrupted word's new int32 value.

        The corrupted array flows into the very next dispatch (prepared
        memories are traced arguments of the compiled programs), but the
        compiled programs are still invalidated — the reflash semantics:
        a bitstream rewrite under a running design drops its loaded
        configuration, and with a shared ProgramLRU this also keeps any
        isomorphic sibling from replaying a program whose trace predates
        the fault plan. Silent by construction: no error is raised,
        subsequent outputs are simply wrong, and only a golden-vector
        canary can tell.
        """
        if not 0 <= bit <= 31:
            raise ValueError(f"bit must be in [0, 31], got {bit}")
        if node not in self._prep or key not in self._prep[node]:
            raise KeyError(f"no prepared memory {node!r}.{key!r}; see "
                           "memories()")
        flat = np.asarray(self._prep[node][key], np.int32).copy().reshape(-1)
        w = int(word) % flat.size
        # XOR through a uint32 view: flipping bit 31 of an int32 would
        # overflow in python-int arithmetic, the reinterpret-cast doesn't.
        u = flat.view(np.uint32)
        u[w] ^= np.uint32(1) << np.uint32(bit)
        shaped = flat.reshape(np.asarray(self._prep[node][key]).shape)
        with self._lock:
            self._prep[node][key] = jnp.asarray(shaped, jnp.int32)
            self._programs.clear()       # force re-trace on corrupted memory
            self.seu_flips += 1
        get_metrics().counter("rtl.emulator.seu_flips").inc()
        return int(flat[w])

    def _result(self, env: Dict[str, jax.Array]) -> EmulationResult:
        out_edge = self.graph.edges[self.graph.outputs[0]]
        y = env[self.graph.outputs[0]]
        with get_tracer().span("rtl.emulator.unpack"):
            y_f = y.astype(jnp.float32) / out_edge.fmt.scale
        return EmulationResult(outputs=y, outputs_f=y_f, trace=env)

    def _count_dispatch(self, mode: str) -> None:
        with self._lock:
            self.dispatch_counts[mode] = self.dispatch_counts.get(mode, 0) + 1
        get_metrics().counter(f"rtl.emulator.dispatch.{mode}").inc()

    def run_int(self, x_int: jax.Array) -> EmulationResult:
        x_int = jnp.asarray(x_int)
        prog = self._program(x_int.shape, x_int.dtype)
        params = self.params()
        self._count_dispatch(self.mode)
        trc = get_tracer()
        if trc.enabled:                      # hoisted guard: skip the attrs
            with trc.span("rtl.emulator.dispatch", mode=self.mode,
                          shape=str(tuple(x_int.shape)), cached=prog[1],
                          design=self.graph.name):
                env = prog[0](x_int, params)
        else:
            env = prog[0](x_int, params)
        return self._result(env)

    def run(self, x: jax.Array) -> EmulationResult:
        in_fmt = self.graph.edges[self.graph.inputs[0]].fmt
        with get_tracer().span("rtl.emulator.quantize"):
            x_int = jnp.asarray(fxp_to_int(x, in_fmt), jnp.int32)
        return self.run_int(x_int)

    def forward(self, x: jax.Array) -> jax.Array:
        """Float input to dequantized output in ONE compiled program.

        The deployment call (:class:`~repro.rtl.backend.RTLExecutable`):
        quantization to the input format, the graph walk and the
        dequantization of the output edge are traced into one program, so
        a call launches one device program. Equals ``run(x).outputs_f``
        element for element; returns no intermediate edge.
        """
        prog, hit = self._program(x.shape, x.dtype, float_io=True)
        params = self.params()
        self._count_dispatch(self.mode)
        get_metrics().counter("rtl.emulator.dispatch.float_io").inc()
        trc = get_tracer()
        if trc.enabled:                      # hoisted guard: skip the attrs
            with trc.span("rtl.emulator.dispatch", mode=self.mode,
                          shape=str(tuple(x.shape)), cached=hit,
                          design=self.graph.name, io="float"):
                return prog(x, params)
        return prog(x, params)

    # -- batched-throughput entry -------------------------------------------
    def run_many(self, xs: Union[jax.Array, Sequence[jax.Array]]
                 ) -> Union[EmulationResult, List[EmulationResult]]:
        """Many independent float windows in ONE compiled dispatch.

        A plain array is treated as an already-stacked batch (same as
        :meth:`run`). A list/tuple of ``(B_i, ...)`` windows is concatenated
        along batch, executed once, and split back into one
        :class:`EmulationResult` per input — rows are independent, so each
        result is bit-identical to running its window alone. Note distinct
        *total* batch sizes compile distinct programs (the LRU absorbs the
        usual handful of shapes).
        """
        if not isinstance(xs, (list, tuple)):
            return self.run(xs)
        xs = [jnp.asarray(x) for x in xs]
        sizes = [int(x.shape[0]) for x in xs]
        res = self.run(jnp.concatenate(xs, axis=0))
        out, off = [], 0
        for s in sizes:
            sl = slice(off, off + s)
            off += s
            out.append(EmulationResult(
                outputs=res.outputs[sl], outputs_f=res.outputs_f[sl],
                trace={k: v[sl] for k, v in res.trace.items()}))
        return out

    # -- legacy per-step schedule (the PR-1 dispatch pattern) ----------------
    def run_int_per_step(self, x_int: jax.Array) -> EmulationResult:
        """Un-jitted eager walk, one MAC dispatch per timestep per cell.

        This is the pre-fusion execution schedule, kept as the benchmark
        baseline and as an extra cross-check path (it still uses the hoisted
        device constants, so any speed difference is pure dispatch/trace
        overhead, not upload traffic).
        """
        mode = "jnp" if self.mode == "jnp" else "pallas"
        self._count_dispatch("per_step")
        with get_tracer().span("rtl.emulator.dispatch", mode="per_step",
                               design=self.graph.name):
            return self._result(self._execute(jnp.asarray(x_int), mode=mode))

    def run_per_step(self, x: jax.Array) -> EmulationResult:
        in_fmt = self.graph.edges[self.graph.inputs[0]].fmt
        return self.run_int_per_step(
            jnp.asarray(fxp_to_int(x, in_fmt), jnp.int32))


def outputs_by_mode(graph: Graph, x_int,
                    modes: Sequence[str] = RTLEmulator.MODES
                    ) -> Dict[str, np.ndarray]:
    """Run the same integer stimulus through each execution path.

    The conformance harness's raw material: one fresh emulator per mode (so
    no program cache can alias the paths), int32 outputs keyed by mode name.
    """
    return {m: np.asarray(RTLEmulator(graph, mode=m).run_int(x_int).outputs,
                          np.int64)
            for m in modes}


# --------------------------------------------------------------------------- #
# Float oracle: identical semantics expressed with fxp_quantize only
# --------------------------------------------------------------------------- #


def reference_apply(graph: Graph, x: jax.Array) -> jax.Array:
    """The fxp_quantize reference the emulator must match bit-for-bit.

    Registry-dispatched like the integer walk: every node's float semantics
    live on its template (``HWTemplate.reference``).
    """
    from repro.rtl.oplib import ref_q

    env = {graph.inputs[0]: ref_q(x, graph.edges[graph.inputs[0]].fmt)}
    luts = graph.act_luts()
    for n in graph.nodes:
        get_template(n.op).reference(n, env, luts)
    return env[graph.outputs[0]]


def assert_bit_exact(graph: Graph, x: jax.Array,
                     use_pallas: bool = True, mode: str = None) -> None:
    """Raises AssertionError on the first integer mismatch (test helper)."""
    res = RTLEmulator(graph, use_pallas=use_pallas, mode=mode).run(x)
    ref = reference_apply(graph, x)
    fmt = graph.edges[graph.outputs[0]].fmt
    ref_int = np.asarray(jnp.round(ref * fmt.scale), np.int64)
    got = np.asarray(res.outputs, np.int64)
    if not np.array_equal(got, ref_int):
        bad = np.argwhere(got != ref_int)
        raise AssertionError(
            f"emulator != fxp reference at {len(bad)} positions; first "
            f"{bad[0].tolist()}: got {got[tuple(bad[0])]} "
            f"ref {ref_int[tuple(bad[0])]} (fmt {fmt})")
