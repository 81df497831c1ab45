"""The deployment call (``RTLExecutable.__call__``) as one compiled program.

``RTLEmulator.forward`` traces quantization of the float input, the graph
walk and dequantization of the output edge into one ``jax.jit`` program.
These tests hold it to the multi-program path it replaced
(``run(x).outputs_f``) and to the fxp reference, element for element, and
pin its program-cache contract: one trace per ``(shape, dtype)``, one
compiled program per fresh shape, invalidation by ``flip_bit``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.energy.hw import XC7S15
from repro.rtl.backend import RTLExecutable
from repro.rtl.emulator import reference_apply
from repro.verify.vectors import canonical_graph

ARCHS = ("elastic-lstm", "elastic-conv1d")

#: the monitoring event JAX records once per program it lowers
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _dep(arch: str, mode: str = "fused") -> RTLExecutable:
    return RTLExecutable(graph=canonical_graph(arch)[0], artifacts={},
                         hw=XC7S15, emulator_mode=mode)


def _stimulus(graph, batch: int, seed: int = 0) -> np.ndarray:
    """Float windows mixing normal draws, round-half-even ties of the input
    format (``k / 2**(frac+1)``, k odd) and values past both ends of its
    range, so quantization rounds ties and saturates."""
    fmt = graph.edges[graph.inputs[0]].fmt
    shape = (batch,) + tuple(graph.edges[graph.inputs[0]].shape)
    rng = np.random.default_rng(seed)
    half = 2.0 ** -(fmt.frac_bits + 1)
    ties = (2 * rng.integers(fmt.lo, fmt.hi, size=shape) + 1) * half
    past = np.where(rng.random(shape) < 0.5, -1.0, 1.0) * (
        (fmt.hi + 1) / fmt.scale + rng.random(shape) * 4.0)
    normal = rng.normal(size=shape) * 2.0
    pick = rng.integers(0, 3, size=shape)
    x = np.choose(pick, [normal, ties, past]).astype(np.float32)
    if batch > 1:                    # one row at each end of the range
        x[0] = (fmt.hi + 1) / fmt.scale + 1.0
        x[1] = (fmt.lo - 1) / fmt.scale - 1.0
    return x


class _Lowerings:
    """Counts the programs JAX lowers while it is open."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, *args, **kw) -> None:
        if event == LOWERED:
            self.n += 1

    def __enter__(self) -> "_Lowerings":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> bool:
        jax.monitoring.unregister_event_duration_listener(self)
        return False


@pytest.mark.parametrize("batch", [1, 3, 64])
@pytest.mark.parametrize("mode", ["fused", "pallas", "jnp"])
@pytest.mark.parametrize("arch", ARCHS)
def test_call_equals_run_and_reference(arch, mode, batch):
    dep = _dep(arch, mode)
    g = dep.graph
    x = _stimulus(g, batch, seed=batch)
    got = np.asarray(dep(x))
    assert got.dtype == np.float32
    want = np.asarray(dep.emulator.run(x).outputs_f)
    np.testing.assert_array_equal(got, want)
    fmt = g.edges[g.outputs[0]].fmt
    ref = np.asarray(jnp.round(reference_apply(g, x) * fmt.scale), np.int64)
    np.testing.assert_array_equal(
        np.asarray(np.round(got * fmt.scale), np.int64), ref)


def test_stimulus_rounds_ties_and_saturates():
    g = canonical_graph("elastic-lstm")[0]
    fmt = g.edges[g.inputs[0]].fmt
    x = _stimulus(g, 64) * fmt.scale
    assert np.any(x >= fmt.hi + 1) and np.any(x <= fmt.lo - 1)
    assert np.any((np.abs(x) % 1 == 0.5) & (np.abs(x) < fmt.hi))


def test_repeated_calls_trace_once():
    dep = _dep("elastic-lstm")
    x = _stimulus(dep.graph, 4)
    first = np.asarray(dep(x))
    for _ in range(4):
        np.testing.assert_array_equal(np.asarray(dep(x)), first)
    st = dep.emulator.cache_stats()
    assert dep.emulator.trace_count == 1
    assert (st["misses"], st["hits"]) == (1, 4)
    assert dep.holds_program(x.shape, x.dtype)
    assert not dep.emulator.has_program(x.shape, jnp.int32)   # no int walk


def test_flip_bit_retraces_the_call():
    dep = _dep("elastic-lstm")
    x = _stimulus(dep.graph, 8)
    before = np.asarray(dep(x))
    assert dep.emulator.trace_count == 1
    dep.emulator.flip_bit("lstm_cell_l0", "w", 0, 7)   # the int8 sign bit
    assert not dep.holds_program(x.shape, x.dtype)
    after = np.asarray(dep(x))
    assert dep.emulator.trace_count == 2
    assert not np.array_equal(after, before)
    np.testing.assert_array_equal(
        after, np.asarray(dep.emulator.run(x).outputs_f))


@pytest.mark.parametrize("arch", ARCHS)
def test_fresh_shape_compiles_one_program(arch):
    dep = _dep(arch)
    x = _stimulus(dep.graph, 11)
    with _Lowerings() as lowered:
        jax.block_until_ready(dep(x))
    assert lowered.n == 1
    with _Lowerings() as lowered:
        jax.block_until_ready(dep(x))
    assert lowered.n == 0


def test_float_io_counter_counts_each_call():
    dep = _dep("elastic-lstm")
    x = _stimulus(dep.graph, 2)
    with obs.capture("call") as cap:
        for _ in range(3):
            dep(x)
    mx = cap.trace.metrics
    assert mx["rtl.emulator.dispatch.float_io"]["value"] == 3
    assert mx["rtl.emulator.dispatch.fused"]["value"] == 3
    ds = obs.find_spans(cap.trace.spans, "rtl.emulator.dispatch")
    assert [d.attrs["io"] for d in ds] == ["float"] * 3
    assert [d.attrs["cached"] for d in ds] == [False, True, True]
    for call in obs.find_spans(cap.trace.spans, "rtl.call"):
        inside = obs.children_of(cap.trace.spans, call)
        assert [s.name for s in inside] == ["rtl.emulator.dispatch"]
