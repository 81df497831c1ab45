"""Compile-only checks of the emulator's Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, so the kernels of the main path are
compiled here for a described ``v5e:2x2`` chip at their shipped widths —
no chip attached, nothing executed. This catches what interpret mode
cannot: constructs the chip's compiler (Mosaic) refuses, such as vector
gathers or int32×int32 matmuls, and blocks that overflow VMEM.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.kernels.lstm_cell_int import CellSpec, lstm_window_int_pallas
from repro.quant.fixedpoint import FxpFormat
from repro.rtl import RTLEmulator
from repro.rtl.oplib import mac_int_pallas
from repro.verify.vectors import canonical_graph

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.counts import KERNELS  # noqa: E402

W8, A8, C16 = FxpFormat(8, 6), FxpFormat(8, 4), FxpFormat(16, 8)
W12, A9 = FxpFormat(12, 9), FxpFormat(9, 4)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler available
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("fmts", [(A8, W8), (A9, W12)],
                         ids=["act8-w8", "act9-w12"])
@pytest.mark.parametrize("batch", [1, 64, 256, 4096])
def test_fused_lstm_kernel_compiles(one_chip, batch, fmts):
    """Both layouts of the fused kernel: rows at batch 1, lanes above."""
    act, w_fmt = fmts
    spec = CellSpec(seq_len=6, d_in=1, hidden=20, act_fmt=act,
                    state_fmt=C16, w_fmt=w_fmt, sig_lo=act.lo,
                    tanh_lo=act.lo)
    depth = 2 ** act.total_bits
    text = _compiled_text(
        lambda x, w, b, s, t: lstm_window_int_pallas(
            x, w, b, s, t, spec=spec, block_b=128, interpret=False),
        _sds(one_chip, (batch, 6, 1)), _sds(one_chip, (21, 80)),
        _sds(one_chip, (80,)), _sds(one_chip, (depth,)),
        _sds(one_chip, (depth,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [(8, 8), (9, 12)], ids=["8x8", "9x12"])
@pytest.mark.parametrize("mkn", [
    (32768, 9, 3),       # conv1d block 1 at B=4096: 8 frames × 3 taps·3 ch
    (16384, 9, 3),       # conv1d block 2
    (4096, 12, 1),       # conv1d linear head
    (4096, 20, 1),       # elastic-lstm linear head
    (4096, 21, 80),      # elastic-lstm gate MAC (per-step "pallas" mode)
    (1, 21, 80),
], ids=lambda m: "x".join(map(str, m)) if isinstance(m, tuple) else m)
def test_mac_kernel_compiles(one_chip, mkn, bits):
    m, k, n = mkn
    x_bits, w_bits = bits
    text = _compiled_text(
        lambda x, w, b: mac_int_pallas(x, w, b, shift=6, lo=-128, hi=127,
                                       x_bits=x_bits, w_bits=w_bits,
                                       interpret=False),
        _sds(one_chip, (m, k)), _sds(one_chip, (k, n)), _sds(one_chip, (n,)))
    assert "tpu_custom_call" in text


#: the two programs an emulator compiles per input shape: the graph walk on
#: int32 codes, and the deployment call's float-in program (``forward``)
IO = {"int": jnp.int32, "float": jnp.float32}


def _lower(emu, one_chip, shape, io):
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          emu.params())
    return emu.lower(_sds(one_chip, shape, IO[io]), params,
                     float_io=io == "float")


@pytest.mark.parametrize("io", sorted(IO))
@pytest.mark.parametrize("batch", [1, 4096])
@pytest.mark.parametrize("arch", ["elastic-lstm", "elastic-conv1d"])
@pytest.mark.parametrize("mode", ["fused", "pallas"])
def test_emulator_walk_compiles(one_chip, monkeypatch, mode, arch, batch,
                                io):
    """The whole program of each design in each kernel mode — every node,
    as one dispatch runs it — compiles with its kernels compiled, not
    interpreted."""
    monkeypatch.setattr(repro.kernels, "INTERPRET", False)
    graph = canonical_graph(arch)[0]
    emu = RTLEmulator(graph, mode=mode)
    assert emu.interpret is False
    shape = (batch,) + tuple(graph.edges[graph.inputs[0]].shape)
    text = _lower(emu, one_chip, shape, io).compile().as_text()
    assert "tpu_custom_call" in text


#: an instruction of compiled HLO text: ``%name = type op(...)``
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? custom-call\(",
                          re.M)


@pytest.mark.parametrize("io", sorted(IO))
@pytest.mark.parametrize("batch", [1, 4096])
def test_fused_walk_kernels_keep_their_trace_names(one_chip, monkeypatch,
                                                   batch, io):
    """A TPU trace names a device operation by its HLO instruction. The
    benchmark's kernel patterns (``bench/counts.KERNELS``), which the
    roofline readers sum device time by, each match a custom call of the
    compiled fused walk — and of the float-in program the deployment call
    runs."""
    monkeypatch.setattr(repro.kernels, "INTERPRET", False)
    graph = canonical_graph("elastic-lstm")[0]
    emu = RTLEmulator(graph, mode="fused")
    text = _lower(emu, one_chip, (batch, 6, 1), io).compile().as_text()
    calls = _INSTRUCTION.findall(text)
    assert calls, "no custom call in the compiled walk"
    for kernel, pattern in KERNELS.items():
        assert [c for c in calls if re.search(pattern, c)], (kernel, calls)


def test_multi_design_walk_compiles(one_chip):
    """DSE: 32 elastic-lstm candidates vmapped into one jnp program."""
    from repro.rtl import MultiDesignEmulator

    graphs = [canonical_graph("elastic-lstm", seed=s)[0] for s in range(32)]
    multi = MultiDesignEmulator(graphs)
    prog, _ = multi._program((16, 6, 1), jnp.int32, False)
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          multi._params)
    prog.lower(_sds(one_chip, (16, 6, 1)), params).compile()
