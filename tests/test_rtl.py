"""RTL backend: codegen artifacts, bit-exact emulation, resource model,
and the full Workflow round-trip with backend="rtl"."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                       # image lacks hypothesis: use shim
    from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core.creator import Creator
from repro.core.types import SHAPES_CONV1D, SHAPES_LSTM
from repro.energy.hw import XC7S15
from repro.model.layers import init_params
from repro.model.lstm import lstm_flops, lstm_schema
from repro.quant.fixedpoint import FxpFormat, fxp_requant_int, fxp_quantize
from repro.rtl import (ActLUTNode, Conv1dNode, ElementwiseNode, Graph, Edge,
                       LinearNode, LSTMCellNode, RTLEmulator, RTLOptions,
                       assert_bit_exact, emit_graph, estimate, lower_conv_stack,
                       lower_linear_stack, lower_model, node_cost, synthesize,
                       validate_formats)


def _conv_graph(**fmts):
    from repro.model.conv1d import conv1d_schema

    cfg = get_config("elastic-conv1d")
    params = init_params(conv1d_schema(cfg), jax.random.PRNGKey(0))
    return lower_model(cfg, params, **fmts), cfg, params


def _lstm_graph(n_layers: int = 1, **fmts):
    cfg = get_config("elastic-lstm")
    if n_layers != 1:
        cfg = cfg.with_(lstm=cfg.lstm.__class__(
            hidden=cfg.lstm.hidden, n_layers=n_layers, in_features=1,
            out_features=1, seq_len=6))
    params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
    return lower_model(cfg, params, **fmts)


# --------------------------------------------------------------------------- #
# Codegen artifacts
# --------------------------------------------------------------------------- #


def test_translate_rtl_emits_artifacts():
    """The acceptance path: translate(target="rtl") -> ≥3 template files."""
    cr = Creator(hw=XC7S15)
    st_ = cr.build(get_config("elastic-lstm"), SHAPES_LSTM["infer_1"])
    syn, exe = cr.translate(st_, target="rtl")
    assert syn.backend == "rtl"
    assert syn.n_artifacts >= 3
    assert len(exe.artifacts) >= 3
    vhds = [n for n in exe.artifacts if n.endswith(".vhd")]
    mems = [n for n in exe.artifacts if n.endswith(".mem")]
    assert len(vhds) >= 3 and len(mems) >= 3
    assert "manifest.json" in exe.artifacts
    man = json.loads(exe.artifacts["manifest.json"])
    assert man["total_macs"] > 0
    assert "Q8.4" in str(man["edges"])
    # entity text mentions the ROM files it loads
    cell_vhd = exe.artifacts["lstm_cell_l0.vhd"]
    assert "lstm_cell_l0_w.mem" in cell_vhd
    assert "entity lstm_cell_l0" in cell_vhd


def test_artifact_hex_round_trips():
    """BRAM init words decode back to the fxp_to_int weight codes."""
    g = _lstm_graph()
    arts = emit_graph(g)
    node = g.node("lstm_cell_l0")
    lines = arts["lstm_cell_l0_w.mem"].splitlines()
    codes = node.weight_int().reshape(-1)
    assert len(lines) == codes.size
    bits = node.w_fmt.total_bits
    for line, code in zip(lines[:64], codes[:64]):
        v = int(line, 16)
        if v >= 1 << (bits - 1):
            v -= 1 << bits
        assert v == int(code)


def test_lut_table_matches_fxp_reference():
    """ROM contents equal fxp_to_int(act(code/scale)) for every code."""
    from repro.quant.qat import hard_sigmoid

    lut = ActLUTNode(name="s", op="act_lut", inputs=[], outputs=[],
                     kind="hard_sigmoid", in_fmt=FxpFormat(8, 4),
                     out_fmt=FxpFormat(8, 4))
    t = lut.table()
    assert t.shape == (256,)
    codes = np.arange(-128, 128)
    ref = np.asarray(jnp.round(jnp.clip(
        fxp_quantize(hard_sigmoid(codes / 16.0), FxpFormat(8, 4)) * 16.0,
        -128, 127)), np.int32)
    assert np.array_equal(t, ref)


# --------------------------------------------------------------------------- #
# Bit-exactness: emulator vs fxp_quantize reference
# --------------------------------------------------------------------------- #


def test_emulator_bit_exact_default_formats():
    g = _lstm_graph()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 6, 1)) * 2.0
    assert_bit_exact(g, x, use_pallas=True)
    assert_bit_exact(g, x, use_pallas=False)


def test_emulator_pallas_and_jnp_agree():
    g = _lstm_graph()
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 6, 1))
    a = RTLEmulator(g, use_pallas=True).run(x).outputs
    b = RTLEmulator(g, use_pallas=False).run(x).outputs
    assert np.array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(4, 8), st.integers(4, 8), st.integers(10, 16),
       st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_emulator_bit_exact_random_formats(w_total, a_total, s_total, seed):
    """Property: exact integer equality over random Q-formats + inputs."""
    w_fmt = FxpFormat(w_total, max(1, w_total - 2))
    a_fmt = FxpFormat(a_total, max(1, a_total - 3))
    s_fmt = FxpFormat(s_total, max(a_fmt.frac_bits, s_total - 8))
    g = _lstm_graph(w_fmt=w_fmt, act_fmt=a_fmt, state_fmt=s_fmt)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 6, 1)) * 3.0
    assert_bit_exact(g, x, use_pallas=False)


def test_netlist_references_resolve():
    """Every `entity work.X` the top level instantiates must be emitted."""
    import re

    k = jax.random.PRNGKey(0)
    ws = [np.asarray(jax.random.normal(k, (6, 6))) * 0.4] * 2
    bs = [np.zeros(6, np.float32)] * 2
    for g in (_lstm_graph(),
              lower_linear_stack("mlp_ref", list(zip(ws, bs)))):
        arts = emit_graph(g)
        top = arts[f"{g.name}.vhd"]
        refs = set(re.findall(r"entity work\.(\w+)", top))
        ents = {m for a in arts.values()
                for m in re.findall(r"^entity (\w+) is", a, re.M)}
        assert refs <= ents, (g.name, refs - ents)


def test_mlp_stack_bit_exact():
    k = jax.random.PRNGKey(3)
    ws = [np.asarray(jax.random.normal(jax.random.PRNGKey(i), s)) * 0.5
          for i, s in enumerate([(8, 16), (16, 4)])]
    bs = [np.full(16, 0.1, np.float32), np.zeros(4, np.float32)]
    g = lower_linear_stack("mlp_demo", list(zip(ws, bs)))
    x = jax.random.normal(k, (5, 8))
    assert_bit_exact(g, x, use_pallas=True)
    assert_bit_exact(g, x, use_pallas=False)
    arts = emit_graph(g)
    assert "mlp_demo.vhd" in arts and "linear_0_w.mem" in arts


def test_elementwise_node_bit_exact():
    a_fmt = FxpFormat(8, 4)
    out_fmt = FxpFormat(8, 5)
    g = Graph(name="ew")
    g.edges["x"] = Edge("x", (6,), a_fmt)
    g.edges["x2"] = Edge("x2", (6,), a_fmt)
    g.inputs = ["x"]
    g.add(ElementwiseNode(name="sq", op="elementwise", inputs=["x", "x"],
                          outputs=["y"], kind="mul", a_fmt=a_fmt,
                          b_fmt=a_fmt, out_fmt=out_fmt),
          Edge("y", (6,), out_fmt))
    g.outputs = ["y"]
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 6))
    assert_bit_exact(g, x, use_pallas=False)


def test_requant_int_matches_fxp_quantize():
    """The integer rounding shift is fxp_quantize, code-for-code."""
    rng = np.random.default_rng(0)
    for from_frac, fmt in [(8, FxpFormat(8, 4)), (10, FxpFormat(8, 6)),
                           (4, FxpFormat(8, 6)), (6, FxpFormat(16, 6))]:
        v = jnp.asarray(rng.integers(-(1 << 15), 1 << 15, 256), jnp.int32)
        got = fxp_requant_int(v, from_frac, fmt)
        ref = fxp_quantize(v.astype(jnp.float32) / (1 << from_frac), fmt)
        assert np.array_equal(np.asarray(got, np.int64),
                              np.asarray(jnp.round(ref * fmt.scale),
                                         np.int64)), (from_frac, str(fmt))


def test_validate_formats_rejects_overflow_risk():
    with pytest.raises(ValueError):
        validate_formats(act=FxpFormat(16, 8), weight=FxpFormat(16, 8),
                         state=FxpFormat(16, 8), fan_in=1024)
    with pytest.raises(ValueError):
        # state narrower than activations: alignment shift would be lossy
        validate_formats(act=FxpFormat(8, 6), weight=FxpFormat(8, 6),
                         state=FxpFormat(16, 4), fan_in=8)


# --------------------------------------------------------------------------- #
# Staged executor: execution paths × batch × depth, program cache, run_many
# --------------------------------------------------------------------------- #


#: Q-formats at the edges of the §4 envelope (DESIGN.md §4): 12-bit
#: weights (two int8 limbs in the kernels' MXU matmuls) and 9-bit
#: activations (512-word ROMs), each within the int32/f32 exactness bound
#: at two stacked cells
EDGE_FMTS = {
    "default": {},
    "w12": dict(w_fmt=FxpFormat(12, 9), act_fmt=FxpFormat(8, 4),
                state_fmt=FxpFormat(16, 8)),
    "act9": dict(w_fmt=FxpFormat(8, 6), act_fmt=FxpFormat(9, 4),
                 state_fmt=FxpFormat(16, 8)),
}


@pytest.mark.parametrize("fmts", sorted(EDGE_FMTS))
@pytest.mark.parametrize("batch,mode", [
    (batch, mode) for mode in ("fused", "pallas", "jnp") for batch in (1, 7, 64)
] + [(130, "fused")])       # the fused kernel's lanes layout, padded tile
@pytest.mark.parametrize("n_layers", [1, 2])
def test_emulator_bit_exact_all_paths(mode, batch, n_layers, fmts):
    """Every execution path × batch size × stacked depth × envelope-edge
    format, exact equality."""
    g = _lstm_graph(n_layers=n_layers, **EDGE_FMTS[fmts])
    x = jax.random.normal(jax.random.PRNGKey(10 * batch + n_layers),
                          (batch, 6, 1)) * 2.0
    assert_bit_exact(g, x, mode=mode)


def test_compiled_program_cache_hits():
    """Repeated same-shape runs replay one compiled program (no retrace)."""
    g = _lstm_graph()
    em = RTLEmulator(g)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 1))
    first = em.run(x)
    assert em.trace_count == 1
    for _ in range(5):
        rep = em.run(x)
    assert em.trace_count == 1, "same (shape, dtype) must not retrace"
    assert np.array_equal(np.asarray(rep.outputs), np.asarray(first.outputs))
    em.run(x[:2])
    assert em.trace_count == 2              # new batch size: one more trace
    em.run(x)
    assert em.trace_count == 2              # original program still cached


def test_program_cache_lru_evicts():
    g = _lstm_graph()
    em = RTLEmulator(g, max_programs=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 6, 1))
    em.run(x[:1]), em.run(x[:2]), em.run(x[:3])     # 3 shapes, capacity 2
    assert em.trace_count == 3
    em.run(x[:3]), em.run(x[:2])                    # both still resident
    assert em.trace_count == 3
    em.run(x[:1])                                   # was evicted: retrace
    assert em.trace_count == 4


def test_run_many_single_dispatch_matches_individual():
    g = _lstm_graph()
    em = RTLEmulator(g)
    xs = [jax.random.normal(jax.random.PRNGKey(i), (b, 6, 1)) * 2.0
          for i, b in enumerate((1, 3, 4))]
    outs = em.run_many(xs)
    assert em.trace_count == 1, "list input must execute as ONE dispatch"
    assert [o.outputs.shape[0] for o in outs] == [1, 3, 4]
    for x, r in zip(xs, outs):
        solo = RTLEmulator(g).run(x)
        assert np.array_equal(np.asarray(r.outputs),
                              np.asarray(solo.outputs))
        assert np.array_equal(np.asarray(r.trace["h0"]),
                              np.asarray(solo.trace["h0"]))


def test_per_step_legacy_path_matches_fused():
    """The un-jitted per-step schedule (benchmark baseline) stays exact."""
    g = _lstm_graph()
    em = RTLEmulator(g)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 6, 1))
    a = em.run(x)
    b = em.run_per_step(x)
    assert np.array_equal(np.asarray(a.outputs), np.asarray(b.outputs))


def test_executable_run_many_and_mode_plumbing():
    cr = Creator(hw=XC7S15)
    st_ = cr.build(get_config("elastic-lstm"), SHAPES_LSTM["infer_1"])
    _, exe = cr.translate(st_, target="rtl",
                          options=RTLOptions(emulator_mode="jnp"))
    assert exe.emulator.mode == "jnp"
    _, exe_f = cr.translate(st_, target="rtl")
    assert exe_f.emulator.mode == "fused"
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 1))
    outs = exe_f.run_many([x, x])
    assert len(outs) == 2
    assert np.array_equal(np.asarray(outs[0].outputs),
                          np.asarray(outs[1].outputs))


# --------------------------------------------------------------------------- #
# Resource / cycle model
# --------------------------------------------------------------------------- #


def test_resource_model_monotone_in_hidden():
    cfg = get_config("elastic-lstm")
    prev = None
    for hidden in (8, 16, 32):
        c2 = cfg.with_(lstm=cfg.lstm.__class__(
            hidden=hidden, n_layers=1, in_features=1, out_features=1,
            seq_len=6))
        params = init_params(lstm_schema(c2), jax.random.PRNGKey(0))
        rr = estimate(lower_model(c2, params))
        cur = (rr.cycles, rr.dsp, rr.bram36, rr.lut)
        if prev is not None:
            assert all(a >= b for a, b in zip(cur, prev)), (cur, prev)
        assert rr.cycles > 0 and rr.duty > 0.5
        prev = cur


def test_resource_model_monotone_in_bits():
    a5 = FxpFormat(5, 3)                  # keeps Q16 weights in the envelope
    g8 = _lstm_graph(w_fmt=FxpFormat(8, 6), act_fmt=a5)
    g16 = _lstm_graph(w_fmt=FxpFormat(16, 12), act_fmt=a5)
    r8, r16 = estimate(g8), estimate(g16)
    assert r16.bram36 >= r8.bram36
    assert r16.lut >= r8.lut


def test_synthesis_report_tracks_table1():
    """Generated-artifact estimate must sit in the paper's ~10% band."""
    g = _lstm_graph()
    rep = synthesize(g, hw=XC7S15,
                     model_flops=float(lstm_flops(get_config("elastic-lstm"))))
    assert rep.fits
    lat_err = (rep.est_latency_s * 1e6 - 57.25) / 57.25
    eff_err = (rep.est_gop_per_j - 5.33) / 5.33
    assert abs(lat_err) < 0.12, rep.est_latency_s
    assert abs(eff_err) < 0.12, rep.est_gop_per_j
    assert rep.resources["dsp"] <= 20 and rep.resources["bram36"] <= 10


# --------------------------------------------------------------------------- #
# Workflow round-trip on the generated accelerator
# --------------------------------------------------------------------------- #


def test_workflow_roundtrip_target_rtl():
    from repro.core.report import DesignReport
    from repro.core.workflow import Requirement, Workflow

    cfg = get_config("elastic-lstm")

    def train_fn(knobs):
        params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
        rep = DesignReport(model="elastic-lstm", train_loss=0.0,
                           eval_loss=0.0, weight_fmt=str(
                               FxpFormat(knobs["bits"], knobs["bits"] - 2)))
        return params, rep, None

    def step_builder(knobs, params):
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 1))
        return None, (params, x), float(lstm_flops(cfg))

    def stepper_builder(knobs):
        return Creator(hw=XC7S15).build(cfg, SHAPES_LSTM["infer_1"])

    def options_from_knobs(knobs):
        b = knobs["bits"]
        return RTLOptions(w_fmt=FxpFormat(b, b - 2),
                          act_fmt=FxpFormat(b, b - 4))

    wf = Workflow(creator=Creator(hw=XC7S15), train_fn=train_fn,
                  step_builder=step_builder, stepper_builder=stepper_builder,
                  target="rtl", options_from_knobs=options_from_knobs)
    hist = wf.run(Requirement(max_latency_s=1.0), lambda h: None,
                  {"bits": 8}, max_iters=2)
    assert len(hist) == 1 and hist[0].satisfied
    rec = hist[0]
    assert rec.synthesis.backend == "rtl"
    assert rec.synthesis.n_artifacts >= 3
    assert rec.measurement.platform.startswith("rtl-emulator")
    assert rec.measurement.target == "rtl"
    assert rec.measurement.n_runs >= 1
    assert rec.measurement.latency_s > 0
    assert abs(rec.est_vs_meas["latency_rel_err"]) < 1e-9
    assert rec.measurement.gop_per_j > 1.0


def test_rtl_executable_save(tmp_path):
    cr = Creator(hw=XC7S15)
    st_ = cr.build(get_config("elastic-lstm"), SHAPES_LSTM["infer_1"])
    _, exe = cr.translate(st_, target="rtl")
    exe.save(str(tmp_path))
    files = {p.name for p in tmp_path.iterdir()}
    # artifacts + the static verifier's report (DESIGN.md §13)
    assert files == set(exe.artifacts) | {"analysis.json"}
    assert exe.analysis is not None and exe.analysis.passed
    assert exe.cycles > 0


# --------------------------------------------------------------------------- #
# IR construction safety: array fields are required, shape-checked at build
# --------------------------------------------------------------------------- #


def test_nodes_reject_missing_arrays():
    with pytest.raises(TypeError, match="weight.*required"):
        LinearNode(name="l", op="linear", inputs=["x"], outputs=["y"],
                   weight=None, bias=np.zeros(4, np.float32))
    with pytest.raises(TypeError, match="bias.*required"):
        LSTMCellNode(name="c", op="lstm_cell", inputs=["x"], outputs=["h"],
                     weight=np.zeros((21, 80), np.float32), bias=None)
    with pytest.raises(TypeError):
        LinearNode(name="l", op="linear", inputs=["x"], outputs=["y"])  # noqa


def test_nodes_reject_shape_mismatch():
    with pytest.raises(ValueError, match="bias shape"):
        LinearNode(name="l", op="linear", inputs=["x"], outputs=["y"],
                   weight=np.zeros((4, 8), np.float32),
                   bias=np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="weight shape"):
        LSTMCellNode(name="c", op="lstm_cell", inputs=["x"], outputs=["h"],
                     weight=np.zeros((10, 80), np.float32),
                     bias=np.zeros(80, np.float32), d_in=1, hidden=20)
    with pytest.raises(ValueError, match="out_len"):
        Conv1dNode(name="cv", op="conv1d", inputs=["x"], outputs=["y"],
                   weight=np.zeros((5, 2), np.float32),
                   bias=np.zeros(2, np.float32), kernel=5, stride=1,
                   seq_len=4, channels=2)


# --------------------------------------------------------------------------- #
# Golden artifacts: emission is deterministic and pinned to a snapshot
# --------------------------------------------------------------------------- #


def test_emit_graph_deterministic():
    """Emitting the same lowered graph twice yields byte-identical dicts."""
    g = _lstm_graph()
    a1, a2 = emit_graph(g), emit_graph(g)
    assert sorted(a1) == sorted(a2)
    for name in a1:
        assert a1[name] == a2[name], f"{name} differs between emissions"
    gc, _, _ = _conv_graph()
    b1, b2 = emit_graph(gc), emit_graph(gc)
    assert b1 == b2


def test_elastic_lstm_manifest_matches_golden():
    """The reference design's manifest is pinned: codegen drift (formats,
    cycle model, node set) must be an intentional, reviewed change. The
    manifest depends only on the config (shapes/Q-formats/cost model), not
    on trained weights, so the snapshot is platform-stable."""
    import os

    g = _lstm_graph()
    got = emit_graph(g)["manifest.json"]
    golden = os.path.join(os.path.dirname(__file__), "golden",
                          "elastic_lstm_manifest.json")
    with open(golden) as f:
        want = f.read()
    assert got == want, (
        "manifest.json drifted from tests/golden/elastic_lstm_manifest.json"
        " — if the change is intentional, regenerate the snapshot")


# --------------------------------------------------------------------------- #
# Hardware-template (op) registry
# --------------------------------------------------------------------------- #


def test_template_registry_lists_and_resolves():
    from repro.rtl import get_template, list_templates

    kinds = list_templates()
    for kind in ("linear", "lstm_cell", "conv1d", "act_lut", "act_apply",
                 "elementwise"):
        assert kind in kinds
        assert get_template(kind).kind == kind


def test_template_registry_unknown_kind_lists_registered():
    from repro.rtl import get_template

    with pytest.raises(ValueError) as ei:
        get_template("systolic_gemm")
    msg = str(ei.value)
    assert "systolic_gemm" in msg and "lstm_cell" in msg and "conv1d" in msg


def test_template_registry_double_registration_policy():
    from repro.rtl import get_template, register_template
    from repro.rtl.oplib import HWTemplate

    class Dup(HWTemplate):
        kind = "linear"

    with pytest.raises(ValueError, match="already registered"):
        register_template(Dup())
    orig = get_template("linear")
    register_template(Dup(), overwrite=True)      # explicit swap is allowed
    try:
        assert isinstance(get_template("linear"), Dup)
    finally:
        register_template(orig, overwrite=True)


def test_unknown_family_error_lists_lowerable():
    from repro.rtl.oplib import lowering_for

    with pytest.raises(NotImplementedError) as ei:
        lowering_for("dense")
    assert "conv1d" in str(ei.value) and "lstm" in str(ei.value)


def test_custom_template_round_trips():
    """A minimal in-test template: lower -> emit -> emulate -> cost, without
    touching any repro internals — the plugin contract of DESIGN.md §9."""
    from dataclasses import dataclass as dc

    from repro.rtl import (HWTemplate, get_template, register_template,
                           unregister_template)
    from repro.rtl.ir import Node
    from repro.rtl.resources import NodeCost

    @dc
    class NegNode(Node):
        fmt: FxpFormat = FxpFormat(8, 4)

    class NegTemplate(HWTemplate):
        """y = -x: one adder, no memories."""

        kind = "negate"
        node_cls = NegNode

        def execute(self, n, env, em, mode):
            env[n.outputs[0]] = jnp.clip(-env[n.inputs[0]],
                                         n.fmt.lo, n.fmt.hi)

        def reference(self, n, env, luts):
            env[n.outputs[0]] = fxp_quantize(-env[n.inputs[0]], n.fmt)

        def emit(self, graph, n, out):
            out[f"{n.name}.vhd"] = (f"entity {n.name} is\n"
                                    f"-- y <= -x\nend entity {n.name};\n")

        def cost(self, n):
            return NodeCost(n.name, n.op, cycles=1, active_cycles=1,
                            dsp=0, bram36=0, lut=8)

    register_template(NegTemplate())
    try:
        fmt = FxpFormat(8, 4)
        g = Graph(name="neg_demo")
        g.edges["x"] = Edge("x", (6,), fmt)
        g.inputs = ["x"]
        g.add(NegNode(name="neg0", op="negate", inputs=["x"],
                      outputs=["y"], fmt=fmt), Edge("y", (6,), fmt))
        g.outputs = ["y"]
        x = jax.random.normal(jax.random.PRNGKey(7), (3, 6))
        assert_bit_exact(g, x, mode="jnp")            # emulate == reference
        arts = emit_graph(g)                          # emit walks the plugin
        assert "neg0.vhd" in arts and "neg_demo.vhd" in arts
        assert "i_neg0 : entity work.neg0" in arts["neg_demo.vhd"]
        rr = estimate(g)                              # cost walks the plugin
        assert rr.cycles == 1 and rr.lut == 8
        assert get_template("negate").kind == "negate"
    finally:
        unregister_template("negate")


# --------------------------------------------------------------------------- #
# conv1d template: bit-exact, deployable end-to-end, costed
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("fmts", ["default", "w12-act9"])
@pytest.mark.parametrize("mode", ["fused", "pallas", "jnp"])
@pytest.mark.parametrize("batch", [1, 5])
def test_conv1d_bit_exact_all_paths(mode, batch, fmts):
    edge = dict(w_fmt=FxpFormat(12, 9), act_fmt=FxpFormat(9, 4))
    g, cfg, _ = _conv_graph(**(edge if fmts == "w12-act9" else {}))
    c = cfg.conv1d
    x = jax.random.normal(jax.random.PRNGKey(3 * batch),
                          (batch, c.seq_len, c.channels)) * 2.0
    assert_bit_exact(g, x, mode=mode)


def test_conv1d_stack_strides_and_kernels_bit_exact():
    k = jax.random.PRNGKey(11)
    for kernel, stride, seq in [(2, 1, 8), (3, 2, 16), (4, 3, 15)]:
        C = 2
        t1 = (seq - kernel) // stride + 1
        t2 = (t1 - kernel) // stride + 1
        if t2 < 1:
            continue
        blocks = [(np.asarray(jax.random.normal(
            jax.random.PRNGKey(kernel * 10 + stride + i),
            (kernel, C))) * 0.5, np.full(C, 0.05, np.float32))
            for i in range(2)]
        head = (np.asarray(jax.random.normal(k, (t2 * C, 2))) * 0.4,
                np.zeros(2, np.float32))
        g = lower_conv_stack(f"c{kernel}{stride}", blocks, head,
                             seq_len=seq, stride=stride)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, seq, C))
        assert_bit_exact(g, x, mode="jnp")
        assert_bit_exact(g, x, mode="fused")


def test_conv_stack_envelope_uses_widest_kernel():
    """A later block's bigger kernel must count toward the §4 fan-in."""
    C = 2
    blocks = [(np.zeros((2, C), np.float32), np.zeros(C, np.float32)),
              (np.zeros((200, C), np.float32), np.zeros(C, np.float32))]
    head = (np.zeros((1 * C, 1), np.float32), np.zeros(1, np.float32))
    with pytest.raises(ValueError, match="envelope"):
        lower_conv_stack("wide", blocks, head, seq_len=256, stride=1,
                         w_fmt=FxpFormat(12, 8), act_fmt=FxpFormat(9, 4))


def test_conv1d_artifacts_and_netlist():
    import re

    g, _, _ = _conv_graph()
    arts = emit_graph(g)
    assert "conv1d_0.vhd" in arts and "conv1d_0_w.mem" in arts
    vhd = arts["conv1d_0.vhd"]
    assert "entity conv1d_0" in vhd
    assert "conv1d_0_w.mem" in vhd and 'rom_style' in vhd   # BRAM taps
    assert "STRIDE" in vhd and "KERNEL" in vhd
    # tap .mem round-trips to the fxp_to_int codes
    node = g.node("conv1d_0")
    lines = arts["conv1d_0_w.mem"].splitlines()
    codes = node.weight_int().reshape(-1)
    assert len(lines) == codes.size
    # every instantiated entity resolves
    top = arts[f"{g.name}.vhd"]
    refs = set(re.findall(r"entity work\.(\w+)", top))
    ents = {m for a in arts.values()
            for m in re.findall(r"^entity (\w+) is", a, re.M)}
    assert refs <= ents, refs - ents


def test_conv1d_cost_model():
    g, _, _ = _conv_graph()
    n = g.node("conv1d_0")
    c = node_cost(n)
    assert c.dsp >= 1 and c.bram36 >= 1
    assert c.cycles > c.active_cycles > 0
    assert c.active_cycles == n.macs() + n.out_len * n.channels
    rr = estimate(g)
    assert rr.fits() and rr.cycles > 0
    syn = synthesize(g, hw=XC7S15)
    assert syn.fits and syn.est_latency_s < 57.25e-6   # lighter than Table I


def test_conv1d_end_to_end_deployment(tmp_path):
    """Creator.translate(target="rtl") -> Deployment.measure -> .save."""
    from repro.model.conv1d import conv1d_flops

    cfg = get_config("elastic-conv1d")
    cr = Creator(hw=XC7S15)
    st_ = cr.build(cfg, SHAPES_CONV1D["infer_1"])
    syn, dep = cr.translate(st_, target="rtl")
    assert syn.backend == "rtl" and syn.fits
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (2, cfg.conv1d.seq_len, cfg.conv1d.channels))
    y = dep(x)
    assert y.shape == (2, cfg.conv1d.out_features)
    meas = dep.measure((x,), model=cfg.name,
                       model_flops=float(conv1d_flops(cfg)), n_runs=2)
    assert meas.target == "rtl" and meas.latency_s > 0
    dep.save(str(tmp_path))
    # every artifact, plus the static-analysis report save() adds
    assert ({p.name for p in tmp_path.iterdir()}
            == set(dep.artifacts) | {"analysis.json"})


def test_workflow_roundtrip_target_rtl_conv1d():
    """The same single run_once path drives the conv1d arch."""
    from repro.core.report import DesignReport
    from repro.core.workflow import Requirement, Workflow
    from repro.model.conv1d import conv1d_apply, conv1d_flops, conv1d_schema

    cfg = get_config("elastic-conv1d")

    def train_fn(knobs):
        params = init_params(conv1d_schema(cfg), jax.random.PRNGKey(0))
        rep = DesignReport(model=cfg.name, train_loss=0.0, eval_loss=0.0,
                           weight_fmt=str(FxpFormat(knobs["bits"],
                                                    knobs["bits"] - 2)))
        return params, rep, None

    def step_builder(knobs, params):
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (1, cfg.conv1d.seq_len, cfg.conv1d.channels))
        return ((lambda p, xx: conv1d_apply(p, xx, cfg)[0]), (params, x),
                float(conv1d_flops(cfg)))

    def stepper_builder(knobs):
        return Creator(hw=XC7S15).build(cfg, SHAPES_CONV1D["infer_1"])

    wf = Workflow(creator=Creator(hw=XC7S15), train_fn=train_fn,
                  step_builder=step_builder, stepper_builder=stepper_builder,
                  target="rtl")
    hist = wf.run(Requirement(max_latency_s=1.0), lambda h: None,
                  {"bits": 8}, max_iters=2)
    assert len(hist) == 1 and hist[0].satisfied
    rec = hist[0]
    assert rec.synthesis.backend == "rtl"
    assert rec.measurement.platform.startswith("rtl-emulator")
    assert rec.measurement.target == "rtl"


def test_rtl_options_w_fmt_overrides():
    opts = RTLOptions(w_fmt_overrides={"conv1d": FxpFormat(6, 4)})
    assert opts.w_fmt_overrides["conv1d"] == FxpFormat(6, 4)
    with pytest.raises(ValueError, match="unknown hardware template"):
        RTLOptions(w_fmt_overrides={"cnv1d": FxpFormat(6, 4)})
    with pytest.raises(TypeError, match="FxpFormat"):
        RTLOptions(w_fmt_overrides={"conv1d": (6, 4)})
    # weightless kinds are rejected, not silently ignored
    with pytest.raises(ValueError, match="carries no weight format"):
        RTLOptions(w_fmt_overrides={"act_lut": FxpFormat(6, 4)})
    # an override for a kind ABSENT from the model must not widen (or
    # reject via) that model's envelope check — shared sweep dicts work
    g_lstm = _lstm_graph(w_fmt_overrides={"conv1d": FxpFormat(14, 10)})
    assert g_lstm.node("lstm_cell_l0").w_fmt == FxpFormat(8, 6)
    # overrides reach the lowered nodes (and stay bit-exact)
    g, cfg, params = _conv_graph(
        w_fmt_overrides={"conv1d": FxpFormat(6, 4)})
    assert g.node("conv1d_0").w_fmt == FxpFormat(6, 4)
    assert g.node("linear_head").w_fmt == FxpFormat(8, 6)   # default kept
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (2, cfg.conv1d.seq_len, cfg.conv1d.channels))
    assert_bit_exact(g, x, mode="jnp")


def test_emulator_cache_stats_and_dispatch_counters():
    """cache_stats() mirrors trace_count and splits hits/misses/evictions;
    dispatch spans carry mode + cached flag when a tracer is installed."""
    from repro import obs

    g = _lstm_graph()
    em = RTLEmulator(g, max_programs=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 6, 1))
    with obs.capture("emu") as cap:
        em.run(x[:1])                       # miss
        em.run(x[:1])                       # hit
        em.run(x[:2])                       # miss
        em.run(x[:3])                       # miss -> evicts (1,6,1)
        em.run(x[:1])                       # miss again (was evicted)
    st = em.cache_stats()
    assert st["misses"] == st["retraces"] == em.trace_count == 4
    assert st["hits"] == 1
    assert st["evictions"] >= 1
    assert st["dispatches"]["fused"] == 5
    # spans: one per dispatch, cached flag tracks hit/miss
    ds = obs.find_spans(cap.trace.spans, "rtl.emulator.dispatch")
    assert len(ds) == 5
    assert [d.attrs["cached"] for d in ds] == [False, True, False, False,
                                               False]
    assert all(d.attrs["mode"] == "fused" for d in ds)
    # counters mirrored into the captured registry
    mx = cap.trace.metrics
    assert mx["rtl.emulator.cache_miss"]["value"] == 4
    assert mx["rtl.emulator.cache_hit"]["value"] == 1


def test_measurement_report_percentiles_rtl():
    """RTL measure keeps per-run samples: latency_s stays the deterministic
    cycle model while p50/p99 characterize the executing proxy."""
    cr = Creator(hw=XC7S15)
    st_ = cr.build(get_config("elastic-lstm"), SHAPES_LSTM["infer_1"])
    _, exe = cr.translate(st_, target="rtl")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 1))
    rep = exe.measure((x,), model="elastic-lstm", model_flops=1e6, n_runs=7)
    assert rep.n_runs == 7
    assert 0 < rep.latency_p50_s <= rep.latency_p99_s
    # the fabric latency is the cycle model, not host wall-clock
    assert rep.latency_s == pytest.approx(exe.cycles / 100e6, rel=1e-6)


def test_emulator_thread_hammer_consistent():
    """Pooled serving dispatches one emulator from worker threads; the lock
    in _program/_count_dispatch must keep the LRU + counters consistent
    under contention (cache churn forced by max_programs < live shapes),
    and every thread must still see bit-exact outputs."""
    import threading

    g = _lstm_graph()
    em = RTLEmulator(g, max_programs=2)
    xs = {b: jax.random.normal(jax.random.PRNGKey(b), (b, 6, 1))
          for b in (1, 2, 3)}
    want = {b: np.asarray(RTLEmulator(g).run(x).outputs)
            for b, x in xs.items()}
    n_threads, n_iters = 4, 6
    errors = []

    def hammer(tid):
        try:
            for i in range(n_iters):
                b = 1 + (tid + i) % 3
                out = np.asarray(em.run(xs[b]).outputs)
                if not np.array_equal(out, want[b]):
                    errors.append((tid, i, b, "mismatch"))
            outs = em.run_many([xs[1], xs[2]])   # one composite dispatch
            for b, r in zip((1, 2), outs):
                if not np.array_equal(np.asarray(r.outputs), want[b]):
                    errors.append((tid, b, "run_many mismatch"))
        except Exception as e:              # noqa: BLE001 - collect, don't die
            errors.append((tid, repr(e)))

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]
    st = em.cache_stats()
    total = n_threads * (n_iters + 1)       # run_many is ONE dispatch
    assert sum(st["dispatches"].values()) == total
    assert st["hits"] + st["misses"] == total
    assert st["misses"] >= 3                # at least one per distinct shape
    # the LRU honored its capacity: live programs = misses - evictions
    assert st["misses"] - st["evictions"] <= 2
