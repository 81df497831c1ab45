"""The ElasticAI-Workflow 3-stage loop on the paper's LSTM, end to end."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.creator import Creator
from repro.core.registry import validate_config
from repro.core.report import DesignReport
from repro.core.workflow import Requirement, Workflow
from repro.data.pipeline import TrafficConfig, traffic_flow_batch
from repro.model.layers import init_params
from repro.model.lstm import lstm_flops, lstm_schema
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.quant.fixedpoint import FxpFormat
from repro.quant.qat import QATConfig, make_qat_lstm_apply, make_qat_loss


def _train(knobs):
    cfg = get_config("elastic-lstm")
    qcfg = QATConfig(weight_fmt=FxpFormat(knobs["bits"], knobs["frac"]),
                     act_fmt=FxpFormat(knobs["bits"], knobs["frac"] - 2))
    params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    loss_fn = make_qat_loss(cfg, qcfg)
    ocfg = AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=100,
                      weight_decay=0.0)
    batch = traffic_flow_batch(TrafficConfig(batch=128), 0)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(lambda pp: loss_fn(pp, batch)[0])(p)
        p2, o2, _ = adamw_update(g, o, p, ocfg)
        return p2, o2, loss

    first = last = None
    for _ in range(60):
        params, opt, loss = step(params, opt)
        first = first if first is not None else float(loss)
        last = float(loss)
    ev = traffic_flow_batch(TrafficConfig(batch=128, seed=9), 1)
    apply = make_qat_lstm_apply(cfg, qcfg)
    pred, _ = apply(params, jnp.asarray(ev["x"]))
    eval_loss = float(jnp.mean((pred - jnp.asarray(ev["y"])) ** 2))
    rep = DesignReport(model="elastic-lstm", train_loss=last,
                       eval_loss=eval_loss,
                       weight_fmt=str(qcfg.weight_fmt),
                       act_fmt=str(qcfg.act_fmt))
    return params, rep, apply


def _steps(knobs, params):
    cfg = get_config("elastic-lstm")
    apply = make_qat_lstm_apply(
        cfg, QATConfig(weight_fmt=FxpFormat(knobs["bits"], knobs["frac"]),
                       act_fmt=FxpFormat(knobs["bits"], knobs["frac"] - 2)))
    x = jnp.asarray(traffic_flow_batch(TrafficConfig(batch=1), 0)["x"])
    fn = lambda p, xx: apply(p, xx)[0]
    return fn, (params, x), float(lstm_flops(cfg))


def test_registry_validates_all():
    assert "lstm" in validate_config(get_config("elastic-lstm"))
    with pytest.raises(KeyError):
        from repro.core import registry

        registry.get("nonexistent-component")


def test_workflow_loop_terminates_on_requirement():
    wf = Workflow(creator=Creator(), train_fn=_train, step_builder=_steps)
    req = Requirement(max_eval_loss=0.05, max_latency_s=10.0)

    def optimizer(history):
        k = dict(history[-1].knobs)
        if k["bits"] >= 16:
            return None
        k["bits"] += 4
        k["frac"] += 3
        return k

    hist = wf.run(req, optimizer, {"bits": 8, "frac": 6}, max_iters=3)
    assert hist, "no iterations ran"
    assert hist[-1].satisfied or len(hist) == 3
    # estimation and measurement exist and are comparable (Table-I shape)
    rec = hist[-1]
    assert rec.synthesis.est_latency_s > 0
    assert rec.measurement.latency_s > 0
    assert "latency_rel_err" in rec.est_vs_meas


def test_lstm_flops_matches_paper_scale():
    """Table I implies ~21.7 kOP/inference; our counted graph must agree."""
    flops = lstm_flops(get_config("elastic-lstm"))
    assert 15_000 < flops < 30_000, flops


@pytest.mark.parametrize("target", ["xla", "rtl"])
def test_workflow_single_path_over_targets(target):
    """Both deployment targets execute the same run_once (no backend fork);
    every MeasurementReport records the unified n_runs and target name."""
    from repro.core.target import DEFAULT_N_RUNS
    from repro.core.types import SHAPES_LSTM
    from repro.energy.hw import XC7S15
    from repro.model.lstm import lstm_apply

    cfg = get_config("elastic-lstm")
    assert not hasattr(Workflow, "_run_once_rtl"), \
        "the RTL fork must be gone: one run_once for every target"

    def train(knobs):
        params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
        rep = DesignReport(model="elastic-lstm", train_loss=0.0,
                           eval_loss=0.0)
        return params, rep, None

    def steps(knobs, params):
        x = jnp.asarray(traffic_flow_batch(TrafficConfig(batch=1), 0)["x"])
        fn = lambda p, xx: lstm_apply(p, xx, cfg)[0]
        return fn, (params, x), float(lstm_flops(cfg))

    creator = Creator(hw=XC7S15) if target == "rtl" else Creator()
    wf = Workflow(creator=creator, train_fn=train, step_builder=steps,
                  stepper_builder=(
                      (lambda k: creator.build(cfg, SHAPES_LSTM["infer_1"]))
                      if target == "rtl" else None),
                  target=target)
    rec = wf.run_once({"bits": 8, "frac": 6})
    assert rec.measurement.target == target
    assert rec.measurement.n_runs == DEFAULT_N_RUNS
    assert rec.measurement.latency_s > 0
    # satellite: _synth_from_fn threads the real model name (no more "wf")
    assert rec.synthesis.model == "elastic-lstm"
    assert "latency_rel_err" in rec.est_vs_meas


def test_workflow_run_once_emits_span_tree():
    """The observability tentpole, end to end: an RTL run_once under
    obs.capture decomposes into stage1 -> stage2 -> stage3 (-> verify) with
    emulator dispatch spans nested inside, and the measurement surfaces a
    non-degenerate latency distribution (p50/p99)."""
    from repro import obs
    from repro.core.types import SHAPES_LSTM
    from repro.energy.hw import XC7S15
    from repro.model.lstm import lstm_apply

    cfg = get_config("elastic-lstm")

    def train(knobs):
        params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
        return params, DesignReport(model="elastic-lstm", train_loss=0.0,
                                    eval_loss=0.0), None

    def steps(knobs, params):
        x = jnp.asarray(traffic_flow_batch(TrafficConfig(batch=1), 0)["x"])
        fn = lambda p, xx: lstm_apply(p, xx, cfg)[0]
        return fn, (params, x), float(lstm_flops(cfg))

    creator = Creator(hw=XC7S15)
    wf = Workflow(creator=creator, train_fn=train, step_builder=steps,
                  stepper_builder=lambda k: creator.build(
                      cfg, SHAPES_LSTM["infer_1"]),
                  target="rtl", verify=True)
    with obs.capture("wf") as cap:
        rec = wf.run_once({"bits": 8, "frac": 6})

    spans = cap.trace.spans
    root = obs.find_spans(spans, "workflow.run_once")[0]
    assert root.attrs["target"] == "rtl" and root.attrs["knob.bits"] == 8
    stages = {s.name for s in obs.children_of(spans, root)}
    assert {"workflow.stage1", "workflow.stage2", "workflow.stage3",
            "workflow.verify"} <= stages
    # emulator dispatches nest under the stage that issued them
    dispatches = obs.find_spans(spans, "rtl.emulator.dispatch")
    assert dispatches, "stage 3 must dispatch the emulator"
    s3 = obs.find_spans(spans, "workflow.stage3")[0]
    assert any(s3 in obs.ancestors(spans, d) for d in dispatches)
    # verify stage contains the differential conformance spans
    sv = obs.find_spans(spans, "workflow.verify")[0]
    conf = obs.find_spans(spans, "verify.conformance")[0]
    assert sv in obs.ancestors(spans, conf)
    assert sv.attrs["passed"] is True

    # the Chrome export is valid JSON and preserves the tree
    import json as _json
    doc = _json.loads(_json.dumps(cap.trace.chrome()))
    assert len(doc["traceEvents"]) == len(spans)
    assert sorted(ev["args"]["span_id"] for ev in doc["traceEvents"]) == \
        sorted(s.span_id for s in spans)

    # non-degenerate latency distribution on the report
    m = rec.measurement
    assert 0 < m.latency_p50_s <= m.latency_p99_s
    # pipeline metrics landed in the captured registry
    snap = cap.trace.metrics
    assert snap["rtl.emulator.dispatch.fused"]["value"] > 0
    assert snap["measure.latency_s.rtl"]["count"] > 0


def test_workflow_tracing_disabled_is_noop():
    """With the default (disabled) tracer, run_once records nothing — the
    near-zero-overhead contract of DESIGN.md §11."""
    from repro.obs import get_tracer

    trc = get_tracer()
    assert trc.enabled is False
    assert trc.spans == []


def test_workflow_resilience_stage_records_report():
    """Workflow(resilience=ChaosSpec): run_once drives the scripted chaos
    scenario against the deployed RTL artifact — SEU detected by the
    canary, breaker quarantined, traffic degraded to the XLA fallback —
    and attaches the ResilienceReport under a workflow.resilience span."""
    from repro import obs
    from repro.core.types import SHAPES_LSTM
    from repro.energy.hw import XC7S15
    from repro.model.lstm import lstm_apply
    from repro.resilience import (ChaosSpec, FaultPlan, FaultSpec,
                                  GuardPolicy)

    cfg = get_config("elastic-lstm")

    def train(knobs):
        params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
        return params, DesignReport(model="elastic-lstm", train_loss=0.0,
                                    eval_loss=0.0), None

    def steps(knobs, params):
        x = jnp.asarray(traffic_flow_batch(TrafficConfig(batch=1), 0)["x"])
        fn = lambda p, xx: lstm_apply(p, xx, cfg)[0]
        return fn, (params, x), float(lstm_flops(cfg))

    spec = ChaosSpec(
        plan=FaultPlan(faults=(
            FaultSpec(kind="bitflip", at_call=3, memory="lstm_cell_l0.w",
                      word=0, bit=7),), seed=3),
        n_requests=10,
        policy=GuardPolicy(max_retries=1, breaker_threshold=3,
                           canary_every=2))
    creator = Creator(hw=XC7S15)
    wf = Workflow(creator=creator, train_fn=train, step_builder=steps,
                  stepper_builder=lambda k: creator.build(
                      cfg, SHAPES_LSTM["infer_1"]),
                  target="rtl", resilience=spec)
    with obs.capture("wf") as cap:
        rec = wf.run_once({"bits": 8, "frac": 6})

    resil = rec.resilience
    assert resil is not None and resil.passed, resil.summary()
    assert resil.detected and resil.recovered
    assert resil.corrupted_after_detection == 0
    assert resil.requests_degraded > 0      # RTL→XLA failover carried it
    assert resil.counters["resilience.faults_injected.bitflip"] == 1
    sr = obs.find_spans(cap.trace.spans, "workflow.resilience")[0]
    assert sr.attrs["passed"] is True and sr.attrs["detected"] is True
    assert obs.find_spans(cap.trace.spans, "resilience.chaos")
    # the record still carries the ordinary stage-3 artifacts
    assert rec.measurement.target == "rtl"


def test_workflow_resilience_needs_graph_target():
    """The chaos stage needs a graph-carrying deployment (golden vectors +
    same-design XLA fallback); host-executed targets fail loudly."""
    from repro.resilience import ChaosSpec, FaultPlan, FaultSpec

    spec = ChaosSpec(plan=FaultPlan(
        faults=(FaultSpec(kind="transient", at_call=0),)), n_requests=2)
    wf = Workflow(creator=Creator(), train_fn=_train, step_builder=_steps,
                  target="xla", resilience=spec)
    with pytest.raises(ValueError, match="graph-carrying"):
        wf.run_once({"bits": 8, "frac": 6})
