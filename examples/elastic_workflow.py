"""The paper's demo, as a script: the full ElasticAI-Workflow on an edge
workload — design/train -> translate+estimate -> deploy+measure, with the
feedback loop widening the fixed-point format until the requirement is met
(what the PerCom audience would do interactively).

    PYTHONPATH=src python examples/elastic_workflow.py               # XLA loop
    PYTHONPATH=src python examples/elastic_workflow.py --target rtl
    PYTHONPATH=src python examples/elastic_workflow.py --target rtl --arch conv1d

``--arch`` picks the workload: the paper's traffic-flow LSTM (QAT-trained)
or the TCN-style depthwise conv1d sensor stack — both lower through the same
hardware-template registry (DESIGN.md §9). With ``--target rtl`` the loop's
stage 2/3 run against the *generated accelerator*: template artifacts are
emitted and the bit-exact emulator's cycle schedule provides the
measurement. Both targets drive the same ``Workflow.run_once`` — the target
registry resolves the substrate, and the RTL target's own
``options_from_knobs`` clamps the knobs to the exactness envelope (no
per-script format plumbing needed). Either way, the script finishes by
"pressing the button" — translating the final design to RTL artifacts
through the registry (written to ``--build-dir`` when given).
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.creator import Creator
from repro.core.report import DesignReport
from repro.core.target import get_target, list_targets
from repro.core.workflow import Requirement, Workflow
from repro.data.pipeline import (SensorConfig, TrafficConfig,
                                 sensor_window_batch, traffic_flow_batch)
from repro.model.layers import init_params
from repro.model.lstm import lstm_flops, lstm_schema
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.quant.fixedpoint import FxpFormat
from repro.quant.qat import QATConfig, make_qat_loss, make_qat_lstm_apply

TRAIN_STEPS = 120

ARCH_ALIASES = {"lstm": "elastic-lstm", "conv1d": "elastic-conv1d"}


def lstm_train_fn(knobs):
    cfg = get_config("elastic-lstm")
    qcfg = QATConfig(weight_fmt=FxpFormat(knobs["bits"], knobs["frac"]),
                     act_fmt=FxpFormat(knobs["bits"],
                                       max(0, knobs["frac"] - 2)),
                     hard_activations=knobs.get("hard_act", True))
    params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    loss_fn = make_qat_loss(cfg, qcfg)
    ocfg = AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=150,
                      weight_decay=0.0)
    batch = {k: jnp.asarray(v) for k, v in
             traffic_flow_batch(TrafficConfig(batch=256), 0).items()}

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(lambda pp: loss_fn(pp, batch)[0])(p)
        p2, o2, _ = adamw_update(g, o, p, ocfg)
        return p2, o2, loss

    for _ in range(TRAIN_STEPS):
        params, opt, loss = step(params, opt)
    ev = traffic_flow_batch(TrafficConfig(batch=256, seed=9), 1)
    apply = make_qat_lstm_apply(cfg, qcfg)
    pred, _ = apply(params, jnp.asarray(ev["x"]))
    eval_loss = float(jnp.mean((pred - jnp.asarray(ev["y"])) ** 2))
    rep = DesignReport(model="elastic-lstm", train_loss=float(loss),
                       eval_loss=eval_loss, params=2021,
                       weight_fmt=str(qcfg.weight_fmt),
                       act_fmt=str(qcfg.act_fmt))
    return params, rep, apply


def lstm_step_builder(knobs, params):
    cfg = get_config("elastic-lstm")
    qcfg = QATConfig(weight_fmt=FxpFormat(knobs["bits"], knobs["frac"]),
                     act_fmt=FxpFormat(knobs["bits"],
                                       max(0, knobs["frac"] - 2)))
    apply = make_qat_lstm_apply(cfg, qcfg)
    x = jnp.asarray(traffic_flow_batch(TrafficConfig(batch=1), 0)["x"])
    return (lambda p, xx: apply(p, xx)[0]), (params, x), float(lstm_flops(cfg))


def conv1d_train_fn(knobs):
    """Stage 1 for the sensor stack: the hard activations are already in
    the float graph, so QAT is just fake-quantizing the weights to the
    knobs' format (straight-through) — widening the knobs genuinely moves
    the reported eval loss, which is what the feedback loop reads."""
    from repro.model.conv1d import conv1d_apply, conv1d_schema
    from repro.quant.qat import fake_quant_tree

    cfg = get_config("elastic-conv1d")
    c = cfg.conv1d
    wfmt = FxpFormat(knobs["bits"], knobs["frac"])
    params = init_params(conv1d_schema(cfg), jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    ocfg = AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=150,
                       weight_decay=0.0)
    scfg = SensorConfig(seq_len=c.seq_len, channels=c.channels, batch=256)
    batch = {k: jnp.asarray(v) for k, v in
             sensor_window_batch(scfg, 0).items()}

    def loss_fn(p):
        pred, _ = conv1d_apply(fake_quant_tree(p, wfmt), batch["x"], cfg)
        return jnp.mean((pred - batch["y"]) ** 2)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(loss_fn)(p)
        p2, o2, _ = adamw_update(g, o, p, ocfg)
        return p2, o2, loss

    for _ in range(TRAIN_STEPS):
        params, opt, loss = step(params, opt)
    ev = sensor_window_batch(SensorConfig(seq_len=c.seq_len,
                                          channels=c.channels,
                                          batch=256, seed=9), 1)
    pred, _ = conv1d_apply(fake_quant_tree(params, wfmt),
                           jnp.asarray(ev["x"]), cfg)
    eval_loss = float(jnp.mean((pred - jnp.asarray(ev["y"])) ** 2))
    rep = DesignReport(model="elastic-conv1d", train_loss=float(loss),
                       eval_loss=eval_loss,
                       params=sum(x.size for x in jax.tree.leaves(params)),
                       weight_fmt=str(wfmt), act_fmt=str(
                           FxpFormat(knobs["bits"],
                                     max(0, knobs["frac"] - 2))))
    return params, rep, None


def conv1d_step_builder(knobs, params):
    from repro.model.conv1d import conv1d_apply, conv1d_flops

    cfg = get_config("elastic-conv1d")
    c = cfg.conv1d
    x = jnp.asarray(sensor_window_batch(
        SensorConfig(seq_len=c.seq_len, channels=c.channels, batch=1),
        0)["x"])
    return ((lambda p, xx: conv1d_apply(p, xx, cfg)[0]), (params, x),
            float(conv1d_flops(cfg)))


BUILDERS = {
    "elastic-lstm": (lstm_train_fn, lstm_step_builder),
    "elastic-conv1d": (conv1d_train_fn, conv1d_step_builder),
}


def make_workflow(arch: str, target: str, *, verify: bool):
    """The demo's Workflow for ``arch`` on ``target``, and the builder of
    the stepper it lowers (the RTL target needs the model graph)."""
    from repro.core.types import shape_table_for, shapes_for
    from repro.energy.hw import XC7S15

    cfg = get_config(arch)
    infer_shape = shapes_for(cfg)[0]             # "infer_1" for both archs
    creator = Creator(hw=XC7S15) if target == "rtl" else Creator()
    train_fn, step_builder = BUILDERS[arch]

    def stepper_builder(knobs):
        return creator.build(cfg, shape_table_for(cfg)[infer_shape])

    wf = Workflow(creator=creator, train_fn=train_fn,
                  step_builder=step_builder, target=target,
                  stepper_builder=stepper_builder if target == "rtl"
                  else None, verify=verify,
                  analyze="error" if target == "rtl" else None)
    return wf, stepper_builder


#: the knobs the feedback loop starts from
INITIAL_KNOBS = {"bits": 4, "frac": 2}


def optimizer(history):
    """The feedback rule a developer would apply after reading the reports:
    eval loss too high -> widen the fixed-point format."""
    k = dict(history[-1].knobs)
    print(f"  [feedback] eval_loss={history[-1].design.eval_loss:.4f} "
          f"with {history[-1].design.weight_fmt} -> widening")
    if k["bits"] >= 16:
        return None
    k["bits"] += 4
    k["frac"] += 3
    return k


def main():
    import argparse

    global TRAIN_STEPS
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--target", "--backend", dest="target",
                    choices=sorted(list_targets()), default="xla",
                    help="registered deployment target (--backend is the "
                         "legacy spelling)")
    ap.add_argument("--arch", default="lstm",
                    choices=sorted(set(ARCH_ALIASES) | set(BUILDERS)),
                    help="workload: the paper's LSTM or the conv1d sensor "
                         "stack (short or full arch id)")
    ap.add_argument("--max-iters", type=int, default=4,
                    help="feedback-loop budget (CI smoke uses 1)")
    ap.add_argument("--train-steps", type=int, default=TRAIN_STEPS,
                    help="stage-1 training steps per iteration")
    ap.add_argument("--build-dir", default=None,
                    help="write the final RTL artifact bundle here "
                         "(<build-dir>/<arch>/)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="capture the whole run (spans + metrics) and write "
                         "Chrome trace-event JSON here — open it in Perfetto "
                         "or chrome://tracing; the full RunTrace bundle "
                         "(trace.jsonl, metrics.json, summary.txt) lands "
                         "next to it, and a copy goes into the --build-dir "
                         "bundle when given")
    ap.add_argument("--verify", action="store_true",
                    help="run the Elastic Node conformance stage: "
                         "Deployment.verify after every loop measurement, "
                         "plus a full differential check + golden vectors "
                         "for the final RTL design (reports land in "
                         "<build-dir>/<arch>/ when given)")
    ap.add_argument("--chaos", default=None, metavar="PLAN_JSON",
                    help="run a scripted chaos scenario against the final "
                         "RTL deployment: the FaultPlan JSON is injected "
                         "under a guarded wrapper (canary + breaker + "
                         "RTL->XLA fallback) and scored on the golden "
                         "vectors; exits non-zero unless the fault is "
                         "detected and traffic recovers with zero "
                         "post-detection corruption (resilience.json "
                         "lands in <build-dir>/<arch>/ when given); "
                         "see examples/chaos_plan.json")
    args = ap.parse_args()
    if args.chaos and args.target != "rtl":
        ap.error("--chaos models SEUs in the generated accelerator; "
                 "use --target rtl")
    target = args.target
    arch = ARCH_ALIASES.get(args.arch, args.arch)
    TRAIN_STEPS = args.train_steps
    from repro.energy.hw import XC7S15

    cap = None
    if args.trace:
        from repro import obs

        cap = obs.capture(f"elastic-workflow[{arch}:{target}]")
        cap.__enter__()                  # closed (and written) at the end

    cfg = get_config(arch)
    train_fn, _ = BUILDERS[arch]
    wf, stepper_builder = make_workflow(arch, target, verify=args.verify)
    req = Requirement(max_eval_loss=0.01, max_latency_s=1.0)
    hist = wf.run(req, optimizer, INITIAL_KNOBS,
                  max_iters=args.max_iters)
    print(f"\n{'it':>3} {'fmt':>7} {'eval':>8} {'est_ms':>8} {'meas_ms':>8} "
          f"{'est_uJ':>8} {'GOP/J':>7} {'vrfy':>4} {'ok':>3}")
    for r in hist:
        vrfy = "-" if r.conformance is None else \
            ("Y" if r.conformance.passed else "FAIL")
        print(f"{r.iteration:>3} {r.design.weight_fmt:>7} "
              f"{r.design.eval_loss:8.4f} "
              f"{r.synthesis.est_latency_s*1e3:8.3f} "
              f"{r.measurement.latency_s*1e3:8.3f} "
              f"{r.synthesis.est_energy_j*1e6:8.2f} "
              f"{r.measurement.gop_per_j:7.2f} "
              f"{vrfy:>4} "
              f"{'Y' if r.satisfied else 'n':>3}")
    print("\nworkflow finished:",
          "requirement met" if hist[-1].satisfied else "budget exhausted")

    # --- "press the button": translate the final design to RTL ----------- #
    best = hist[-1].knobs
    params, _, _ = train_fn(best)
    rtl = get_target("rtl")
    creator_rtl = Creator(hw=XC7S15)
    st = stepper_builder(best)
    syn, dep = creator_rtl.translate(
        st, target="rtl", params=params,
        options=rtl.options_from_knobs(best))
    if hist[-1].analysis is not None:
        print(f"\nstatic analysis: {hist[-1].analysis.summary()}")
    print(f"\nRTL translate [{arch}]: {syn.n_artifacts} artifacts, "
          f"{syn.resources['cycles']} cycles "
          f"({syn.est_latency_s*1e6:.2f} us @ 100 MHz), "
          f"dsp={syn.resources['dsp']} bram36={syn.resources['bram36']} "
          f"lut={syn.resources['lut']}, fits={syn.fits}")
    for name in sorted(dep.artifacts):
        print(f"  - {name}")
    out = None
    if args.build_dir:
        import os

        out = os.path.join(args.build_dir, arch)
        dep.save(out)
        print(f"artifact bundle written to {out}/")

    # --- Elastic Node conformance of the final design -------------------- #
    if args.verify:
        from repro.model.conv1d import conv1d_flops
        from repro.model.lstm import lstm_flops
        from repro.verify import generate_vectors, save_vectors

        flops = float(lstm_flops(cfg) if cfg.family == "lstm"
                      else conv1d_flops(cfg))
        rep = dep.verify(model=cfg.name, model_flops=flops)
        print(f"\nconformance: {rep.summary()}")
        for note in rep.notes:
            print(f"  note: {note}")
        if out is not None:
            import os

            with open(os.path.join(out, "conformance.json"), "w") as f:
                f.write(rep.to_json())
            save_vectors(generate_vectors(dep.graph),
                         os.path.join(out, "vectors"))
            print(f"ConformanceReport + golden vectors written to {out}/")
        if not rep.passed:
            raise SystemExit("conformance FAILED — see report above")

    # --- scripted chaos: fault-inject the deployed accelerator ----------- #
    if args.chaos:
        from repro.resilience import ChaosSpec, FallbackPolicy, run_chaos
        from repro.resilience import FaultPlan, GuardPolicy
        from repro.rtl.emulator import reference_apply
        from repro.core.target import XLADeployment

        plan = FaultPlan.load(args.chaos)
        spec = ChaosSpec(plan=plan, n_requests=24, seed=plan.seed,
                         policy=GuardPolicy(timeout_s=0.25, max_retries=2,
                                            breaker_threshold=3,
                                            canary_every=4))
        fb = XLADeployment(fn=jax.jit(
            lambda x: reference_apply(dep.graph, x)), hw=XC7S15)
        resil = run_chaos(dep, spec, fallback=FallbackPolicy.to_xla(fb))
        print(f"\n{resil.summary()}")
        for f in resil.faults_injected:
            print(f"  injected: {f}")
        for d in resil.faults_detected:
            print(f"  detected: {d}")
        if out is not None:
            import os

            resil.save(os.path.join(out, "resilience.json"))
            print(f"ResilienceReport written to {out}/resilience.json")
        if not resil.passed:
            raise SystemExit(
                "chaos scenario FAILED: detected="
                f"{resil.detected} recovered={resil.recovered} "
                "corrupted_after_detection="
                f"{resil.corrupted_after_detection}")

    # --- write the captured trace ---------------------------------------- #
    if cap is not None:
        import json
        import os

        cap.__exit__(None, None, None)
        rt = cap.trace
        trace_path = os.path.abspath(args.trace)
        bundle_dir = os.path.dirname(trace_path) or "."
        paths = rt.save(bundle_dir)
        if trace_path != paths["trace.json"]:    # honor a custom filename
            with open(trace_path, "w") as f:
                json.dump(rt.chrome(), f, indent=2, sort_keys=True)
        if out is not None:                      # copy into the RTL bundle
            rt.save(out)
        print(f"\n{rt.summary()}")
        print(f"\nChrome trace written to {args.trace} "
              "(open in Perfetto / chrome://tracing)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
