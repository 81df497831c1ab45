"""Pluggable deployment targets — the registry behind ``Creator.translate``.

The paper's promise is one button for many substrates: the developer designs
a model once and the toolchain translates it to whatever accelerator the
deployment calls for. This module is that boundary, as two first-class
abstractions (DESIGN.md §8):

* A :class:`Target` — a named translation backend. Each target declares its
  ``name``, a ``default_hw`` :class:`HWSpec`, an ``options_cls`` dataclass
  (the *only* place target-specific knobs live; nothing leaks into the
  shared ``Creator.translate`` signature), an ``options_from_knobs`` hook
  that maps Workflow knob dicts onto valid options, and
  ``translate(cfg, params, stepper, options) -> (SynthesisReport,
  Deployment)``.

* A :class:`Deployment` — the uniform stage-3 artifact every target returns.
  It is callable on inputs, measurable (:meth:`Deployment.measure`, one
  documented ``n_runs`` default for every target), savable
  (:meth:`Deployment.save`), and carries ``target``/``cycles`` metadata.

Targets register by name (:func:`register_target`); the RTL target is a
lazy entry so ``repro.rtl`` only imports when first requested. Adding a new
backend (multi-device XLA, a per-FPGA-part RTL variant, ...) means writing
one Target class and registering it — ``Creator`` and ``Workflow`` never
change again.

The RTL target applies the same pattern one level down: inside it, each
*layer kind* is a registered hardware template (``repro.rtl.oplib``,
DESIGN.md §9), and its options dataclass (``RTLOptions``) carries per-kind
knobs such as ``w_fmt_overrides`` validated against that registry.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import (Any, Dict, Optional, Protocol, Tuple, Type,
                    runtime_checkable)

import jax

from repro.core.report import MeasurementReport, SynthesisReport
from repro.energy.hw import HWSpec, TPU_V5E
from repro.energy.meter import meter_channels
from repro.energy.roofline import roofline
from repro.obs import get_metrics, get_tracer, percentile

#: The single documented stage-3 measurement default, shared by every
#: target. (Pre-redesign the XLA path used 20 and the RTL path used 1; the
#: RTL emulator replays a cached compiled program per repeat, so 20 is cheap
#: there too and both substrates now average over the same sample count.)
DEFAULT_N_RUNS = 20


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N_active·D (forward-only serving)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch * shape.step_tokens   # decode


# --------------------------------------------------------------------------- #
# Options — the per-target translate knobs
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class TargetOptions:
    """Base for every target's options dataclass.

    ``hw`` / ``model_flops`` are shared across targets; ``Creator.translate``
    fills them (from its own ``hw`` and the cfg/shape FLOP estimate) when the
    caller leaves them ``None``. Target-specific knobs (Q-formats, emulator
    modes, ...) live on subclasses, never on ``Creator.translate`` itself.
    """

    hw: Optional[HWSpec] = None
    model_flops: Optional[float] = None

    def filled(self, *, hw: Optional[HWSpec],
               model_flops: Optional[float]) -> "TargetOptions":
        """Return a copy with unset shared fields defaulted."""
        return dataclasses.replace(
            self,
            hw=self.hw if self.hw is not None else hw,
            model_flops=(self.model_flops if self.model_flops is not None
                         else model_flops))


@dataclass(frozen=True)
class XLAOptions(TargetOptions):
    """Options for the jit/XLA target.

    ``kind`` overrides the stepper shape's program kind
    ("train" | "prefill" | "decode"); ``None`` uses ``stepper.shape.kind``.
    """

    kind: Optional[str] = None

    _KINDS = (None, "train", "prefill", "decode")

    def __post_init__(self):
        if self.kind not in self._KINDS:
            raise ValueError("XLAOptions.kind must be one of "
                             f"{self._KINDS[1:]} or None, got {self.kind!r}")


# --------------------------------------------------------------------------- #
# Deployment — the uniform stage-3 artifact
# --------------------------------------------------------------------------- #


class Deployment:
    """What ``Target.translate`` hands back next to the SynthesisReport.

    The uniform contract, regardless of substrate:

    * callable on inputs (``deployment(*args)`` runs the deployed design);
    * :meth:`measure` executes it and returns a :class:`MeasurementReport`
      that records ``n_runs`` and the target name;
    * :meth:`save` writes the deployable artifacts to a build directory;
    * ``target`` (name) and ``cycles`` (cycle-schedule length, ``None`` when
      the substrate has no fabric clock) are inspectable metadata;
    * :meth:`bind_step` lets the Workflow hand over the concrete step
      function it wants timed — host-executed targets (XLA) measure that
      callable, targets with their own execution substrate (the RTL
      emulator) ignore it, because their measurement must come off the
      deployed design itself.
    """

    target = ""
    cycles: Optional[int] = None

    def __call__(self, *args):
        raise NotImplementedError

    def bind_step(self, fn) -> "Deployment":
        """Default: the deployment is its own executor."""
        return self

    def measure(self, args, *, model: str, model_flops: float,
                n_runs: int = DEFAULT_N_RUNS, warmup: int = 1,
                hw: Optional[HWSpec] = None) -> MeasurementReport:
        """Execute ``warmup`` unrecorded runs, then ``n_runs`` timed ones.

        Warmup runs are part of the contract, not a courtesy: compile /
        trace / first-touch cost must be excluded from the latency samples,
        so ``latency_p50_s``/``latency_p99_s`` characterize steady-state
        tails only (the serving layer's admission decisions read them)."""
        raise NotImplementedError

    def save(self, build_dir: str) -> None:
        raise NotImplementedError

    def verify(self, args=None, *, model: str, model_flops: float,
               hw: Optional[HWSpec] = None, protocol=None, oracle=None):
        """Elastic Node conformance: run this deployment through the
        verification subsystem (:mod:`repro.verify`) and return its
        :class:`~repro.verify.ConformanceReport`.

        Part of the uniform Deployment contract, like :meth:`measure`:
        self-executing targets (RTL) get the full differential check —
        every emulator mode mutually bit-exact over the design's golden
        vectors, int output within the error budget of the float oracle —
        plus the measurement protocol (warmup, ``n_runs``, latency/energy
        bands vs the XC7S15 model and Table I); host-executed targets get
        the protocol plus an ``oracle`` comparison when one is supplied.
        ``args`` follows the :meth:`measure` convention and may be omitted
        for self-executing targets (the golden stimulus stands in).
        """
        from repro.verify import verify_deployment

        return verify_deployment(self, args, model=model,
                                 model_flops=model_flops, hw=hw,
                                 protocol=protocol, oracle=oracle)

    def guarded(self, **kwargs) -> "Deployment":
        """Wrap this deployment for fault-tolerant serving: per-call
        timeout, bounded retry, circuit breaker, golden-vector canary
        probes, and graceful fallback (``repro.resilience``, DESIGN.md
        §12). Keyword arguments go to
        :class:`~repro.resilience.GuardedDeployment` (``policy=``,
        ``fallback=``, ``canary=``, injectable ``clock``/``rng``, ...).
        Part of the uniform contract so a pool can guard any target the
        registry produces.
        """
        from repro.resilience import GuardedDeployment

        return GuardedDeployment(self, **kwargs)


@dataclass
class XLADeployment(Deployment):
    """The jitted-executable deployment: wall-clock timing on the device
    JAX runs it on, with duty-1 power from the HWSpec.

    A call is the span ``xla.call`` (attrs ``kind``, ``arch``, ``batch``)
    and counts ``xla.prefill.tokens`` (batch × sequence of the compiled
    shape) or ``xla.decode.steps`` and ``xla.decode.tokens`` (``step_tokens``
    per sequence of the batch)."""

    fn: Any                                     # compiled/jitted callable
    hw: HWSpec = TPU_V5E
    hlo_text: str = ""
    cost: Dict[str, float] = field(default_factory=dict)
    kind: str = ""                              # "train" | "prefill" | "decode"
    arch: str = ""
    batch: int = 0
    seq: int = 0
    step_tokens: int = 1

    target = "xla"

    def __call__(self, *args):
        mx = get_metrics()
        if self.kind == "decode":
            mx.counter("xla.decode.steps").inc()
            mx.counter("xla.decode.tokens").inc(self.batch * self.step_tokens)
        elif self.kind == "prefill":
            mx.counter("xla.prefill.tokens").inc(self.batch * self.seq)
        trc = get_tracer()
        if not trc.enabled:                  # hoisted guard: skip the attrs
            return self.fn(*args)
        with trc.span("xla.call", kind=self.kind, arch=self.arch,
                      batch=self.batch):
            return self.fn(*args)

    def bind_step(self, fn) -> "XLADeployment":
        """Measure ``fn`` instead of the translated executable, keeping the
        translate-time metadata (HLO, cost) on the new artifact."""
        return dataclasses.replace(self, fn=fn)

    def measure(self, args, *, model: str, model_flops: float,
                n_runs: int = DEFAULT_N_RUNS, warmup: int = 1,
                hw: Optional[HWSpec] = None) -> MeasurementReport:
        """Time ``n_runs`` executions, keeping every per-run latency (each
        run is individually synchronized) so the report carries real
        p50/p99 tail percentiles, not just the mean. The ``warmup`` runs
        execute first and never enter the samples — compile time is a
        deployment cost, not a steady-state tail."""
        hw = hw or self.hw
        n_runs = max(1, n_runs)
        samples = []
        with get_tracer().span("xla.measure", model=model, n_runs=n_runs,
                               warmup=warmup):
            for _ in range(max(0, warmup)):     # excluded from percentiles
                jax.block_until_ready(self.fn(*args))
            for _ in range(n_runs):
                t0 = time.perf_counter()
                out = self.fn(*args)
                jax.block_until_ready(out)
                samples.append(time.perf_counter() - t0)
        hist = get_metrics().histogram("measure.latency_s.xla")
        for s in samples:
            hist.observe(s)
        lat = sum(samples) / n_runs
        energy = hw.energy_j(lat)
        leaves = jax.tree.leaves(out)
        dev = (next(iter(leaves[0].devices())) if leaves
               and hasattr(leaves[0], "devices") else jax.devices()[0])
        return MeasurementReport(
            model=model, platform=f"xla({dev.platform}:{dev.device_kind})",
            latency_s=lat, power_w=hw.active_w, energy_j=energy,
            gop_per_j=(model_flops / 1e9) / energy if energy else 0.0,
            n_runs=n_runs, target=self.target,
            latency_p50_s=percentile(samples, 50),
            latency_p99_s=percentile(samples, 99))

    def save(self, build_dir: str) -> None:
        """Artifacts for this substrate: the compiled HLO plus a manifest."""
        os.makedirs(build_dir, exist_ok=True)
        with open(os.path.join(build_dir, "module.hlo.txt"), "w") as f:
            f.write(self.hlo_text)
        with open(os.path.join(build_dir, "deployment.json"), "w") as f:
            json.dump({"target": self.target, "hw": self.hw.name,
                       "cost": self.cost}, f, indent=2)


# --------------------------------------------------------------------------- #
# Target protocol + registry
# --------------------------------------------------------------------------- #


@runtime_checkable
class Target(Protocol):
    """What a translation backend must provide to plug into the toolchain."""

    name: str
    default_hw: HWSpec
    options_cls: Type[TargetOptions]
    #: Workflow refuses step-fn-only operation for targets that must lower a
    #: real Stepper (e.g. RTL needs the model graph, not a closed-over fn).
    requires_stepper: bool

    def options_from_knobs(self, knobs: Dict[str, Any]) -> TargetOptions:
        """Map Workflow knobs onto a *valid* options instance (this replaces
        the old per-Workflow ``fmt_builder`` hook)."""
        ...

    def translate(self, cfg, params, stepper,
                  options: TargetOptions) -> Tuple[SynthesisReport,
                                                   Deployment]:
        ...


_REGISTRY: Dict[str, Target] = {}
#: name -> (module, attribute); resolved on first get_target() so heavyweight
#: backends don't import until requested.
_LAZY: Dict[str, Tuple[str, str]] = {}


def register_target(target: Target, *, overwrite: bool = False) -> Target:
    """Register ``target`` under ``target.name``. Registering a name twice is
    an error unless ``overwrite=True`` (lazy placeholders may be overwritten
    by the concrete target they resolve to)."""
    name = target.name
    if not overwrite and (name in _REGISTRY or name in _LAZY):
        raise ValueError(f"target {name!r} already registered "
                         f"(registered: {list_targets()})")
    _LAZY.pop(name, None)
    _REGISTRY[name] = target
    return target


def register_lazy_target(name: str, module: str, attr: str) -> None:
    """Register a target import path, deferring the import to first use."""
    if name in _REGISTRY or name in _LAZY:
        raise ValueError(f"target {name!r} already registered "
                         f"(registered: {list_targets()})")
    _LAZY[name] = (module, attr)


def list_targets() -> list:
    """Names of every registered target (lazy ones included), sorted."""
    return sorted(set(_REGISTRY) | set(_LAZY))


def get_target(name) -> Target:
    """Resolve a target by name (or pass a Target instance through).

    Unknown names raise ``ValueError`` listing what *is* registered, so the
    error message doubles as discovery.
    """
    if not isinstance(name, str):               # already a Target
        return name
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name in _LAZY:
        module, attr = _LAZY[name]
        target = getattr(importlib.import_module(module), attr)
        register_target(target, overwrite=True)
        return target
    raise ValueError(f"unknown target {name!r}; "
                     f"registered targets: {list_targets()}")


# --------------------------------------------------------------------------- #
# The XLA target (the former Creator.translate backend="xla" body)
# --------------------------------------------------------------------------- #


class XLATarget:
    """jit/XLA lowering against a TPU-class HWSpec; the SynthesisReport is
    the Vivado-estimation analogue (memory_analysis as resource utilization,
    roofline + 8-channel meter as timing/power estimation)."""

    name = "xla"
    default_hw = TPU_V5E
    options_cls = XLAOptions
    requires_stepper = False

    def options_from_knobs(self, knobs: Dict[str, Any]) -> XLAOptions:
        return XLAOptions()

    def translate(self, cfg, params, st,
                  options: XLAOptions) -> Tuple[SynthesisReport,
                                                XLADeployment]:
        hw = options.hw or self.default_hw
        kind = options.kind or st.shape.kind
        abstract = st.abstract_inputs()
        if st.mesh is not None:
            from jax.sharding import NamedSharding
            from repro.model.lm import batch_pspecs

            param_sh = st.shardings(st.schema)
            bspecs = batch_pspecs(st.cfg, st.shape, st.mesh_cfg)
            batch_sh = {k: NamedSharding(st.mesh, v)
                        for k, v in bspecs.items()}
            ctxmgr = st.mesh
        else:
            param_sh = batch_sh = None
            import contextlib

            ctxmgr = contextlib.nullcontext()

        trc = get_tracer()
        t0 = time.perf_counter()
        with ctxmgr:
            with trc.span("xla.lower", arch=st.cfg.name, kind=kind):
                if kind == "train":
                    if param_sh is not None:
                        from jax.sharding import NamedSharding
                        from repro.model.layers import tree_map_pspec
                        from repro.optim.adamw import opt_state_schema

                        opt_sh = tree_map_pspec(
                            lambda s: NamedSharding(st.mesh, s.pspec),
                            opt_state_schema(st.schema, st.mesh_cfg))
                        fn = jax.jit(st.train_fn(),
                                     in_shardings=(param_sh, opt_sh,
                                                   batch_sh),
                                     donate_argnums=(0, 1))
                    else:
                        fn = jax.jit(st.train_fn(), donate_argnums=(0, 1))
                    lowered = fn.lower(abstract["params"],
                                       abstract["opt_state"],
                                       abstract["batch"])
                elif kind == "prefill":
                    fn = jax.jit(st.prefill_fn()) if param_sh is None \
                        else jax.jit(st.prefill_fn(),
                                     in_shardings=(param_sh, batch_sh))
                    lowered = fn.lower(abstract["params"], abstract["batch"])
                else:
                    if param_sh is not None:
                        from jax.sharding import NamedSharding
                        from repro.model.layers import tree_map_pspec

                        cache_sh = tree_map_pspec(
                            lambda s: NamedSharding(st.mesh, s.pspec),
                            st.cache_schema())
                        fn = jax.jit(st.decode_fn(),
                                     in_shardings=(param_sh,
                                                   batch_sh["tokens"],
                                                   cache_sh),
                                     donate_argnums=(2,))
                    else:
                        fn = jax.jit(st.decode_fn(), donate_argnums=(2,))
                    lowered = fn.lower(abstract["params"],
                                       abstract["batch"]["tokens"],
                                       abstract["cache"])
            with trc.span("xla.compile", arch=st.cfg.name, kind=kind):
                compiled = lowered.compile()
        compile_s = time.perf_counter() - t0

        cost = compiled.cost_analysis()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        n_dev = st.mesh.size if st.mesh is not None else 1

        model_flops = options.model_flops
        if model_flops is None:
            model_flops = model_flops_estimate(st.cfg, st.shape)
        rep = roofline(arch=st.cfg.name, shape=st.shape.name,
                       mesh=f"{n_dev}dev", n_devices=n_dev, cost=cost,
                       hlo_text=hlo, model_flops=model_flops, hw=hw)
        ch = meter_channels(hlo, n_dev, hw)

        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        est_latency = rep.step_s
        est_energy = ch.total_joules + hw.idle_w * est_latency
        syn = SynthesisReport(
            model=st.cfg.name, target=hw.name,
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            fits=peak <= hw.hbm_bytes,
            utilization=peak / hw.hbm_bytes,
            flops=rep.flops_per_device, bytes_accessed=rep.bytes_per_device,
            wire_bytes=rep.wire_bytes_per_device,
            est_latency_s=est_latency,
            est_power_w=est_energy / est_latency if est_latency else 0.0,
            est_energy_j=est_energy,
            est_gop_per_j=(rep.model_flops / 1e9) / est_energy / max(n_dev, 1)
            if est_energy else 0.0,
            bottleneck=rep.bottleneck,
            channels=ch.seconds, channel_joules=ch.joules,
            compile_seconds=compile_s, backend=self.name)
        dep = XLADeployment(fn=compiled, hw=hw, hlo_text=hlo,
                            cost={"flops": rep.flops_per_device,
                                  "bytes_accessed": rep.bytes_per_device,
                                  "wire_bytes": rep.wire_bytes_per_device},
                            kind=kind, arch=st.cfg.name,
                            batch=st.shape.global_batch,
                            seq=st.shape.seq_len,
                            step_tokens=st.shape.step_tokens)
        return syn, dep


XLA_TARGET = register_target(XLATarget())
register_lazy_target("rtl", "repro.rtl.backend", "RTL_TARGET")
