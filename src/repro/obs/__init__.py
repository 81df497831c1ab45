"""Workflow-wide observability: spans, counters, latency histograms
(DESIGN.md §11).

Dependency-free tracing + metrics threaded through every pipeline layer —
the telemetry substrate the serving runtime and the DSE engine consume:

* :mod:`repro.obs.trace`   — nested context-manager spans on a monotonic
  (injectable) clock, or on the JAX profiler's clock
  (``Tracer(profiler=True)``), a process-default :class:`Tracer` that is
  a no-op until enabled, exporters for Chrome trace-event JSON (Perfetto)
  and JSONL;
* :mod:`repro.obs.metrics` — named counters / gauges / histograms with
  p50/p95/p99 summaries;
* :mod:`repro.obs.export`  — the :class:`RunTrace` artifact written next
  to ``Deployment.save`` bundles, and :class:`capture`, the one-liner that
  scopes an enabled tracer + fresh registry to a ``with`` body.

Overhead contract: with tracing disabled (the default) every instrumented
site costs one function call and one attribute check (sites with
attributes hoist the check and build nothing) — the chip benchmark's
``lstm-b1`` ``windows_per_s``, one deployment call per window, is the
regression guard (``BENCHMARK.json``, PERF.md).

Metric namespaces by layer: ``rtl.*`` (emulator), ``measure.*``
(Deployment.measure), ``resilience.*`` (guards, §12), ``server.*`` (the
batched LM server + the pool shims), and ``serving.*`` (the accelerator
farm, §14: ``serving.queue.admitted/shed_full/expired/depth``, per-router
``serving.router.<design>.<len>.affinity_hit|miss``, histograms
``serving.latency_s[.<design>]``, ``serving.queue_wait_s``,
``serving.batch_fill``, ``serving.batch_size``).
"""
from repro.obs.export import RunTrace, capture  # noqa: F401
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, get_metrics, percentile,
                               set_metrics)
from repro.obs.trace import (Span, Tracer, ancestors,  # noqa: F401
                             children_of, find_spans, get_tracer,
                             set_tracer, span, span_tree, to_chrome_trace,
                             to_jsonl)
