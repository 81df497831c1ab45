"""What every run shares: the run record, the benchmark's own host spans,
and the compile counter."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from . import spec as spec_mod


class Spans:
    """The benchmark's own spans around its calls into each layer.

    Durations are kept per name on the host clock. In a traced run each
    span is also a ``jax.profiler.TraceAnnotation``, so the trace
    reduction can say what the host was doing in a device idle gap.
    """

    def __init__(self, profiling: bool = False):
        self.durations: Dict[str, List[float]] = {}
        self.profiling = profiling
        self._name = ""
        self._t0 = 0.0
        self._ann = None

    def __call__(self, name: str) -> "Spans":
        self._name = name
        return self

    def __enter__(self) -> "Spans":
        if self.profiling:
            import jax

            self._ann = jax.profiler.TraceAnnotation(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        d = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        self.durations.setdefault(self._name, []).append(d)
        return False


class CompileCounter:
    """Counts JAX compilation events (tracing, lowering, compiling, cache
    reads) between ``start`` and ``stop``."""

    def __init__(self):
        self.events: Dict[str, int] = {}

    def __call__(self, event: str, *args, **kw) -> None:
        if "compil" in event:
            self.events[event] = self.events.get(event, 0) + 1

    def start(self) -> "CompileCounter":
        import jax

        self.events.clear()
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def stop(self) -> int:
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
        return sum(self.events.values())


def one_in_flight(run, call, ring, keep):
    """The bulk loop: issue call i+1, fetch result i to the host, repeat
    until ``run.seconds`` have passed, then fetch the last one. Inputs
    cycle through ``ring``; ``keep(slot, host)`` is stored per fetch.
    Returns ``(kept, seconds from the first issue to the last fetch)``."""
    import numpy as np

    spans, r = run.spans, len(ring)
    kept = []
    t0 = time.perf_counter()
    end = t0 + run.seconds
    with spans("bench.call"):
        pending = call(ring[0])
    i = 1
    while True:
        last = time.perf_counter() >= end
        if not last:
            with spans("bench.call"):
                nxt = call(ring[i % r])
        with spans("bench.fetch"):
            host = np.asarray(pending)
        kept.append(((i - 1) % r, keep(host)))
        if last:
            return kept, time.perf_counter() - t0
        pending = nxt
        i += 1


@dataclass
class Run:
    """One run of one cell: its inputs, and what its window recorded.

    ``wrap``, when set, replaces the system under test: it is called with
    the callable the kind module built and keyword context (the weights, the
    configuration, the kind), and returns the callable the window drives.
    The control and the fault tests use it; a benchmark run never does.
    """

    cell: spec_mod.Cell
    seed: int
    seconds: float
    trace: bool = False
    spans: Spans = field(default_factory=Spans)
    wrap: Optional[Callable] = None
    traffic_overrides: Dict = field(default_factory=dict)
    stats: Dict = field(default_factory=dict)
    summary: object = None
    peaks: Optional[dict] = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return {**self.cell.traffic, **self.traffic_overrides}

    def system(self, fn, **context):
        """The callable the window drives: ``fn`` unless wrapped."""
        if self.wrap is None:
            return fn
        return self.wrap(fn, config=self.config, kind=self.traffic["kind"],
                         ref=self.cell.ref, **context)
