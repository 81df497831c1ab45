"""Reduction of the program's own spans in a ``jax.profiler`` trace.

With ``repro.obs.Tracer(profiler=True)`` installed, every span of the
program is a host annotation in the XSpace, on the clock the device
operations are stamped with. A deployment call is one tree on one thread
line: ``rtl.call`` around ``rtl.emulator.quantize``,
``rtl.emulator.dispatch`` and ``rtl.emulator.unpack``. Over the traced
window (the harness's ``bench.window`` annotation) this module reduces
the spans whose names start with ``rtl.`` to:

* the median duration of each span name, each span clipped to the window;
* the median self time of ``rtl.call``: its clipped duration less the
  union of the ``rtl.`` spans nested in it on the same thread line;
* the programs per call: the events on the first used device's
  ``XLA Modules`` line (one per program run) that start in the window,
  over the ``rtl.call`` spans that start in it.

The benchmark's own spans and the device operations are read by
``tracing`` as before; nothing here changes what it computes.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness import tracing

#: the prefix of the program's spans read here, and the deployment call
PREFIX = "rtl."
CALL = "rtl.call"
#: the line of a device plane that holds one event per program run
MODULES_LINE = "XLA Modules"

#: (plane, index of the line in the plane): one thread of the host
Line = Tuple[str, int]


@dataclass
class Split:
    """The deployment call's stages over one traced window (seconds)."""

    median_s: Dict[str, float] = field(default_factory=dict)
    call_self_s: Optional[float] = None
    calls: int = 0                  # rtl.call spans that start in the window
    modules: Optional[int] = None   # None: no device ran in the window

    @property
    def programs_per_call(self) -> Optional[float]:
        if self.modules is None or not self.calls:
            return None
        return self.modules / self.calls


def extract(profile) -> Tuple[List[Tuple[Line, tracing.Event]],
                              Dict[str, List[tracing.Event]]]:
    """``(program spans with their thread line, XLA Modules events by
    device plane)`` of a ``ProfileData``."""
    spans: List[Tuple[Line, tracing.Event]] = []
    modules: Dict[str, List[tracing.Event]] = {}
    for plane in profile.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            evs = [tracing.Event(e.name, float(e.start_ns),
                                 float(e.duration_ns))
                   for line in plane.lines if line.name == MODULES_LINE
                   for e in line.events]
            if evs:
                modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans.extend(((plane.name, i),
                              tracing.Event(e.name, float(e.start_ns),
                                            float(e.duration_ns)))
                             for e in line.events
                             if e.name.startswith(PREFIX))
    return spans, modules


def _clipped(e: tracing.Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))


def self_time_ns(call: tracing.Event, nested: Sequence[tracing.Event],
                 lo: float, hi: float) -> float:
    """``call``'s duration in ``[lo, hi)`` less the union of the spans of
    ``nested`` that lie inside it."""
    a, b = max(call.start_ns, lo), min(call.end_ns, hi)
    inside = [(max(e.start_ns, a), min(e.end_ns, b)) for e in nested
              if e.start_ns >= call.start_ns and e.end_ns <= call.end_ns]
    covered = sum(y - x for x, y in tracing.union(inside) if y > x)
    return max(0.0, (b - a) - covered)


def summarize(spans: Sequence[Tuple[Line, tracing.Event]],
              modules: Dict[str, List[tracing.Event]],
              devices: Dict[str, List[tracing.Event]],
              window: Tuple[float, float]) -> Split:
    """Reduce the program spans over ``window``; ``devices`` are the
    device operations by plane (``tracing.extract``), which say which
    device is the first used one."""
    lo, hi = window
    durations: Dict[str, List[float]] = {}
    by_line: Dict[Line, List[tracing.Event]] = {}
    for line, e in spans:
        d = _clipped(e, lo, hi)
        if d > 0:
            durations.setdefault(e.name, []).append(d)
            by_line.setdefault(line, []).append(e)
    self_ns = []
    for evs in by_line.values():
        # spans on one thread nest: sorted by start (the outer one first
        # where two start together), a span's nested ones follow it
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        for i, e in enumerate(evs):
            if e.name == CALL:
                j = i + 1
                while j < len(evs) and evs[j].start_ns < e.end_ns:
                    j += 1
                self_ns.append(self_time_ns(e, evs[i + 1:j], lo, hi))
    calls = sum(1 for _, e in spans if e.name == CALL and lo <= e.start_ns < hi)
    used = [name for name in sorted(devices)
            if any(e.end_ns > lo and e.start_ns < hi for e in devices[name])]
    n_modules = None
    if used:
        n_modules = sum(1 for e in modules.get(used[0], ())
                        if lo <= e.start_ns < hi)
    return Split(
        median_s={k: statistics.median(v) * 1e-9
                  for k, v in sorted(durations.items())},
        call_self_s=statistics.median(self_ns) * 1e-9 if self_ns else None,
        calls=calls, modules=n_modules)


def reduce_profile(profile) -> Split:
    devices, host = tracing.extract(profile)
    spans, modules = extract(profile)
    return summarize(spans, modules, devices, tracing.window_of(host))
