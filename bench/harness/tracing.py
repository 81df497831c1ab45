"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

A traced run writes an XSpace (``*.xplane.pb``). This module reads it with
``jax.profiler.ProfileData`` and reduces it, in one place, to:

* ``busy_s``: per device, the union of the intervals in which an XLA
  operation ran, inside the traced window, averaged over the devices
  that ran anything;
* per-kernel device time: the summed durations of the custom calls whose
  instruction name matches a kernel's pattern (``counts.KERNELS``);
* the operations that took most device time, by instruction name;
* idle gaps of the first used device, each labelled by the benchmark's
  own host annotation (``bench.call``, ``bench.fetch``) that overlaps it
  most, ``other`` where the host was outside them.

Times are nanoseconds on the profiler's clock, on which the host
annotations and the device operations are aligned.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: device planes of the TPU runtime, one per chip
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: the harness's window annotation, and the prefix of its other spans
WINDOW = "bench.window"
HOST_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """The HLO instruction's name: a TPU trace names an operation by
        its whole instruction, ``%name = type op(operands), ...``."""
        head = self.name.split(" = ", 1)[0] if " = " in self.name \
            else self.name
        return head.lstrip("%")

    @property
    def is_custom_call(self) -> bool:
        return " custom-call(" in self.name


@dataclass
class Summary:
    window_s: float
    busy_s: float                        # averaged over used devices
    n_devices: int
    kernel_s: Dict[str, float] = field(default_factory=dict)   # all devices
    kernel_calls: Dict[str, int] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {found}")
    return found[0]


def extract(profile) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """``(device ops by plane, host bench annotations)`` of a
    ``ProfileData``."""
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend(Event(e.name, float(e.start_ns), float(e.duration_ns))
                        for line in plane.lines for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return devices, host


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(evs: Sequence[Event], lo: float, hi: float) -> List[Tuple[float,
                                                                     float]]:
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in evs
            if e.end_ns > lo and e.start_ns < hi]


def window_of(host: Sequence[Event]) -> Tuple[float, float]:
    wins = [e for e in host if e.name == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} annotation, found "
                           f"{len(wins)}")
    return wins[0].start_ns, wins[0].end_ns


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host: Sequence[Event]) -> List[Tuple[str, float]]:
    """Idle seconds per host activity: each gap goes to the benchmark span
    that overlaps it most (``other`` where none does), totals sorted.
    The benchmark's spans run one after another on one thread, so sorted
    by start they are sorted by end too."""
    spans = sorted((e for e in host if e.name != WINDOW),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    totals: Dict[str, float] = {}
    for a, b in gaps:
        best, best_ov = "other", 0.0
        j = bisect.bisect_left(starts, b) - 1
        while j >= 0 and spans[j].end_ns > a:
            ov = min(b, spans[j].end_ns) - max(a, spans[j].start_ns)
            if ov > best_ov:
                best, best_ov = spans[j].name, ov
            j -= 1
        totals[best] = totals.get(best, 0.0) + (b - a) * 1e-9
    return sorted(totals.items(), key=lambda kv: -kv[1])


def summarize(devices: Dict[str, List[Event]], host: Sequence[Event],
              kernels: Dict[str, str], *,
              window: Optional[Tuple[float, float]] = None,
              top: int = 10) -> Summary:
    """Reduce extracted events over the window (the ``bench.window``
    annotation unless given)."""
    lo, hi = window if window is not None else window_of(host)
    pats = {k: re.compile(p) for k, p in kernels.items()}
    busy, per_op = [], {}
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    first_gaps: List[Tuple[float, float]] = []
    used = 0
    for name in sorted(devices):
        evs = [e for e in devices[name] if e.end_ns > lo and e.start_ns < hi]
        if not evs:
            continue
        used += 1
        merged = union(_clip(evs, lo, hi))
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        for e in evs:
            d = (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
            short = e.short
            per_op[short] = per_op.get(short, 0.0) + d
            if not e.is_custom_call:
                continue
            for k, p in pats.items():
                if p.search(short):
                    kernel_s[k] += d
                    kernel_calls[k] += 1
        if used == 1:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            first_gaps = [(edges[j], edges[j + 1])
                          for j in range(0, len(edges), 2)
                          if edges[j + 1] > edges[j]]
    n = max(used, 1)
    ops = sorted(((k, v / n) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / n, n_devices=used,
                   kernel_s={k: v for k, v in kernel_s.items() if v > 0},
                   kernel_calls={k: v for k, v in kernel_calls.items() if v},
                   top_ops=ops,
                   idle_gaps=label_gaps(first_gaps, host)[:top])


def reduce_profile(profile, kernels: Dict[str, str]) -> Summary:
    devices, host = extract(profile)
    return summarize(devices, host, kernels)


def reduce_dir(log_dir: str, kernels: Dict[str, str]) -> Summary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(xplane_file(log_dir)),
                          kernels)
