"""Arithmetic shared by the per-layer metric readers in ``bench/metrics/``.

Each reader takes the finished ``core.Run`` of a traced run and returns
a number, or None where there is nothing to read (then the metric is
left out of the line; a share of a roofline or a peak is never 0 by
default).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench import counts


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """Percent of the roofline: the kernel's ops and bytes in the traced
    window (calls seen x per-call counts from the design's shapes) at the
    chip's int8 and HBM peaks, over its summed device time."""
    s = run.summary
    if s is None or kernel not in s.kernel_s or not run.peaks:
        return None
    per = counts.per_dispatch(run.config, int(run.traffic["batch"]))
    if kernel not in per:
        return None
    row = per[kernel]
    dispatches = s.kernel_calls[kernel] / row["calls"]
    share, _ = counts.roofline_share(row["ops"] * dispatches,
                                     row["bytes"] * dispatches,
                                     s.kernel_s[kernel], run.peaks)
    return share


def idle_percent(run) -> Optional[float]:
    s = run.summary
    if s is None or s.n_devices == 0 or s.window_s <= 0:
        return None
    return 100.0 * s.idle_share


def mfu_percent(run) -> Optional[float]:
    """Emulated operations per second (OP = 2 x MAC of the design, per
    window) over the chips' int8 peak, in percent."""
    st = run.stats
    if not run.peaks or not st.get("windows") or not st.get("elapsed_s"):
        return None
    rate = counts.ops_per_window(run.config) * st["windows"] / st["elapsed_s"]
    return 100.0 * rate / (run.cell.chips * run.peaks["int8_ops_per_s"])


def median_ms(durations) -> Optional[float]:
    if not durations:
        return None
    return 1e3 * float(np.median(durations))
