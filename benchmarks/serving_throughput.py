"""Serving-farm throughput benchmark -> BENCH_serving.json.

Drives the seeded mixed lstm+conv1d tape (``repro.serving.loadgen``)
through three farm configurations and records the acceptance figures:

* ``steady_state`` — max_batch=128, wave=512: a warm pass compiles every
  ``(B, L, F)`` program, then a second identical pass measures pure
  scheduling + dispatch (the per-run report only counts its own requests,
  so compile time never pollutes the tail). Gate: sustained >= 10k
  windows/s on CPU with a bounded p99.
* ``batch32`` — max_batch=32, wave=128: the batch-32-equivalent load the
  speedup criterion is defined at.
* ``unbatched`` — max_batch=1, pad_batch=False: every window is its own
  dispatch. Gate: batch-32 throughput >= 5x this.
"""
from __future__ import annotations

import dataclasses
import json

from repro.obs import MetricsRegistry
from repro.serving import FarmConfig
from repro.serving.loadgen import TrafficSpec, build_farm, run_loadgen

ARCHS = ("lstm", "conv1d")


def _measure(max_batch: int, pad_batch: bool, spec: TrafficSpec,
             *, replicas: int = 2, seed: int = 0) -> dict:
    """Warm pass (compile), then one timed pass on the same farm."""
    farm, pools = build_farm(
        ARCHS, replicas=replicas, seed=seed,
        cfg=FarmConfig(max_batch=max_batch, pad_batch=pad_batch),
        metrics=MetricsRegistry())
    run_loadgen(farm, pools, spec)               # warm: compile programs
    return run_loadgen(farm, pools, spec)        # steady state


def run(out: str = "BENCH_serving.json", *, requests: int = 4096,
        seed: int = 0) -> dict:
    spec = TrafficSpec(archs=ARCHS, n_requests=requests, wave=512,
                       seed=seed)
    steady = _measure(128, True, spec, seed=seed)
    b32 = _measure(32, True, dataclasses.replace(spec, wave=128),
                   seed=seed)
    # the unbatched pass is ~20x slower per window; a quarter of the tape
    # gives a stable rate without dominating the benchmark's wall time
    unb = _measure(1, False,
                   dataclasses.replace(spec, wave=128,
                                       n_requests=max(256, requests // 4)),
                   seed=seed)

    tput = steady["throughput_windows_per_s"] or 0.0
    tput32 = b32["throughput_windows_per_s"] or 0.0
    tput1 = unb["throughput_windows_per_s"] or 0.0
    report = {
        "config": {"archs": list(ARCHS), "requests": requests,
                   "replicas": 2, "seed": seed,
                   "steady_state": {"max_batch": 128, "wave": 512},
                   "batch32": {"max_batch": 32, "wave": 128},
                   "unbatched": {"max_batch": 1, "pad_batch": False}},
        "steady_state": steady,
        "batch32": {
            "throughput_windows_per_s": tput32,
            "latency_p99_s": b32["latency_p99_s"]},
        "unbatched": {
            "throughput_windows_per_s": tput1,
            "latency_p99_s": unb["latency_p99_s"]},
        "speedup_batch32_vs_unbatched": tput32 / tput1 if tput1 else None,
        "speedup_steady_vs_unbatched": tput / tput1 if tput1 else None,
        "meets_10k_windows_per_s": tput >= 10_000,
        "meets_5x_speedup": tput1 > 0 and tput32 / tput1 >= 5.0,
    }
    if out:
        with open(out, "w", encoding="utf-8") as f:
            f.write(json.dumps(report, indent=2, sort_keys=True) + "\n")

    print(f"steady state (batch 128): {tput:,.0f} windows/s  "
          f"p50/p99 {steady['latency_p50_s']*1e3:.2f}/"
          f"{steady['latency_p99_s']*1e3:.2f} ms  "
          f"dropped={steady['dropped_after_admission']}")
    for fam, d in sorted(steady["per_design"].items()):
        print(f"  {fam}: {d['done']} done, {d['gop_per_j']:.2f} GOP/J")
    print(f"batch 32: {tput32:,.0f} windows/s;  unbatched: "
          f"{tput1:,.0f} windows/s  -> speedup x{tput32 / tput1:.1f} "
          f"(steady x{tput / tput1:.1f})")
    print(f"gates: >=10k win/s {report['meets_10k_windows_per_s']}  "
          f">=5x vs unbatched {report['meets_5x_speedup']}")
    return report


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    run()
