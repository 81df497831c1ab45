#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and per-layer metric readers are files under
``bench/`` found by name (``bench/harness/spec.py``). A run builds the
system from the seed, warms every shape the mix uses (set-up), measures
for ``--seconds``, then compares what the window produced with the plain
reference and prints one JSON line as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, last,
``checks`` (each number compared, with its limit). With ``--trace 1``
the metrics are the cell's per-layer metrics, read from a profiler trace
of the window, and the line carries ``breakdown``.

Without a TPU, or with fewer chips than the cell asks for, nothing runs
and the exit code is 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import counts  # noqa: E402
from bench.harness import check, core, spec, tracing  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


#: the persistent compilation cache: inside the checkout, at a fixed path
#: (the path is part of the cache's key), whatever the environment says
CACHE_DIR = ROOT / ".jax_cache"


def _enable_cache() -> str:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program of a cell is small: keep all of them, not only those
    # that took a second to compile, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: a size limit from the environment turns on JAX's LRU
    # bookkeeping, whose scan fails on an entry written without its
    # access-time file, and the entry is then not written at all
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices=None, wrap=None, traffic_overrides=None,
             t_start: float = None) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``devices`` are the chips to report (None: no device numbers, for
    runs on the CPU in tests); ``wrap`` replaces the system under test
    (``core.Run``).
    """
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    run = core.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                   spans=core.Spans(profiling=trace), wrap=wrap,
                   traffic_overrides=dict(traffic_overrides or {}))
    if trace:
        run.seconds = min(seconds, float(run.traffic["trace_seconds"]))
        if devices is not None:
            run.peaks = spec.peaks_for(devices[0].device_kind)
    state = cell.kind.setup(run)
    setup_s = time.perf_counter() - t_start

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no Python call events
        opts.host_tracer_level = 1         # annotations, not runtime detail
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    compiles = core.CompileCounter().start()
    try:
        if trace:
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                e2e, kept = cell.kind.window(run, state)
        else:
            e2e, kept = cell.kind.window(run, state)
    finally:
        n_compiles = compiles.stop()
        if trace:
            jax.profiler.stop_trace()
    try:
        if trace:
            run.summary = tracing.reduce_dir(log_dir, counts.KERNELS)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    device = {}
    if devices is not None:
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": _memory_peak(devices)}
        if trace:
            device.update(busy_s=run.summary.busy_s,
                          window_s=run.summary.window_s)
    pl = cell.kind.payload(state)
    del state
    gc.collect()
    values = cell.kind.check_outputs(run, pl, kept)
    correct, checks = check.verdict(values)

    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    out = {"correct": correct, "attempted": int(values["attempted"]),
           "failed": int(values["failed"]), "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.summary.top_ops],
            "idle_gaps": [[k, v] for k, v in run.summary.idle_gaps]}
    out["checks"] = checks
    print(f"bench: compiles in window: {n_compiles}; {run.seconds} s "
          f"window read {json.dumps(e2e)}", file=sys.stderr)
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        devices = devices_for(cell.chips)
    except (spec.SpecError, NoChip) as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(f"bench: compile cache {_enable_cache()}", file=sys.stderr)
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices,
                   t_start=T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
