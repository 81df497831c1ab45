#!/usr/bin/env python3
"""Split a cell's deployment call into the program's own stages, on the chip.

    python3 bench/call_split.py --workload lstm-b1 --seed 7 --pairs 2

Sets the cell up from the seed as ``bench/run.py`` does, then traces
``--pairs`` pairs of windows of the cell's ``trace_seconds``, as a
``--trace 1`` run traces its window: one window without the program's
spans, one with ``repro.obs.Tracer(profiler=True)`` installed, the order
alternating from pair to pair. Per window it prints the rate, the median
``bench.call`` (what ``issue_ms.bulk`` reads), the device idle share and,
with the program's spans, the median of each ``rtl.`` span, the self time
of ``rtl.call`` and the programs per call
(``bench/harness/program_spans.py``). The last line is JSON: the medians
over the windows of each kind, the cost of the program's spans (the rate
lost against the windows without them), and the four stages' sum over
the median ``bench.call`` of the same windows.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import statistics
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import counts  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.harness import core, program_spans, spec, tracing  # noqa: E402

#: the stages a deployment call's time divides into
STAGES = ("rtl.emulator.quantize", "rtl.emulator.dispatch",
          "rtl.emulator.unpack")


def traced_window(run, state, *, program: bool) -> dict:
    """One traced window; returns its row."""
    import jax
    from jax.profiler import ProfileData

    from repro import obs

    run.spans = core.Spans(profiling=True)
    log_dir = tempfile.mkdtemp(prefix="bench-split-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    prev = obs.set_tracer(obs.Tracer(profiler=True)) if program else None
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            e2e, kept = run.cell.kind.window(run, state)
    finally:
        jax.profiler.stop_trace()
        if program:
            obs.set_tracer(prev)
    try:
        profile = ProfileData.from_file(tracing.xplane_file(log_dir))
        summary = tracing.reduce_profile(profile, counts.KERNELS)
        split = program_spans.reduce_profile(profile)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    checked = run.cell.kind.check_outputs(run, run.cell.kind.payload(state),
                                          kept)
    row = {"program_spans": program,
           "windows_per_s": e2e["windows_per_s"],
           "issue_ms": 1e3 * statistics.median(
               run.spans.durations["bench.call"]),
           "idle_pct": 100.0 * summary.idle_share,
           "mismatched_codes": checked["mismatched_codes"]}
    if program:
        row.update({f"{k}_ms": 1e3 * v for k, v in split.median_s.items()},
                   call_self_ms=(None if split.call_self_s is None
                                 else 1e3 * split.call_self_s),
                   calls=split.calls, modules=split.modules,
                   programs_per_call=split.programs_per_call)
    return row


def summarize(rows) -> dict:
    """Medians over the windows of each kind, and what they give."""
    def med(key, program):
        vals = [r[key] for r in rows
                if r["program_spans"] == program and r.get(key) is not None]
        return statistics.median(vals) if vals else None

    out = {"windows_per_s": med("windows_per_s", False),
           "windows_per_s_spans": med("windows_per_s", True),
           "issue_ms": med("issue_ms", False),
           "issue_ms_spans": med("issue_ms", True),
           "idle_pct": med("idle_pct", False),
           "programs_per_call": med("programs_per_call", True),
           "call_self_ms": med("call_self_ms", True),
           "mismatched_codes": sum(r["mismatched_codes"] for r in rows)}
    for k in ("rtl.call",) + STAGES:
        out[f"{k}_ms"] = med(f"{k}_ms", True)
    out["spans_cost_pct"] = 100.0 * (
        1.0 - out["windows_per_s_spans"] / out["windows_per_s"])
    parts = [out[f"{k}_ms"] for k in STAGES] + [out["call_self_ms"]]
    if None not in parts:
        out["stages_ms"] = sum(parts)
        out["stages_over_issue"] = out["stages_ms"] / out["issue_ms_spans"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=2)
    args = ap.parse_args(argv)
    if args.pairs < 1:
        ap.error("--pairs must be at least 1")
    cell = spec.load_cell(args.workload)
    try:
        bench_run.devices_for(cell.chips)
    except bench_run.NoChip as e:
        print(f"call_split: {e}; nothing was run", file=sys.stderr)
        return 2
    bench_run._enable_cache()
    run = core.Run(cell=cell, seed=args.seed, seconds=0.0, trace=True)
    run.seconds = float(run.traffic["trace_seconds"])
    state = cell.kind.setup(run)
    rows = []
    for k in range(args.pairs):
        for program in ((False, True) if k % 2 == 0 else (True, False)):
            rows.append(traced_window(run, state, program=program))
            print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      **summarize(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
