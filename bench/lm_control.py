#!/usr/bin/env python3
"""Readings that set the tolerances of ``correct`` in a cell whose kind
brings its own control (``lm_decode``): the program's, and the control's,
on the chip at the cell's own size.

    python3 bench/lm_control.py --workload zamba2-decode \\
        --program-seeds 11,12,13,14,15,16 --seeds 21,22,23 --seconds 10

For every seed of ``--program-seeds`` a run of the program, and for every
seed of ``--seeds`` a run with the kind's ``control`` in the program's
place (the plain reference at float8 weights), all in one process. One
line per run: ``correct``, each number compared (and its two parts: logits
outside the tolerance, greedy picks that disagree), per rtol the largest
``|got - want| - rtol |want|`` over the kept logits, the sessions' start
positions and the resets in the window; the last line is the table as
JSON. The program's largest excess is the lower end of the atol
at each rtol, the control's the upper end (PERF.md section 2).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as bench_run  # noqa: E402
from bench.harness import spec  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="", help="seeds of control runs")
    ap.add_argument("--program-seeds", default="",
                    help="seeds of program runs")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        devices = bench_run.devices_for(cell.chips)
    except (spec.SpecError, bench_run.NoChip) as e:
        print(f"lm_control: {e}; nothing was run", file=sys.stderr)
        return 2
    bench_run._enable_cache()
    stats = {}

    def check_outputs(run, pl, kept):          # keeps each run's readings
        values = kind.check_outputs(run, pl, kept)
        stats.update(run.stats)
        return values

    kind = cell.kind
    cell = dataclasses.replace(cell, kind=types.SimpleNamespace(
        **{**vars(kind), "check_outputs": check_outputs}))
    table = []
    plan = [("program", s, None) for s in _seeds(args.program_seeds)] + \
        [("control", s, kind.control) for s in _seeds(args.seeds)]
    for what, seed, wrap in plan:
        stats.clear()
        out = bench_run.run_cell(cell, seed=seed, seconds=args.seconds,
                                 trace=False, devices=devices, wrap=wrap)
        row = {"run": what, "seed": seed, "correct": out["correct"],
               "attempted": out["attempted"],
               **{k: v["value"] for k, v in out["checks"].items()},
               "excess": stats.get("logit_excess"),
               **{k: stats.get(k) for k in ("bad_logits", "bad_picks",
                                            "resets", "start_positions")},
               "memory_peak_bytes": out["device"]["memory_peak_bytes"],
               **{k: v["value"] for k, v in out["metrics"].items()}}
        table.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
