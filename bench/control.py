#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's, and the
control's, on the chip at the cell's own size.

    python3 bench/control.py --workload lstm-bulk --seeds 1,2,3 \\
        --program-seeds 4,5,6,7,8,9,10,11,12,13,14,15 --seconds 3

For every seed of ``--program-seeds`` a run of the program, and for
every seed of ``--seeds`` a run with the control in the program's place
(``bench/harness/substitutes.py``: the plain reference at int4 weights),
each a short window at the cell's own load, all in one process. One line
per run with each number compared; the last line is the table as JSON.
The program's largest reading is the lower end of each limit, the
control's smallest the upper end (PERF.md section 2).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as bench_run  # noqa: E402
from bench.harness import spec, substitutes  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="", help="seeds of control runs")
    ap.add_argument("--program-seeds", default="",
                    help="seeds of program runs")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = bench_run.devices_for(cell.chips)
    except bench_run.NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    bench_run._enable_cache()
    table = []
    plan = [("program", s, None) for s in _seeds(args.program_seeds)] + \
        [("control", s, substitutes.control) for s in _seeds(args.seeds)]
    for what, seed, wrap in plan:
        out = bench_run.run_cell(cell, seed=seed, seconds=args.seconds,
                                 trace=False, devices=devices, wrap=wrap)
        row = {"run": what, "seed": seed, "correct": out["correct"],
               "attempted": out["attempted"],
               **{k: v["value"] for k, v in out["checks"].items()}}
        table.append(row)
        print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
