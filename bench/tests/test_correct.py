"""The comparison that decides ``correct`` fails where it must.

Each cell is driven through the harness (set-up, window, check) with the
chip check skipped and the traffic shrunk: with the plain reference in
the program's place it reads correct; with the control (the reference at
int4 weights) or with each fault the cell can have planted under the
timed path, it reads not correct. A few runs drive the program itself,
in Pallas interpret mode.
"""
from __future__ import annotations

import functools

import pytest

from bench import run as bench_run
from bench.harness import spec, substitutes

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]



def faults(name):
    """The faults a cell can have (PERF.md section 2): an altered answer
    always, half of the batch left out where a batch has two windows."""
    batch = int(spec.load_cell(name).traffic["batch"])
    return ("altered", "half") if batch > 1 else ("altered",)


def _run(name, small, wrap, seed=2 ** 31 + 5, seconds=0.3):
    cell = spec.load_cell(name)
    return bench_run.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                              wrap=wrap, traffic_overrides=small(cell))


def _planted(fault):
    def wrap(fn, **kw):
        return substitutes.FAULTS[fault](substitutes.reference(fn, **kw),
                                         **kw)
    return wrap


@pytest.mark.parametrize("name", CELLS)
def test_reference_in_place_reads_correct(name, small):
    out = _run(name, small, substitutes.reference)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_not_correct(name, small):
    out = _run(name, small, substitutes.control)
    assert not out["correct"]
    assert out["checks"]["mismatched_codes"]["value"] > 0


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in CELLS for f in faults(c)])
def test_fault_reads_not_correct(name, fault, small):
    out = _run(name, small, _planted(fault))
    assert not out["correct"], (fault, out["checks"])


@pytest.mark.parametrize("name", CELLS)
def test_program_reads_correct_and_an_altered_answer_does_not(name, small):
    out = _run(name, small, None)
    assert out["correct"], out["checks"]
    bad = _run(name, small, functools.partial(substitutes.altered))
    assert not bad["correct"]
    assert bad["checks"]["mismatched_codes"]["value"] > 0
