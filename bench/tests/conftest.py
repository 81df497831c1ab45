"""Shared set-up of the benchmark's own tests (CPU, small sizes)."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: per traffic kind, the overrides that shrink a mix to what a CPU test
#: holds (given the mix's own parameters); the code path is the cell's own
SMALL = {
    "bulk": lambda tr: {"batch": min(int(tr["batch"]), 32), "ring": 2},
}


@pytest.fixture
def small():
    """``small(cell)``: the overrides for the cell's traffic."""
    return lambda cell: SMALL[cell.traffic["kind"]](cell.traffic)
