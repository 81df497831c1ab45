"""The comparison that decides ``correct``.

The emulator's contract is exact integer equality with the design's
semantics (DESIGN.md section 4), so every number compared here is a
count whose limit is 0: output codes that differ from the plain
reference, and answers that were due and never came. The readings each
limit was set from are in PERF.md.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: number -> limit; each reads 0 on every seed of the program and far
#: above 0 under the control (PERF.md section 2)
LIMITS = {"mismatched_codes": 0, "missing_answers": 0}


def codes(outputs_f: np.ndarray, config: dict) -> np.ndarray:
    """Output codes of a float result at the design's output format."""
    frac = int(config["formats"]["state_fmt"][1])
    return np.round(np.asarray(outputs_f, np.float64)
                    * 2.0 ** frac).astype(np.int64)


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    got = np.asarray(got, np.int64)
    want = np.asarray(want, np.int64)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def verdict(values: Dict[str, int]) -> Tuple[bool, Dict[str, dict]]:
    """``(correct, {name: {"value", "limit"}})`` in a fixed order."""
    out = {k: {"value": int(values[k]), "limit": LIMITS[k]} for k in LIMITS}
    return all(v["value"] <= v["limit"] for v in out.values()), out
