"""Emulated operations per second of the whole step (OP = 2 x MAC per
window, bench/counts.py) over the chips' int8 peak, in percent."""
from bench.harness.readers import mfu_percent


def read(run):
    return mfu_percent(run)
