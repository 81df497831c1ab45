"""Median host time of the benchmark's call into the deployment or
emulator, which returns before the device has finished."""
from bench.harness.readers import median_ms


def read(run):
    return median_ms(run.spans.durations.get("bench.call"))
