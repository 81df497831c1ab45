"""Median host time of the program's decode call (``xla.call`` spans with
``kind=decode`` of ``XLADeployment``), in a traced run's window; the call
returns before the device has finished."""
from bench.harness.readers import median_ms


def read(run):
    trace = run.stats.get("obs")
    if trace is None:
        return None
    return median_ms([s.duration for s in trace.spans
                      if s.name == "xla.call"
                      and s.attrs.get("kind") == "decode"])
