"""Percent of the traced window in which no operation ran on the device,
averaged over the chips used."""
from bench.harness.readers import idle_percent


def read(run):
    return idle_percent(run)
