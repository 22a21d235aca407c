"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), from the profiler trace."""

from benchmark.metrics._lib import idle_pct


def read(r):
    return idle_pct(r)
