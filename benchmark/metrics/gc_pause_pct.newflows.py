"""Share of the window the Python garbage collector stopped the node:
gc_pause_seconds_total over the window ÷ the window."""

from benchmark.metrics._host import gc_pause_pct


def read(r):
    return gc_pause_pct(r)
