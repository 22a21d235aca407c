"""Mean per flow batch of the pipeline's host time outside every
phase: the enqueue and complete halves' wall time less the phases'
self time (the per-flow redirect loop, queue admission, completion
bookkeeping)."""

from benchmark.metrics._host import glue_ms
from benchmark.metrics._lib import flow_traces


def read(r):
    return glue_ms(flow_traces(r))
