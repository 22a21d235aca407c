"""Mean per flow batch of the pipeline's host work: the PhaseTracing
self time of every phase but host_sync (which absorbs device time)."""

from benchmark.metrics._lib import flow_traces, mean_phase_ms


def read(r):
    return mean_phase_ms(flow_traces(r), exclude={"host_sync"})
