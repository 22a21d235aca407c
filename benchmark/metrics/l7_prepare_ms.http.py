"""Mean per HTTP batch of the L7 pipeline's prepare span (requests to
uint8 lanes)."""

from benchmark.metrics._lib import l7_traces, mean_phase_ms


def read(r):
    return mean_phase_ms(l7_traces(r), phases={"prepare"})
