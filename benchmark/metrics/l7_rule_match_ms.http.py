"""Mean per HTTP batch of the proxy's rule_match span: the requests ×
rules loop of HTTPPolicy.check_batch."""

from benchmark.metrics._host import proxy_traces
from benchmark.metrics._lib import mean_phase_ms


def read(r):
    return mean_phase_ms(proxy_traces(r), phases={"rule_match"})
