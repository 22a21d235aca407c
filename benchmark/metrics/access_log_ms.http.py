"""Mean per HTTP batch of the proxy's access_log span: one LogRecord
per request in Proxy.check_http."""

from benchmark.metrics._host import proxy_traces
from benchmark.metrics._lib import mean_phase_ms


def read(r):
    return mean_phase_ms(proxy_traces(r), phases={"access_log"})
