"""Share of the window's redirected flows whose peer the proxymap
hand-off resolved (address string and ipcache lookup), in %:
proxymap_handoff_resolves_total / proxymap_handoff_flows_total. Each
distinct peer of a batch is resolved once, so 100% means no batch
carried a peer twice."""

from benchmark.metrics._lib import counter


def read(r):
    flows = counter(r, "cilium_tpu_proxymap_handoff_flows_total")
    resolves = counter(r, "cilium_tpu_proxymap_handoff_resolves_total")
    return 100.0 * resolves / flows if flows > 0 else None
