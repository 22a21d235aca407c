"""Mean conntrack probe chain of the CT pre-pass over the window:
conntrack_probe_rounds_total{op=lookup} / conntrack_lookups_total{op=lookup}
(16 is the cap, walked when the table is full)."""

from benchmark.metrics._lib import counter


def read(r):
    lookups = counter(r, "cilium_tpu_conntrack_lookups_total", op="lookup")
    probes = counter(r, "cilium_tpu_conntrack_probe_rounds_total", op="lookup")
    return probes / lookups if lookups > 0 else None
