"""Share of new conntrack entries dropped for want of a free slot over
the window: inserts_total{result=dropped} / all inserts_total."""

from benchmark.metrics._lib import counter


def read(r):
    name = "cilium_tpu_conntrack_inserts_total"
    dropped = counter(r, name, result="dropped")
    tried = dropped + counter(r, name, result="inserted")
    return 100.0 * dropped / tried if tried > 0 else None
