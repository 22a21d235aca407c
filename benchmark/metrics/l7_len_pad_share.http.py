"""Share of the L7 walk's length lanes that were padding over the
window: l7_pad_lanes_total{kind=len_bytes} / (len_bytes + len_bytes_live)."""

from benchmark.metrics._lib import counter


def read(r):
    name = "cilium_tpu_l7_pad_lanes_total"
    pad = counter(r, name, kind="len_bytes")
    live = counter(r, name, kind="len_bytes_live")
    return 100.0 * pad / (pad + live) if pad + live > 0 else None
