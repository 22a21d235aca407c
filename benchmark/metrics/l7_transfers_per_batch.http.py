"""Host-device array transfers per HTTP batch of the fused L7 walk over
the window: (l7_device_transfers_total{direction=h2d} + {direction=d2h})
/ l7_batches_total, both with parser=http. None where the program
counts no such transfers or walked no batch."""

from benchmark.metrics._lib import counter


def read(r):
    name = "cilium_tpu_l7_device_transfers_total"
    moved = (counter(r, name, direction="h2d", parser="http")
             + counter(r, name, direction="d2h", parser="http"))
    batches = counter(r, "cilium_tpu_l7_batches_total", parser="http")
    return moved / batches if moved > 0 and batches > 0 else None
