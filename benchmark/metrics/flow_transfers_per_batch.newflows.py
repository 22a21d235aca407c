"""Host-device array transfers per flow batch of the datapath pipeline
over the window of a flows cell:
(device_transfers_total{direction=h2d} + {direction=d2h}) /
datapath_batches_total{path=pipeline}. The program counts transfers on
traced dispatches only. None where it counts no transfer or served no
batch."""

from benchmark.metrics._lib import counter


def read(r):
    moved = (counter(r, "cilium_tpu_device_transfers_total", direction="h2d")
             + counter(r, "cilium_tpu_device_transfers_total", direction="d2h"))
    batches = counter(r, "cilium_tpu_datapath_batches_total", path="pipeline")
    return moved / batches if moved > 0 and batches > 0 else None
