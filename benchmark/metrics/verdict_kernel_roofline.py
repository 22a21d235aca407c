"""The verdict kernels' share of their roofline: the least time the
chip could take for the flows they were given (benchmark/roofline.py,
bytes over HBM peak) over the device time of the process_flows*
programs in the trace. Bound by memory bandwidth: the kernels do no
arithmetic worth counting."""

from benchmark.metrics._lib import counter, flow_traces
from benchmark.roofline import floor_seconds


def read(r):
    if r.trace is None or not r.peaks:
        return None
    kernel_s = sum(s for n, s in r.trace.modules_s.items() if "process_flows" in n)
    if kernel_s <= 0:
        return None
    padded = {4: 0.0, 6: 0.0}
    for t in flow_traces(r):
        padded[4 if t["kind"].startswith("v4") else 6] += t["notes"].get("padded", 0)
    live = {f: max(0.0, n - counter(r, "cilium_tpu_dispatch_pad_lanes_total",
                                    family=f"v{f}"))
            for f, n in padded.items()}
    floor = floor_seconds(live, r.table_shapes, r.peaks["hbm_bytes_per_s"])
    return None if floor is None else 100.0 * floor / kernel_s
