"""The benchmark's ``flow_transfers_per_batch.http`` and ``.newflows``
readers on hand-made counter readings: None where the program counts no
transfer or served no pipeline batch, else (h2d + d2h) /
datapath_batches_total{path=pipeline}."""

import types

import pytest

from benchmark import run

METRICS = ["flow_transfers_per_batch.http", "flow_transfers_per_batch.newflows"]
XFER = "cilium_tpu_device_transfers_total"
BATCHES = "cilium_tpu_datapath_batches_total"


def _key(**labels):
    return tuple(sorted(labels.items()))


def _readings(counters):
    return types.SimpleNamespace(counters=counters)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("counters", [
    {},                                              # neither family
    {BATCHES: {_key(path="pipeline"): 40.0}},        # an untraced run: batches, no transfer
    {XFER: {}, BATCHES: {}},                         # registered, never incremented
    {XFER: {_key(direction="h2d"): 0.0, _key(direction="d2h"): 0.0},
     BATCHES: {_key(path="pipeline"): 40.0}},
    {XFER: {_key(direction="h2d"): 3.0, _key(direction="d2h"): 3.0},
     BATCHES: {_key(path="engine"): 5.0}},           # no pipeline batch to divide by
], ids=["absent", "untraced", "empty", "zero", "no-batch"])
def test_none_without_transfers_or_batches(metric, counters):
    assert run.read_metric(metric, _readings(counters)) is None


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("h2d,d2h,batches,want", [
    (40.0, 40.0, 40.0, 2.0),       # one packed chunk a batch
    (200.0, 120.0, 40.0, 8.0),     # the per-array formula on 1.5 chunks: 5 up, 3 down
    (60.0, 60.0, 40.0, 3.0),       # half the batches in two chunks
    (320.0, 80.0, 40.0, 10.0),     # two chunks on the 2x2 plan: 4 up, 1 down each
])
def test_reads_transfers_over_pipeline_batches(metric, h2d, d2h, batches, want):
    r = _readings({
        XFER: {_key(direction="h2d"): h2d, _key(direction="d2h"): d2h},
        BATCHES: {_key(path="pipeline"): batches, _key(path="engine"): 999.0},
    })
    assert run.read_metric(metric, r) == pytest.approx(want)
