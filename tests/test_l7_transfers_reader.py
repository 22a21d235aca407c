"""The benchmark's ``l7_transfers_per_batch.http`` reader on hand-made
counter readings: None where the program counts no L7 transfers or
walked no HTTP batch, else (h2d + d2h) / batches of the http parser."""

import types

import pytest

from benchmark import run

METRIC = "l7_transfers_per_batch.http"
XFER = "cilium_tpu_l7_device_transfers_total"
BATCHES = "cilium_tpu_l7_batches_total"


def _key(**labels):
    return tuple(sorted(labels.items()))


def _readings(counters):
    return types.SimpleNamespace(counters=counters)


@pytest.mark.parametrize("counters", [
    {},                                           # a program without either family
    {BATCHES: {_key(parser="http"): 40.0}},       # the parent: batches, no transfer counter
    {XFER: {}, BATCHES: {}},                      # registered, never incremented
    {XFER: {_key(direction="h2d", parser="http"): 0.0,
            _key(direction="d2h", parser="http"): 0.0},
     BATCHES: {_key(parser="http"): 0.0}},
    {XFER: {_key(direction="h2d", parser="http"): 3.0},
     BATCHES: {_key(parser="http"): 0.0}},        # no batch: nothing to divide by
], ids=["absent", "parent", "empty", "zero", "no-batch"])
def test_none_without_transfers_or_batches(counters):
    assert run.read_metric(METRIC, _readings(counters)) is None


@pytest.mark.parametrize("h2d,d2h,batches,want", [
    (40.0, 40.0, 40.0, 2.0),      # one chunk a batch: the packed walk
    (120.0, 80.0, 40.0, 5.0),     # three uploads and two pulls a batch
    (45.0, 45.0, 40.0, 2.25),     # some batches past 16,384 lanes
])
def test_reads_transfers_over_http_batches(h2d, d2h, batches, want):
    r = _readings({
        XFER: {_key(direction="h2d", parser="http"): h2d,
               _key(direction="d2h", parser="http"): d2h,
               _key(direction="h2d", parser="kafka"): 999.0,
               _key(direction="d2h", parser="kafka"): 999.0},
        BATCHES: {_key(parser="http"): batches, _key(parser="kafka"): 7.0},
    })
    assert run.read_metric(METRIC, r) == pytest.approx(want)
