"""Per-layer readers of the program's counters: None where the program
has no such counter, the quotient where it has."""

import types

import pytest

from benchmark import run

FLOWS = "cilium_tpu_proxymap_handoff_flows_total"
RESOLVES = "cilium_tpu_proxymap_handoff_resolves_total"


def _readings(counters):
    return types.SimpleNamespace(counters=counters)


@pytest.mark.parametrize("counters", [
    {},                                   # a program without the families
    {FLOWS: {}, RESOLVES: {}},            # registered, never incremented
    {FLOWS: {(): 0.0}, RESOLVES: {(): 0.0}},
], ids=["absent", "empty", "zero"])
def test_redirect_resolve_pct_none_without_handoffs(counters):
    assert run.read_metric("redirect_resolve_pct.newflows", _readings(counters)) is None


def test_redirect_resolve_pct_reads_resolves_over_flows():
    r = _readings({FLOWS: {(): 1400.0}, RESOLVES: {(): 350.0}})
    assert run.read_metric("redirect_resolve_pct.newflows", r) == pytest.approx(25.0)
