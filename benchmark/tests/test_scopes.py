"""The program's names in a trace: idle gaps named by the innermost
``policyd.*`` span, device time by named scope, and agreement with
``benchmark.tracefile`` on the recorded fixture."""

import os

import pytest

from benchmark import scopes
from benchmark.tracefile import WINDOW, find_xplane, reduce_xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")
MS = 1_000_000


def test_gaps_named_by_innermost_program_span():
    host = [(0, 100 * MS, WINDOW),
            (0, 50 * MS, "bench.submit"),
            (0, 45 * MS, "policyd.v4-ingress.enqueue"),
            (10 * MS, 30 * MS, "policyd.v4-ingress.ct_prepass"),
            (20 * MS, 25 * MS, "policyd.gc"),              # nested deeper
            (50 * MS, 100 * MS, "bench.result")]
    busy = [(0, 5 * MS), (28 * MS, 40 * MS), (44 * MS, 60 * MS), (70 * MS, 80 * MS)]
    gaps = scopes.name_gaps(host, busy, 0, 100 * MS)
    # mids: [5,28] → 16.5 ms inside ct_prepass (gc starts at 20)
    #       [40,44] → 42 inside enqueue only
    #       [60,70] → 65 under bench.result, no program span
    #       [80,100] → 90 under bench.result
    assert [n for n, _ in gaps] == ["policyd.v4-ingress.ct_prepass",
                                    "policyd.v4-ingress.enqueue",
                                    "bench.result", "bench.result"]
    assert [ns for _, ns in gaps] == [23 * MS, 4 * MS, 10 * MS, 20 * MS]


def test_gap_under_nested_span_and_under_nothing():
    host = [(0, 100 * MS, WINDOW), (10 * MS, 30 * MS, "policyd.l7.complete"),
            (12 * MS, 28 * MS, "policyd.gc")]
    busy = [(0, 12 * MS), (28 * MS, 60 * MS), (90 * MS, 100 * MS)]
    gaps = scopes.name_gaps(host, busy, 0, 100 * MS)
    assert gaps == [("policyd.gc", 16 * MS), ("bench.idle", 30 * MS)]


def test_innermost_and_self_times():
    spans = [(0, 10, "a"), (2, 8, "b"), (3, 4, "c"), (12, 20, "d")]
    assert scopes.innermost(spans, [1, 3, 5, 9, 11, 15]) == [
        "a", "c", "b", "a", None, "d"]
    # a while op holding two body ops: its self time excludes them
    nested = scopes.nest([(0, 10, "while"), (1, 4, "f1"), (5, 9, "f2"),
                          (12, 15, "g"), (13, 14, "h")])
    assert nested == [("while", 3, -1), ("f1", 3, 0), ("f2", 4, 0),
                      ("g", 2, -1), ("h", 1, 3)]


def test_ops_without_tf_op_take_their_body_scope():
    """A ``while`` op has no ``tf_op`` in the trace: it takes the scope
    its body ops share; a compiler-inserted op with nothing named inside
    it stays ``(compiler)``."""
    own = [None, "dfa_walk/dfa_step", "dfa_walk/dfa_step", None, None,
           "lpm_v4/prefilter/table_flatten", "lpm_v4/prefilter"]
    parents = [-1, 0, 0, -1, 3, -1, -1]
    assert scopes.resolve_scopes(own, parents) == [
        "dfa_walk/dfa_step", "dfa_walk/dfa_step", "dfa_walk/dfa_step",
        "(compiler)", "(compiler)", "lpm_v4/prefilter/table_flatten",
        "lpm_v4/prefilter"]
    assert scopes.resolve_scopes([None, "lpm_v4/x", "lpm_v4/y"], [-1, 0, 0])[0] == "lpm_v4"
    assert scopes.resolve_scopes([None, "lpm_v4", "-"], [-1, 0, 0])[0] == "-"


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(process_flows_wide)/lpm_v4/jit(lpm_lookup_wide)/table_flatten/reshape:",
     "lpm_v4/table_flatten"),
    ("jit(dfa_match_batch_pair)/while/body/closed_call/dfa_step/jit(_take)/gather:",
     "dfa_step"),
    ("jit(process_flows_wide)/jit(lookup_batch)/while/body/closed_call/and:", "-"),
    ("gather:", "-"),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_recorded_fixture_agrees_with_tracefile():
    """The fixture (recorded before the program had named scopes): the
    op names, their times and the gaps match benchmark.tracefile, and
    the scope self times add up to the device's busy time."""
    path = find_xplane(FIXTURE)
    got = scopes.reduce(scopes.load_xspace(path))
    ref = reduce_xplane(path)
    assert got["window_s"] == pytest.approx(ref.window_s)
    ref_ops = dict(ref.breakdown()["device_ops"])
    for name, secs, scope in got["top_ops"]:
        # tracefile truncates each event to whole ns
        assert secs == pytest.approx(ref_ops[name], abs=2e-6)
        assert scope.split("/")[0] == name.split("/")[0]
    assert sum(s for _, s in got["device_scopes"]) == pytest.approx(ref.busy_s, rel=1e-3)
    assert [n for n, _ in got["idle_gaps_by_phase"]] == [
        n for n, _ in ref.longest_gaps[:10]]
    assert got["policyd_share_of_longest_gaps"] == 0.0
