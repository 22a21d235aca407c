"""The trace reduction: device busy and idle time, time by operation
and program, and idle gaps named by the host span open at the time."""

import os

import pytest

from benchmark.tracefile import WINDOW, find_xplane, reduce_xplane, summarize

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


def test_summarize_union_gaps_and_spans():
    ms = 1_000_000
    host = [(0, 100 * ms, WINDOW), (0, 40 * ms, "bench.submit"),
            (40 * ms, 100 * ms, "bench.result")]
    ops = {"/device:TPU:0": [(10 * ms, 20 * ms, "gather"), (15 * ms, 30 * ms, "fusion"),
                             (60 * ms, 70 * ms, "gather"), (95 * ms, 120 * ms, "copy")]}
    mods = {"/device:TPU:0": [(10 * ms, 30 * ms, "jit_process_flows_wide"),
                              (60 * ms, 70 * ms, "jit_process_flows_wide")]}
    s = summarize(host, ops, mods)
    assert s.window_s == pytest.approx(0.1)
    # busy: [10,30] + [60,70] + [95,100] (clipped to the window)
    assert s.busy_s == pytest.approx(0.035)
    assert s.devices == 1
    assert s.ops_s["jit_process_flows_wide/gather"] == pytest.approx(0.02)
    assert s.ops_s["copy"] == pytest.approx(0.005)   # outside any program
    assert s.modules_s["jit_process_flows_wide"] == pytest.approx(0.03)
    # gaps: [0,10] submit, [30,60] result (mid 45), [70,95] result
    assert s.gaps_by_span["bench.submit"] == pytest.approx(0.010)
    assert s.gaps_by_span["bench.result"] == pytest.approx(0.055)
    assert s.longest_gaps[0] == ("bench.result", pytest.approx(0.03))
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_process_flows_wide/gather"
    assert len(b["idle_gaps"]) == 3


def test_summarize_needs_a_window():
    with pytest.raises(ValueError):
        summarize([], {}, {})


def test_recorded_chip_trace():
    """A small trace recorded on one TPU v5 lite (my chip run, PR 22):
    a 1 s traced window of the L7 mesh cell (then with four shared rule
    sets) at 1,500 requests/s. The run itself reported busy_s
    0.021206334 and window_s 0.920165086."""
    s = reduce_xplane(find_xplane(FIXTURE))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.920165086)
    assert s.busy_s == pytest.approx(0.021206334)
    assert any(n.startswith("jit_process_flows_wide") for n in s.modules_s)
    assert any(n.startswith("jit_dfa_match_batch_pair") for n in s.modules_s)
    assert s.gaps_by_span and all(n.startswith("bench.") for n in s.gaps_by_span)
    top = s.breakdown()["device_ops"][0][0]
    assert top.startswith("jit_dfa_match_batch_pair/")
