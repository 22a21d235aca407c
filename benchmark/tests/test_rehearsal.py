"""Each cell end to end on the CPU at a tiny size: set-up through the
daemon's API, warm-up, an open-loop window, the check against the
reference, and the result line's shape."""

import json

import pytest

from benchmark import run
from benchmark.tests.conftest import TINY_TRAFFIC, tiny

CELLS = sorted(TINY_TRAFFIC)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(cell, trace):
    res = run.run_cell(cell, 2**31 + 17, 2.0, trace, rehearsal=True, overrides=tiny(cell))
    assert res["correct"] is True, res["checks"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    bench = run.load_benchmark()
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in run.metrics_for(bench, section, cell)}
    assert set(res["metrics"]) <= declared
    if not trace:
        assert set(res["metrics"]) == declared
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # no device trace on the CPU: only host-side readers report
        # (compile_s too, unless this process compiled it all before)
        assert "world_build_s" in res["metrics"]
    json.dumps(res)
