"""The comparison that decides ``correct`` fails what it must.

1. The controls: the reference with one guarantee broken
   (``reference.CONTROLS``), put in the program's place.
2. Faults planted in the timed path underneath a run that skips only
   the harness's look for a chip: an answer altered where it is
   produced, half of a batch's answers left out, and a batch the
   failsafe resolved degraded.

Each must turn ``correct`` false; the sound run beside them must not.
"""

import numpy as np
import pytest

from benchmark import reference as R
from benchmark import run
from benchmark import traffic as T
from benchmark.tests.conftest import tiny

SEED = 2**31 + 99


def _with_control(monkeypatch, name):
    """Replace the program's answers by the control's: each batch the
    kind would send down the program's entry is answered by the
    reference with one guarantee broken."""
    real = run.prepare

    def prepare(*a, **k):
        prep = real(*a, **k)
        ctrl = R.Reference(prep.w, **R.CONTROLS[name])
        prep.kind.send = lambda b: T.Done(prep.kind.answer(ctrl, b))
        return prep

    monkeypatch.setattr(run, "prepare", prepare)


def _run(cell):
    return run.run_cell(cell, SEED, 2.0, False, rehearsal=True, overrides=tiny(cell))


@pytest.mark.parametrize("cell,control,number", [
    ("node-5k.newflows-sat", "port_blind", "mismatched_verdicts"),
    ("l7-mesh.http-sat", "port_blind", "mismatched_verdicts"),
    ("l7-mesh.http-sat", "prefix_match", "mismatched_http"),
])
def test_control_is_not_correct(monkeypatch, cell, control, number):
    _with_control(monkeypatch, control)
    res = _run(cell)
    assert res["correct"] is False
    assert res["checks"][number][0] > res["checks"][number][1]


def _alter(out):
    v = np.array(out[0], copy=True)
    v[0] = R.DROP_POLICY if v[0] == R.FORWARD else R.FORWARD
    return (v,) + tuple(out[1:])


def _halve(out):
    return tuple(np.asarray(x)[: max(1, len(x) // 2)] for x in out)


def _degrade(out):
    v = np.array(out[0], copy=True)
    v[:] = T.DROP_DEGRADED
    return (v,) + tuple(out[1:])


@pytest.mark.parametrize("cell", ["node-5k.newflows-sat", "l7-mesh.http-sat"])
@pytest.mark.parametrize("fault,number", [
    (_alter, "mismatched_verdicts"),
    (_halve, "mismatched_verdicts"),
    (_degrade, "failed_batches"),
])
def test_planted_fault_is_not_correct(monkeypatch, cell, fault, number):
    from cilium_tpu.datapath import pipeline

    real = pipeline.PendingBatch.result
    hit = {"n": 0}

    def broken(self):
        out = real(self)
        hit["n"] += 1
        return fault(out) if hit["n"] % 7 == 3 else out

    monkeypatch.setattr(pipeline.PendingBatch, "result", broken)
    res = _run(cell)
    assert res["correct"] is False
    assert res["checks"][number][0] > 0


def test_http_answer_altered_is_not_correct(monkeypatch):
    from cilium_tpu.l7.http_policy import HTTPPolicy

    real = HTTPPolicy.check_batch

    def broken(self, requests):
        out = np.array(real(self, requests), copy=True)
        out[0] = ~out[0]
        return out

    monkeypatch.setattr(HTTPPolicy, "check_batch", broken)
    res = _run("l7-mesh.http-sat")
    assert res["correct"] is False
    assert res["checks"]["mismatched_http"][0] > 0


def test_sound_run_is_correct():
    res = _run("node-5k.newflows-sat")
    assert res["correct"] is True
    assert all(v == 0 for v, _ in res["checks"].values())
