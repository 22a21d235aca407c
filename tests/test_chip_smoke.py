"""chip_smoke.py at a tiny size on the CPU: the same phases, checks and
reference as the chip run, so a change that breaks the smoke fails
here before it costs chip time."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

TINY = chip_smoke.Scale(
    rules=300, apps=32, endpoints=8, identities=96, prefixes=500,
    batch=1024, ragged=700, v6_batch=512, check=256, http=64, traces=2,
)


@pytest.mark.parametrize("four_chips", [False, True], ids=["one-chip", "2x2-mesh"])
def test_smoke_passes_on_cpu(four_chips, capsys):
    out = chip_smoke.run(TINY, seed=3, four_chips=four_chips, require_tpu=False)
    assert out["ok"] and out["device"]["platform"] == "cpu"
    text = capsys.readouterr().out
    if four_chips:
        assert "identical=False" not in text
        assert "flow_devices=[0, 1, 2, 3]" in text
    else:
        assert " mismatches=0 " in text and "ladder_level=0 quarantined=0" in text


def test_no_tpu_exits_2_without_a_result(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code == 2
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_reference_catches_a_wrong_verdict():
    w = chip_smoke.build_world(TINY, 0)
    ref = chip_smoke.Reference(w)
    b = chip_smoke.make_batch(w, "x", 64, np.random.default_rng(0))
    want = np.array([
        chip_smoke.DROP_PREFILTER if pf else ref.decision(
            int(b.ep_idx[i]), b.peer_labels[i], int(b.dports[i]),
            int(b.protos[i]), True)
        for i, pf in enumerate(chip_smoke.in_prefilter(w, b.peer))
    ], np.int8)
    red = (want == chip_smoke.FORWARD) & (b.ep_idx == w.l7_ep) & (
        b.dports == chip_smoke.L7_PORT)
    assert ref.check_batch(b, want, red, np.arange(64))["mismatches"] == 0
    flipped = np.where(want == chip_smoke.FORWARD, chip_smoke.DROP_POLICY,
                       chip_smoke.FORWARD).astype(np.int8)
    assert ref.check_batch(b, flipped, red, np.arange(64))["mismatches"] == 64
