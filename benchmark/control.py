"""The controls at a cell's own size: the reference with one guarantee
broken (``reference.CONTROLS``) answers a run's whole schedule in the
program's place, and the run's own comparison reads it. It needs no
daemon: the answers are the control's. The benchmark's runs never run
it; ``benchmark/tests/test_faults.py`` keeps it at a test's size.

    python3 -m benchmark.control --workload <cell> --control <name> --seeds 1,2,3 --seconds 20
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmark import reference as R
from benchmark import run
from benchmark import world as W


def control_numbers(workload: str, control: str, seed: int, seconds: float) -> dict:
    bench = run.load_benchmark()
    cell = run.cell_of(bench, workload)
    cfg = W.load_json("configs", cell["config"])
    traffic = W.load_json("traffic", cell["traffic"])
    prep = run.prepare(cfg, traffic, seed, seconds, None)
    ctrl = R.Reference(prep.w, **R.CONTROLS[control])
    sched = prep.scheds[0]
    results = [prep.kind.answer(ctrl, b) for b in sched.batches]
    n = len(sched.batches)
    due = sched.due
    win = run.Window(due, due.copy(), due.copy(), np.array([len(b) for b in sched.batches]),
                     results, np.zeros(n, bool), seconds, [])
    checks = run.check(prep.kind, prep.ref, sched, win)
    return {k.lstrip("_"): v for k, v in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True, choices=sorted(R.CONTROLS))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for s in args.seeds.split(","):
        got = control_numbers(args.workload, args.control, int(s), args.seconds)
        print(json.dumps({"workload": args.workload, "control": args.control,
                          "seed": int(s), **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
