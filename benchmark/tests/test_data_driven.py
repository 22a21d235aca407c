"""A later PR adds a cell, a kind of traffic and a per-layer metric by
new files alone: a traffic mix in ``benchmark/traffic/``, the code of
its kind in ``benchmark/kinds/``, a reader in ``benchmark/metrics/``,
and entries in BENCHMARK.json. Here all three are added to a copy of
the benchmark, which then runs the new cell and reports the new metric,
with no file the benchmark had edited."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmark.tests.conftest import TINY

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            if "__pycache__" in d:
                continue
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_and_metric_by_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    before = _digests(str(root))

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(root / "benchmark" / "traffic" / "newflows-sat.json") as f:
        mix = json.load(f)
    mix.update(kind="dummy", batch_min=64, batch_max=512, v6_share=0.0, egress_share=0.0,
               why="dummy: small v4 ingress batches")
    # a new kind: the flows kind's entry, each batch sent in two halves
    (root / "benchmark" / "kinds" / "dummy.py").write_text(
        "from benchmark import traffic as T\n"
        "import numpy as np\n"
        "_flows = T.load_kind('flows')\n"
        "class Kind(_flows.Kind):\n"
        "    def send(self, fb):\n"
        "        h = len(fb) // 2\n"
        "        parts = [T.FlowBatch(fb.family, fb.ingress, *(getattr(fb, f)[s] for f in\n"
        "                 T._FIELDS)) for s in (slice(0, h), slice(h, None))]\n"
        "        outs = [T.submit(self.pipe, p).result() for p in parts]\n"
        "        return T.Done(tuple(np.concatenate([o[i] for o in outs]) for i in (0, 1)))\n")
    (root / "benchmark" / "traffic" / "dummy-small.json").write_text(json.dumps(mix))
    (root / "benchmark" / "metrics" / "dummy_batches.py").write_text(
        "def read(r):\n    return float(len(r.window.sizes))\n")
    bench["workloads"].append({"name": "node-5k.dummy-small", "config": "node-5k",
                               "traffic": "dummy-small", "chips": 1, "why": "dummy"})
    for m in bench["end_to_end"]:
        if m["name"] == "flow_verdicts_per_s":
            m["workloads"].append("node-5k.dummy-small")
    bench["per_layer"].append({"name": "dummy_batches", "unit": "batches", "better": "higher",
                               "source": "host_clock", "layer": "benchmark",
                               "moves": "flow_verdicts_per_s",
                               "workloads": ["node-5k.dummy-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(root)!r})\n"
        "from benchmark import run\n"
        f"assert run.ROOT == {str(root)!r}\n"
        "res = run.run_cell('node-5k.dummy-small', 5, 1.5, True, rehearsal=True, "
        f"overrides={{'config': {TINY['node-5k']!r}, 'traffic': {{'rate': 20000}}}})\n"
        "print(json.dumps(res))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], cwd=str(root), env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["dummy_batches"]["value"] > 0
    after = _digests(str(root))
    assert {k: v for k, v in after.items() if k in before} == before
