"""Without a TPU a run prints no result and exits non-zero; so does a
checkout that holds only BENCHMARK.json and the benchmark's files."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["-m", "benchmark.run", "--workload", "node-5k.newflows-sat", "--seed",
        str(2**31 + 5), "--seconds", "1", "--trace", "0"]


def _no_result(out):
    for line in out.stdout.strip().splitlines():
        try:
            got = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in got


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, *ARGS], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    _no_result(out)


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, *ARGS], cwd=str(tmp_path), env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    _no_result(out)
