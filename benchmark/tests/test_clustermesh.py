"""A clustermesh node on four chips, added by new files alone (as in
``test_data_driven.py``): a configuration with ``clusters`` and a
``daemon`` key that asks for the 2D flows x ident mesh, a mix of the
``flows`` kind, and a ``chips: 4`` cell in a copy of the benchmark. The
copy runs in a subprocess on eight virtual CPU devices. The node learns
the remote clusters' identities through ``ClusterNode`` and its pumps,
under cluster-scoped numbers, and every verdict matches the reference.

A ``chips: 4`` cell whose plan does not span four devices on the axes
its configuration asks for ends with no result, and a ``daemon`` key
that ``DaemonConfig`` lacks ends a run before anything boots."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run
from benchmark import world as W
from benchmark.tests.conftest import TINY, tiny
from benchmark.tests.test_data_driven import _digests

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2**31 + 2026
CELL = "mesh-tiny.resolve-tiny"
MESH_2D = {"verdict_sharding": True, "mesh_sharding_2d": True, "mesh_ident_axis": 2,
           "mesh_devices": "0,1,2,3"}


def _copy_with_cell(tmp_path, daemon):
    """A checkout holding the benchmark, plus new files only: the tiny
    clustermesh configuration (with ``daemon`` when given), its mix and
    its ``chips: 4`` cell. Returns (root, digests before the files)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    before = _digests(str(root))
    cfg = W.load_json("configs", "node-5k")
    cfg.update(TINY["node-5k"], name="mesh-tiny",
               clusters={"count": 3, "local": 0, "pinned_peer_share": 0.3})
    if daemon:
        cfg["daemon"] = daemon
    mix = W.load_json("traffic", "newflows-sat")
    mix.update(rate=20000, why="tiny flows for the CPU")
    (root / "benchmark" / "configs" / "mesh-tiny.json").write_text(json.dumps(cfg))
    (root / "benchmark" / "traffic" / "resolve-tiny.json").write_text(json.dumps(mix))
    bench = run.load_benchmark()
    bench["workloads"].append({"name": CELL, "config": "mesh-tiny", "traffic": "resolve-tiny",
                               "chips": 4, "why": "tiny clustermesh node on a 2x2 mesh"})
    for m in bench["end_to_end"]:
        if m["name"] == "flow_verdicts_per_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def _run_copy(root):
    """The cell in the copy, on eight virtual devices; prints the
    daemon's identity numbers on a line of its own after the result."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(root)!r})\n"
        "from benchmark import run, world as W\n"
        f"assert run.ROOT == {str(root)!r}\n"
        "booted = []\n"
        "real = W.boot_daemon\n"
        "def boot(*a, **k):\n"
        "    d, steps = real(*a, **k)\n"
        "    booted.append(d)\n"
        "    return d, steps\n"
        "W.boot_daemon = boot\n"
        f"rc = run.main(['--workload', {CELL!r}, '--seed', '{SEED}', '--seconds', '1.5',\n"
        "               '--trace', '0', '--cpu-rehearsal'])\n"
        "if booted:\n"
        "    print(json.dumps({'ids': sorted(i.id for i in booted[0].registry)}))\n"
        "sys.exit(rc)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return subprocess.run([sys.executable, "-c", code], cwd=str(root), env=env,
                          capture_output=True, text=True, timeout=900)


def _lines(out):
    objs = []
    for line in out.stdout.splitlines():
        try:
            objs.append(json.loads(line))
        except ValueError:
            continue
    return objs


def test_clustermesh_cell_by_new_files(tmp_path):
    root, before = _copy_with_cell(tmp_path, MESH_2D)
    out = _run_copy(root)
    assert out.returncode == 0, out.stderr[-3000:]
    res, ids = _lines(out)[-2:]
    assert res["correct"] is True, res["checks"]
    assert all(v == 0 for v, _ in res["checks"].values())
    assert res["device"]["count"] == 4
    place = [ln for ln in out.stdout.splitlines() if ln.startswith("phase=placement")]
    assert len(place) == 1
    assert 'axes={"flows":2,"ident":2}' in place[0] and "ident_sharded=True" in place[0]
    assert "devices=[0,1,2,3]" in place[0]
    # the remote clusters (ids 2 and 3; the node's own is 1) reached
    # the registry under their own numbers: cluster id in bits 16-23
    clusters = {i >> 16 & 0xFF for i in ids["ids"] if i < 1 << 24}
    assert clusters == {0, 2, 3}
    assert "remote_import=" in out.stdout and "cluster_join=" in out.stdout
    after = _digests(str(root))
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("daemon", [
    None,                                           # no mesh: the plan is one device
    dict(MESH_2D, mesh_devices="0,1,2"),            # 2D asked, 1D over three devices
], ids=["no-mesh", "1d-over-3"])
def test_four_chip_cell_off_its_mesh_has_no_result(tmp_path, daemon):
    root, _ = _copy_with_cell(tmp_path, daemon)
    out = _run_copy(root)
    assert out.returncode == 3, out.stderr[-3000:]
    assert not any("metrics" in o for o in _lines(out))
    assert "no result" in out.stderr


def test_unknown_daemon_key_stops_before_boot(monkeypatch):
    def never(*a, **k):
        raise AssertionError("the run went on past an unknown daemon key")

    monkeypatch.setattr(run, "prepare", never)
    monkeypatch.setattr(W, "boot_daemon", never)
    monkeypatch.setattr(run, "device_or_exit", never)
    cell = "node-5k.newflows-sat"
    ov = tiny(cell)
    ov["config"] = dict(ov["config"], daemon={"verdict_sharding": True, "no_such_field": 1})
    with pytest.raises(SystemExit, match="no_such_field"):
        run.run_cell(cell, SEED, 1.0, False, rehearsal=True, overrides=ov)


def test_remote_stores_hold_each_cluster_as_its_agents_write_it():
    import ipaddress

    from cilium_tpu.kvstore.paths import IDENTITIES_PATH, IP_IDENTITIES_PATH

    cfg = W.load_json("configs", "node-5k")
    cfg.update(TINY["node-5k"], remote_pods=70000,
               clusters={"count": 3, "local": 1, "pinned_peer_share": 0.3})
    w = W.build_world(cfg, SEED)
    stores = W.remote_stores(w)
    assert sorted(stores) == ["cluster0", "cluster2"]
    n_svc = cfg["services"]
    for c, name in ((0, "cluster0"), (2, "cluster2")):
        kv = stores[name].list_prefix("")
        ids = {int(k.rsplit("/", 1)[1]): v.decode() for k, v in kv.items()
               if k.startswith(f"{IDENTITIES_PATH}/id/")}
        assert sorted(ids) == [(c + 1) << 16 | (256 + s) for s in range(n_svc)]
        assert all(f"k8s:{W.CLUSTER_LABEL}={name}" in v.split(";") for v in ids.values())
        ips = {k[len(f"{IP_IDENTITIES_PATH}/{name}/"):]: json.loads(v) for k, v in kv.items()
               if k.startswith(f"{IP_IDENTITIES_PATH}/{name}/")}
        pods = [p for p in range(len(w.pod_app)) if w.pod_app[p] // n_svc == c]
        assert len(ips) == 2 * len(pods)
        for cidr, entry in ips.items():
            assert cidr == str(ipaddress.ip_network(cidr)) == entry["ip"]
            assert entry["identity"] in ids
