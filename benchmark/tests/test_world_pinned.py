"""The worlds of the cells in BENCHMARK.json stay what they were: for
one seed, a digest over each configuration's whole world (the rules'
JSON and every array and table), over the daemon calls that import it,
in order, and over the batches each cell's traffic draws from it. A
change to the generator that moves any of them reshapes a cell that
later PRs are measured on."""

import hashlib
import json
import types

import numpy as np
import pytest

from benchmark import world as W

SEED = 2**31 + 26

# sha256 of _world_bytes / _call_bytes / _schedule_bytes for SEED at
# the cell's full size, taken from the generator as the two cells were
# defined
WORLD = {
    "node-5k": "44e9b9480650d7eedc5bf474b50ff176e595f02ddd1b540766c3f0eca8bac337",
    "l7-mesh": "03b4f0a90a67013bf30fe3ccfe544443f19a4420ced527666171ac56091307de",
}
CALLS = {
    "node-5k": "d479b24d8accc9ffdaf3649e7c3701423c7bc2eed08a0cd3142004a6a3172139",
    "l7-mesh": "1661a50e474d5bbd5f72ce09016fd1124735fd8c9dbd8b08fda5fc32772d4d73",
}
SCHEDULE = {
    "node-5k.newflows-sat": "70c06ac4f866e3d7bfeadaec59a42083c410a49f208efc42ba9aa3666e022dbd",
    "l7-mesh.http-sat": "0982facfbe8aedef8f604ccd74df246df532e0b30042acfb3a33f10a8ccd8f61",
}
# the setting each configuration's cell boots with (its traffic kind's)
L7_DEVICE_BATCH = {"node-5k": False, "l7-mesh": True}


def _feed(h, obj) -> None:
    if isinstance(obj, np.ndarray):
        h.update(f"{obj.dtype.str}{obj.shape}".encode())
        h.update(np.ascontiguousarray(obj).tobytes())
    elif isinstance(obj, dict):
        h.update(b"{")
        for k in sorted(obj, key=repr):
            h.update(repr(k).encode())
            _feed(h, obj[k])
        h.update(b"}")
    elif isinstance(obj, (list, tuple)):
        h.update(b"[")
        for x in obj:
            _feed(h, x)
        h.update(b"]")
    else:
        h.update(repr(obj).encode())


def _schedule_bytes(kind, sched) -> str:
    """Every field of every batch, as the kind drew them."""
    h = hashlib.sha256()
    _feed(h, sched.due)
    for b in sched.batches:
        _feed(h, {k: v for k, v in vars(b).items() if k != "conns"})
        if hasattr(b, "conns"):
            _feed(h, vars(b.conns))
    _feed(h, getattr(kind, "_banks", {}))
    return h.hexdigest()


def _world_bytes(w) -> str:
    h = hashlib.sha256()
    h.update(json.dumps(w.rules_json, sort_keys=True).encode())
    for f in ("app_names", "app_labels", "ep_app", "ep_ip4", "pod_app", "pod_ip4",
              "pods_of_app", "ingress", "egress", "allow_in", "allow_eg", "egress_eps",
              "http_sets", "prefixes", "l7_port"):
        h.update(f.encode())
        _feed(h, getattr(w, f))
    return h.hexdigest()


class _Recorder:
    """A Daemon in name only: records every call the import makes."""

    def __init__(self) -> None:
        self.log = []
        rec = self

        class _IPCache:
            def upsert(self, *a, **k):
                rec.log.append(("ipcache.upsert", a, sorted(k.items())))

        class _Prefilter:
            revision = 0

            def insert(self, *a):
                rec.log.append(("prefilter.insert", a))

        self.ipcache, self.prefilter = _IPCache(), _Prefilter()
        self.pipeline = types.SimpleNamespace(endpoint_id_at=lambda i: i + 1)
        self._next = 256

    def endpoint_add(self, *a, **k):
        self.log.append(("endpoint_add", a, sorted(k.items())))

    def allocate_identity(self, labels):
        self.log.append(("allocate_identity", str(labels)))
        self._next += 1
        return types.SimpleNamespace(id=self._next)

    def policy_add(self, text):
        self.log.append(("policy_add", text))
        return {"count": len(json.loads(text))}


def _call_bytes(monkeypatch, w, l7_device_batch: bool) -> str:
    """The daemon's configuration, then every call of the import."""
    import dataclasses

    from cilium_tpu import daemon, option

    made, cfgs = [], []
    monkeypatch.setattr(daemon, "Daemon", lambda: made.append(_Recorder()) or made[-1])
    monkeypatch.setattr(option, "set_config", lambda c: cfgs.append(dataclasses.asdict(c)))
    W.boot_daemon(w, l7_device_batch=l7_device_batch, phase_tracing=False)
    return hashlib.sha256(repr((cfgs, made[0].log)).encode()).hexdigest()


@pytest.mark.parametrize("config", sorted(WORLD))
def test_world_unchanged(config):
    w = W.build_world(W.load_json("configs", config), SEED)
    assert _world_bytes(w) == WORLD[config]


@pytest.mark.parametrize("config", sorted(CALLS))
def test_daemon_calls_unchanged(monkeypatch, config):
    w = W.build_world(W.load_json("configs", config), SEED)
    assert _call_bytes(monkeypatch, w, L7_DEVICE_BATCH[config]) == CALLS[config]


@pytest.mark.parametrize("cell", sorted(SCHEDULE))
def test_traffic_unchanged(cell):
    from benchmark import run

    c = run.cell_of(run.load_benchmark(), cell)
    prep = run.prepare(W.load_json("configs", c["config"]), W.load_json("traffic", c["traffic"]),
                       SEED, 2.0, None)
    assert _schedule_bytes(prep.kind, prep.scheds[0]) == SCHEDULE[cell]
