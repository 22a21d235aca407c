"""policyd-fed: one identity plane + one policy epoch across N nodes.

Covers the federation acceptance contract: two daemons sharing one
kvstore converge to identical identity numbering and cluster policy
epoch; under an injected partition (FlakyBackend) plus node lease
expiry the reserve/confirm allocator never double-assigns and its
retries ride utils/backoff; with ClusterFederation OFF the engine
compiles the exact pre-option programs (tripwire-spied bit-identical);
and the /cluster + CLI + bugtool surfaces answer.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from cilium_tpu.daemon import Daemon
from cilium_tpu.federation import (
    ClusterIdentityAllocator,
    EpochExchange,
    FederationError,
    FederationMember,
)
from cilium_tpu.kvstore.allocator import Allocator
from cilium_tpu.kvstore.backend import InMemoryBackend, InMemoryStore
from cilium_tpu.kvstore.filestore import FileBackend, FlakyBackend
from cilium_tpu.kvstore.paths import IDENTITIES_PATH
from cilium_tpu.ops.lpm import ip_strings_to_u32
from cilium_tpu.utils.backoff import Backoff

RULES = [{
    "endpointSelector": {"matchLabels": {"k8s:app": "web"}},
    "ingress": [{"fromEndpoints": [{"matchLabels": {"k8s:app": "client"}}],
                 "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}]}]}],
    "labels": ["k8s:policy=fed"],
}]


def _fast_backoff():
    return Backoff(min_s=0.001, max_s=0.01, full_jitter=True,
                   max_elapsed_s=5.0)


def _alloc(store, name, **kw):
    kw.setdefault("backoff_factory", _fast_backoff)
    kw.setdefault("min_id", 256)
    kw.setdefault("max_id", 4096)
    return ClusterIdentityAllocator(
        InMemoryBackend(store, name), IDENTITIES_PATH, node_name=name, **kw
    )


# ---------------------------------------------------------------------------
class TestClusterIdentityAllocator:
    def test_two_nodes_converge_same_key_same_id(self):
        store = InMemoryStore()
        a, b = _alloc(store, "a"), _alloc(store, "b")
        ia, new_a = a.allocate("k8s:app=web")
        ib, new_b = b.allocate("k8s:app=web")
        assert ia == ib
        assert new_a and not new_b
        ic, _ = b.allocate("k8s:app=db")
        assert ic != ia
        st = b.state()
        assert st["allocations"]["adopted"] == 1
        assert st["allocations"]["new"] == 1

    def test_reserve_keys_confirmed_away(self):
        store = InMemoryStore()
        a = _alloc(store, "a")
        for i in range(5):
            a.allocate(f"k8s:app=svc-{i}")
        # confirm deletes every reserve; nothing lease-bound leaks
        assert a.backend.list_prefix(a.reserve_prefix) == {}

    def test_reserve_skips_candidate_mid_confirm(self):
        """A live reserve (peer mid-confirm) steers id selection away
        from the candidate without any master-CAS burn."""
        store = InMemoryStore()
        a = _alloc(store, "a")
        ghost = InMemoryBackend(store, "ghost")
        assert ghost.create_only(
            a.reserve_prefix + "256", b"ghost", lease=True
        )
        id_, is_new = a.allocate("k8s:app=web")
        assert is_new and id_ == 257  # 256 is reserved by the peer

    def test_interop_with_legacy_allocator(self):
        """Wire compatibility: a pre-federation Allocator node and a
        reserve/confirm node on the same path agree on numbering."""
        store = InMemoryStore()
        fed = _alloc(store, "fed")
        legacy = Allocator(
            InMemoryBackend(store, "legacy"), IDENTITIES_PATH,
            suffix="legacy", min_id=256, max_id=4096,
        )
        i1, _ = legacy.allocate("k8s:app=web")
        i2, _ = fed.allocate("k8s:app=web")      # adopts legacy's master
        i3, _ = fed.allocate("k8s:app=db")       # fresh via reserve/confirm
        i4, _ = legacy.allocate("k8s:app=db")    # adopts fed's master
        assert (i1, i4) == (i2, i3)

    def test_concurrent_contention_no_double_assign(self):
        store = InMemoryStore()
        a, b = _alloc(store, "a"), _alloc(store, "b")
        keys = [f"k8s:app=svc-{i}" for i in range(32)]
        got = {"a": {}, "b": {}}

        def worker(alloc, tag):
            for k in keys:
                got[tag][k] = alloc.allocate(k)[0]

        ts = [threading.Thread(target=worker, args=(a, "a")),
              threading.Thread(target=worker, args=(b, "b"))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30.0)
        assert got["a"] == got["b"]
        ids = list(got["a"].values())
        assert len(set(ids)) == len(ids)  # injective: no double-assign

    def test_partition_retries_ride_backoff(self):
        """A partition mid-allocation stalls (bounded) and converges
        once healed, with the retry outcomes accounted."""
        store = InMemoryStore()
        a = _alloc(store, "a")
        flaky = FlakyBackend(InMemoryBackend(store, "b"))
        b = ClusterIdentityAllocator(
            flaky, IDENTITIES_PATH, node_name="b",
            min_id=256, max_id=4096, backoff_factory=_fast_backoff,
        )
        ia, _ = a.allocate("k8s:app=web")
        flaky.fail(True)
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault("id", b.allocate("k8s:app=web")[0])
        )
        t.start()
        time.sleep(0.03)
        flaky.fail(False)
        t.join(10.0)
        assert out["id"] == ia
        st = b.state()["allocations"]
        assert st.get("retry", 0) >= 1 and st["adopted"] == 1
        assert flaky.op_errors >= 1

    def test_three_nodes_on_filestore_converge_injective(self, tmp_path):
        """Three nodes race allocate() over one key set on the durable
        SQLite store, not the in-memory double: all three id maps
        agree and no id is handed out twice."""
        path = str(tmp_path / "kvstore.sqlite")
        names = ["node-0", "node-1", "node-2"]
        backends = [FileBackend(path, n, lease_ttl=60.0) for n in names]
        allocs = [
            ClusterIdentityAllocator(
                be, IDENTITIES_PATH, node_name=n, min_id=256,
                max_id=1 << 16, backoff_factory=_fast_backoff,
            )
            for be, n in zip(backends, names)
        ]
        keys = [f"k8s:app=fs-{i}" for i in range(16)]
        got = [{} for _ in allocs]

        def worker(i):
            for k in keys:
                got[i][k] = allocs[i].allocate(k)[0]

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(len(allocs))]
        try:
            for t in ts:
                t.start()
            for t in ts:
                t.join(30.0)
            assert not any(t.is_alive() for t in ts)
            assert got[0] == got[1] == got[2]
            assert len(set(got[0].values())) == len(keys)
        finally:
            for a in allocs:
                a.close()
            for be in backends:
                be.close()

    def test_partition_and_lease_expiry_during_contention(self):
        """Both faults land inside one two-node allocation storm: node
        b is partitioned before it starts, node c (holding ids) dies
        mid-storm. The maps still agree and stay injective, no id of
        the dead node is reused, and GC reaps exactly its ids."""
        store = InMemoryStore()
        c = _alloc(store, "c")
        c_ids = {c.allocate(f"k8s:app=ephemeral-{i}")[0] for i in range(8)}
        a = _alloc(store, "a")
        flaky = FlakyBackend(InMemoryBackend(store, "b"))
        b = ClusterIdentityAllocator(
            flaky, IDENTITIES_PATH, node_name="b",
            min_id=256, max_id=4096, backoff_factory=_fast_backoff,
        )
        keys = [f"k8s:app=storm-{i}" for i in range(24)]
        got = {"a": {}, "b": {}}

        def worker(alloc, tag):
            for k in keys:
                got[tag][k] = alloc.allocate(k)[0]

        flaky.fail(True)
        ts = [threading.Thread(target=worker, args=(a, "a")),
              threading.Thread(target=worker, args=(b, "b"))]
        for t in ts:
            t.start()
        time.sleep(0.005)
        store.revoke_lease(c.backend.lease_id)  # node c dies mid-storm
        time.sleep(0.005)
        flaky.fail(False)
        for t in ts:
            t.join(30.0)
        assert not any(t.is_alive() for t in ts)
        assert got["a"] == got["b"]
        ids = set(got["a"].values())
        assert len(ids) == len(keys)
        assert not ids & c_ids
        assert set(a.run_gc()) == c_ids
        assert flaky.op_errors >= 1

    def test_backoff_exhausted_raises_federation_error(self):
        store = InMemoryStore()
        flaky = FlakyBackend(InMemoryBackend(store, "b"))
        b = ClusterIdentityAllocator(
            flaky, IDENTITIES_PATH, node_name="b", min_id=256, max_id=4096,
            backoff_factory=lambda: Backoff(
                min_s=0.001, max_s=0.002, full_jitter=True,
                max_elapsed_s=0.02,
            ),
        )
        flaky.fail(True)
        with pytest.raises(FederationError):
            b.allocate("k8s:app=web")
        assert b.state()["allocations"]["error"] == 1

    def test_heartbeat_repairs_lease_loss(self):
        """Slave AND master keys wiped (what a lease expiry does to the
        lease-bound half, GC to the rest) come back on heartbeat, so
        identities still in local use survive."""
        store = InMemoryStore()
        a = _alloc(store, "a")
        id_, _ = a.allocate("k8s:app=web")
        a.backend.delete(a._slave_key("k8s:app=web"))
        a.backend.delete(a._master_key(id_))
        assert a.get_no_cache("k8s:app=web") == 0
        assert a.heartbeat() == 2  # slave + master re-created
        assert a.get_no_cache("k8s:app=web") == id_

    def test_release_on_lease_expiry_via_gc(self):
        """A dead node's slave keys evaporate with its lease; GC then
        reaps the masterless master — release needs no RPC."""
        store = InMemoryStore()
        a, b = _alloc(store, "a"), _alloc(store, "b")
        id_, _ = b.allocate("k8s:app=ephemeral")
        store.revoke_lease(b.backend.lease_id)  # node b dies
        assert a.run_gc() == [id_]
        assert a.backend.get(a._master_key(id_)) is None

    def test_heartbeat_reaps_own_orphaned_reserves(self):
        store = InMemoryStore()
        a = _alloc(store, "a")
        # a crashed confirm's leftover (same node name, not in flight)
        a.backend.update(a.reserve_prefix + "999", b"a", lease=True)
        a.heartbeat()
        assert a.backend.get(a.reserve_prefix + "999") is None


# ---------------------------------------------------------------------------
class TestEpochExchange:
    def _pair(self, store):
        ea = {"v": 0}
        eb = {"v": 0}
        xa = EpochExchange(InMemoryBackend(store, "a"), "node-a",
                           epoch_source=lambda: ea["v"])
        xb = EpochExchange(InMemoryBackend(store, "b"), "node-b",
                           epoch_source=lambda: eb["v"])
        return (xa, ea), (xb, eb)

    def test_cluster_epoch_is_min_over_fleet(self):
        store = InMemoryStore()
        (xa, ea), (xb, eb) = self._pair(store)
        ea["v"], eb["v"] = 5, 3
        for x in (xa, xb):
            x.publish()
        for x in (xa, xb):
            x.pump()
        assert len(xa.view()) == 2
        assert xa.cluster_epoch() == 3
        assert xa.epoch_lag() == 2 and xb.epoch_lag() == 0

    def test_wait_cluster_epoch_barrier(self):
        store = InMemoryStore()
        (xa, ea), (xb, eb) = self._pair(store)
        ea["v"], eb["v"] = 2, 1
        xb.publish()
        assert not xa.wait_cluster_epoch(
            2, timeout=0.1, min_nodes=2, pump=xb.pump
        )
        eb["v"] = 2
        assert xa.wait_cluster_epoch(
            2, timeout=5.0, min_nodes=2,
            pump=lambda: (xb.publish(), xb.pump()),
        )

    def test_filestore_fleet_epoch_barrier(self, tmp_path):
        """Three nodes on the durable SQLite store publish a new policy
        epoch; the barrier returns once the fleet minimum reaches it."""
        path = str(tmp_path / "kvstore.sqlite")
        names = ["node-0", "node-1", "node-2"]
        backends = [FileBackend(path, n, lease_ttl=60.0) for n in names]
        epochs = [{"v": 0} for _ in names]
        xs = [
            EpochExchange(be, n, cluster="t",
                          epoch_source=(lambda e=e: e["v"]))
            for be, n, e in zip(backends, names, epochs)
        ]

        def pump_all():
            for x in xs:
                x.publish()
                x.pump()

        try:
            pump_all()
            assert not xs[0].wait_cluster_epoch(
                1, timeout=0.1, min_nodes=3, pump=pump_all)
            for e in epochs:
                e["v"] = 1
            assert xs[0].wait_cluster_epoch(
                1, timeout=10.0, min_nodes=3, pump=pump_all)
            assert xs[0].cluster_epoch() == 1
        finally:
            for x in xs:
                x.close()
            for be in backends:
                be.close()

    def test_dead_node_drops_from_view(self):
        store = InMemoryStore()
        (xa, _), (xb, _) = self._pair(store)
        for x in (xa, xb):
            x.publish(force=True)
        for x in (xa, xb):
            x.pump()
        assert len(xa.view()) == 2
        store.revoke_lease(xb.store.backend.lease_id)
        xa.pump()
        assert set(r["node"] for r in xa.view().values()) == {"node-a"}


# ---------------------------------------------------------------------------
@pytest.fixture()
def federated():
    store = InMemoryStore()
    made = []

    def make(name, pod_cidr):
        d = Daemon(pod_cidr=pod_cidr, health_probe=lambda a, p: 0.001)
        m = FederationMember(
            d, InMemoryBackend(store, name), name,
            heartbeat_interval=3600, backoff_factory=_fast_backoff,
        )
        d.attach_federation(m)
        d.options.set("ClusterFederation", True)
        made.append((d, m))
        return d, m

    a = make("node-a", "10.1.0.0/16")
    b = make("node-b", "10.2.0.0/16")
    yield store, a, b
    for d, m in made:
        m.close()
        d.shutdown()


def _pump_all(*members, rounds: int = 4):
    for _ in range(rounds):
        for m in members:
            m.pump()


class TestFederationMember:
    def test_identity_numbering_agrees(self, federated):
        _store, (da, ma), (db, mb) = federated
        da.policy_add(json.dumps(RULES))
        db.policy_add(json.dumps(RULES))
        da.endpoint_add(1, ["k8s:app=web"], ipv4="10.1.0.10")
        db.endpoint_add(2, ["k8s:app=web"], ipv4="10.2.0.20")
        da.endpoint_add(3, ["k8s:app=client"], ipv4="10.1.0.11")
        _pump_all(ma, mb)
        ida = da.endpoint_manager.lookup(1).identity.id
        idb = db.endpoint_manager.lookup(2).identity.id
        assert ida == idb  # same labels ⇒ same cluster-wide number
        # node-b mirrors node-a's client identity for row coverage
        idc = da.endpoint_manager.lookup(3).identity.id
        assert db.registry.get(idc) is not None

    def test_cluster_policy_epoch_converges(self, federated):
        _store, (da, ma), (db, mb) = federated
        da.endpoint_add(1, ["k8s:app=web"], ipv4="10.1.0.10")
        db.endpoint_add(2, ["k8s:app=web"], ipv4="10.2.0.20")
        for d in (da, db):
            d.pipeline.rebuild()          # baseline generation
            d.options.set("EpochSwap", True)
        da.policy_add(json.dumps(RULES))  # the delta that swaps
        db.policy_add(json.dumps(RULES))
        for d in (da, db):
            d.pipeline.rebuild()          # kick the shadow build
            assert d.pipeline.wait_epoch_swap(timeout=30.0)
        assert da.pipeline.policy_epoch >= 1
        assert ma.wait_cluster_epoch(
            timeout=10.0, min_nodes=2,
            pump=lambda: mb.pump(),
        )
        st = da.cluster_status()
        assert st["epoch_lag"] == 0
        assert st["cluster_epoch"] >= 1

    def test_cluster_status_surface(self, federated):
        _store, (da, ma), (db, mb) = federated
        _pump_all(ma, mb)
        st = da.cluster_status()
        assert st["enabled"] and st["attached"] and st["joined"]
        assert st["node_count"] == 2
        assert {n["node"] for n in st["nodes"]} == {"node-a", "node-b"}
        assert "identities" in st
        # the /status healthz block answers without the full view
        assert da.status()["cluster"]["enabled"] is True
        # bugtool bundles the same payload as cluster.json
        from cilium_tpu import bugtool
        info = bugtool.collect_debuginfo(da)
        assert info["cluster"]["node_count"] == 2

    def test_release_keeps_remote_rows_covered(self, federated):
        _store, (da, ma), (db, mb) = federated
        da.endpoint_add(1, ["k8s:app=web"], ipv4="10.1.0.10")
        db.endpoint_add(2, ["k8s:app=web"], ipv4="10.2.0.20")
        _pump_all(ma, mb)
        ident = da.endpoint_manager.lookup(1).identity
        da.endpoint_delete(1)
        _pump_all(ma, mb)
        # node-b still uses the number → node-a keeps the row mirrored
        assert da.registry.get(ident.id) is not None

    def test_node_descriptor_rides_epoch_record(self):
        from cilium_tpu.nodes.registry import Node

        store = InMemoryStore()
        d = Daemon(pod_cidr="10.3.0.0/16")
        m = FederationMember(
            d, InMemoryBackend(store, "c"), "node-c",
            descriptor=Node(name="node-c", ipv4="192.168.0.3",
                            ipv4_alloc_cidr="10.3.0.0/16"),
            heartbeat_interval=3600, backoff_factory=_fast_backoff,
        )
        m.pump()
        (rec,) = m.epochs.view().values()
        assert rec["ipv4"] == "192.168.0.3"
        assert rec["ipv4_alloc_cidr"] == "10.3.0.0/16"
        assert rec["policy_epoch"] == 0
        m.close()
        d.shutdown()

    def test_option_requires_membership(self):
        d = Daemon(pod_cidr="10.9.0.0/24")
        with pytest.raises(ValueError, match="no federation membership"):
            d.config_patch({"ClusterFederation": True})
        # standalone surface still answers
        st = d.cluster_status()
        assert not st["attached"] and st["nodes"] == []
        d.shutdown()

    def test_off_restores_registry_allocator(self, federated):
        _store, (da, ma), _b = federated
        assert da.allocate_identity == ma.allocate
        da.options.set("ClusterFederation", False)
        assert da.allocate_identity == da.registry.allocate
        da.options.set("ClusterFederation", True)
        assert da.allocate_identity == ma.allocate


class TestOffPath:
    def test_off_path_bit_identical_and_tripwired(self, monkeypatch):
        """ClusterFederation toggled on and back off must leave the
        exact pre-option path: tripwires on every federation entry
        point prove none runs, and verdicts match a never-federated
        daemon bit-for-bit."""
        store = InMemoryStore()
        ctrl = Daemon(pod_cidr="10.1.0.0/16")     # never federated
        dut = Daemon(pod_cidr="10.1.0.0/16")
        m = FederationMember(
            dut, InMemoryBackend(store, "dut"), "dut",
            heartbeat_interval=3600, backoff_factory=_fast_backoff,
        )
        dut.attach_federation(m)
        dut.options.set("ClusterFederation", True)
        dut.options.set("ClusterFederation", False)

        def boom(*_a, **_k):
            raise AssertionError("off path touched policyd-fed code")

        monkeypatch.setattr(m, "allocate", boom)
        monkeypatch.setattr(m, "release", boom)
        monkeypatch.setattr(m.identities, "allocate", boom)
        for d in (ctrl, dut):
            d.policy_add(json.dumps(RULES))
            d.endpoint_add(1, ["k8s:app=web"], ipv4="10.1.0.10")
            d.endpoint_add(2, ["k8s:app=client"], ipv4="10.1.0.11")
            d.endpoint_add(3, ["k8s:app=other"], ipv4="10.1.0.12")
        src = ip_strings_to_u32(["10.1.0.11", "10.1.0.12"])
        assert (dut.endpoint_manager.lookup(1).identity.id
                == ctrl.endpoint_manager.lookup(1).identity.id)
        ep_c = ctrl.pipeline.endpoint_index(1)
        ep_d = dut.pipeline.endpoint_index(1)
        dports = np.array([80, 80], np.int32)
        protos = np.array([6, 6], np.int32)
        v_c, r_c = ctrl.pipeline.process(
            src, np.full(2, ep_c, np.int32), dports, protos
        )
        v_d, r_d = dut.pipeline.process(
            src, np.full(2, ep_d, np.int32), dports, protos
        )
        np.testing.assert_array_equal(v_c, v_d)
        np.testing.assert_array_equal(r_c, r_d)
        m.close()
        ctrl.shutdown()
        dut.shutdown()


class TestCLISurface:
    def test_cluster_cli_standalone(self, tmp_path, capsys):
        from cilium_tpu.cli import main as cli_main

        args = ["--socket", str(tmp_path / "no.sock"),
                "--state", str(tmp_path / "state")]
        assert cli_main([*args, "cluster", "status"]) == 0
        st = json.loads(capsys.readouterr().out)
        assert st["attached"] is False and st["enabled"] is False
        assert cli_main([*args, "cluster", "nodes"]) == 0
        assert json.loads(capsys.readouterr().out) == []
