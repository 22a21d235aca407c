"""Datapath state maps, IPAM, CNI flow, workloads watcher, infra utils.

Reference analogs: pkg/maps/{lxcmap,tunnel,proxymap}, pkg/counter,
pkg/ip, pkg/ipam, pkg/logging, plugins/cilium-cni, pkg/workloads.
"""

from __future__ import annotations

import gc
import io
import ipaddress
import json
import time

import numpy as np
import pytest

from cilium_tpu import metrics
from cilium_tpu.ipam import IPAM, IPAMError
from cilium_tpu.ipcache.ipcache import IPCache
from cilium_tpu.maps.lxcmap import EndpointInfo, LXCMap
from cilium_tpu.maps.proxymap import ProxyMap, ProxyValue
from cilium_tpu.maps.tunnel import TunnelMap
from cilium_tpu.utils.iputil import (
    coalesce_cidrs,
    prefix_lengths_of,
    range_to_cidrs,
    remove_cidrs,
)
from cilium_tpu.utils.logging import get_logger, setup
from cilium_tpu.utils.prefix_counter import PrefixLengthCounter


class TestLXCMap:
    def test_crud_and_sync(self):
        m = LXCMap()
        m.upsert("10.0.0.5", EndpointInfo(endpoint_id=7))
        assert m.lookup("10.0.0.5").endpoint_id == 7
        assert m.lookup("10.0.0.6") is None

        class EP:
            def __init__(self, id, ipv4=None, ipv6=None):
                self.id, self.ipv4, self.ipv6 = id, ipv4, ipv6

        n = m.sync_endpoints([EP(1, "10.0.0.1"), EP(2, "10.0.0.2", "fd00::2")])
        assert n == 3 and len(m) == 3
        assert m.lookup("10.0.0.5") is None  # stale entry swept
        assert m.lookup("fd00::2").endpoint_id == 2


class TestTunnelMap:
    def test_lpm_and_node_observer(self):
        t = TunnelMap()
        t.upsert("10.1.0.0/16", "192.168.0.1")
        t.upsert("10.1.2.0/24", "192.168.0.2")
        assert t.lookup("10.1.2.9") == "192.168.0.2"  # longest wins
        assert t.lookup("10.1.9.9") == "192.168.0.1"
        assert t.lookup("10.9.0.1") is None

    def test_observe_node_registry(self):
        from cilium_tpu.kvstore import InMemoryBackend, InMemoryStore
        from cilium_tpu.nodes.registry import Node, NodeRegistry

        store = InMemoryStore()
        local = NodeRegistry(
            InMemoryBackend(store, "l"),
            Node(name="local", ipv4="192.168.0.1",
                 ipv4_alloc_cidr="10.1.0.0/24"),
        )
        t = TunnelMap()
        t.observe_nodes(local)
        remote = NodeRegistry(
            InMemoryBackend(store, "r"),
            Node(name="remote", ipv4="192.168.0.2",
                 ipv4_alloc_cidr="10.2.0.0/24"),
        )
        local.pump()
        assert t.lookup("10.2.0.9") == "192.168.0.2"
        remote.unregister()
        local.pump()
        assert t.lookup("10.2.0.9") is None


class TestProxyMap:
    def test_record_lookup_gc(self):
        pm = ProxyMap(lifetime=0.0)  # instant expiry for gc test
        pm2 = ProxyMap()
        v = ProxyValue(orig_dst_ip="10.0.0.9", orig_dst_port=80,
                       src_identity=1002)
        pm2.record_batch([("10.0.0.1", 4444, "10.0.0.2", 15001, 6)],
                         [("10.0.0.9", 80, 1002)])
        got = pm2.lookup("10.0.0.1", 4444, "10.0.0.2", 15001, 6)
        assert got == v
        assert pm2.lookup("10.0.0.1", 4445, "10.0.0.2", 15001, 6) is None
        pm.record_batch([("1.1.1.1", 1, "2.2.2.2", 2, 6)], [("10.0.0.9", 80, 1002)])
        assert pm.lookup("1.1.1.1", 1, "2.2.2.2", 2, 6) is None
        assert pm.gc() == 1

    def test_record_batch_reads_like_record(self):
        pm = ProxyMap()
        now = time.monotonic()
        keys = [("10.0.0.1", 4444, "10.0.0.2", 80, 6),
                ("fd00::1", 4445, "fd00::2", 80, 6),
                ("10.0.0.1", 4444, "10.0.0.2", 80, 6)]   # repeated: last wins
        values = [("10.0.0.2", 80, 1002), ("fd00::2", 80, 0), ("10.0.0.2", 80, 1003)]
        pm.record_batch(keys, values, now)
        assert len(pm) == 2
        assert pm.lookup(*keys[0]) == ProxyValue("10.0.0.2", 80, 1003)
        assert pm.lookup(*keys[1]) == ProxyValue("fd00::2", 80, 0)
        assert sorted(pm.items(), key=lambda e: e["src"]) == [
            {"src": "10.0.0.1:4444", "dst": "10.0.0.2:80", "proto": 6,
             "orig_dst": "10.0.0.2:80", "src_identity": 1003},
            {"src": "fd00::1:4445", "dst": "fd00::2:80", "proto": 6,
             "orig_dst": "fd00::2:80", "src_identity": 0},
        ]
        # entries recorded a lifetime ago are expired: unread, uncounted, reaped
        pm.record_batch([("1.1.1.1", 1, "2.2.2.2", 2, 6)], [("2.2.2.2", 2, 7)],
                        now - pm.lifetime)
        assert pm.lookup("1.1.1.1", 1, "2.2.2.2", 2, 6) is None
        assert len(pm) == 2 and len(pm.items()) == 2
        assert pm.gc() == 1 and len(pm) == 2

    def test_record_batch_adds_no_tracked_object_per_entry(self):
        n = 20_000
        keys = [(f"10.1.{i >> 8}.{i & 255}", 30000 + i % 9000, "10.0.0.7", 80, 6)
                for i in range(n)]
        values = [("10.0.0.7", 80, 1000 + i % 5) for i in range(n)]
        pm = ProxyMap()
        gc.collect()
        before = len(gc.get_objects())
        pm.record_batch(keys, values)
        del keys, values
        gc.collect()
        assert len(pm) == n
        assert len(gc.get_objects()) - before < 50


class TestPrefixCounter:
    def test_refcount_and_change_signal(self):
        c = PrefixLengthCounter()
        assert c.add([(4, 24), (4, 24), (4, 32)])  # new lengths
        assert not c.add([(4, 24)])  # already present
        assert c.distinct() == ([32, 24], [])
        assert not c.delete([(4, 24)])  # refs remain (2 left)
        assert not c.delete([(4, 24)])
        assert c.delete([(4, 24)])  # last ref gone
        assert c.distinct() == ([32], [])
        with pytest.raises(ValueError):
            c.add([(4, 33)])

    def test_daemon_wiring_forces_rebuild(self):
        from cilium_tpu.daemon import Daemon

        d = Daemon()
        d.policy_add(json.dumps([{
            "endpointSelector": {"matchLabels": {"k8s:app": "web"}},
            "ingress": [{"fromCIDR": ["192.0.2.0/24"]}],
            "labels": ["k8s:policy=c1"],
        }]))
        assert d.prefix_lengths.distinct()[0] == [24]
        d.policy_delete(["k8s:policy=c1"])
        assert d.prefix_lengths.distinct() == ([], [])
        d.shutdown()


class TestTunnelChurn:
    def test_local_node_skipped_and_cidr_change_cleans_stale(self):
        from cilium_tpu.kvstore import InMemoryBackend, InMemoryStore
        from cilium_tpu.nodes.registry import Node, NodeRegistry

        store = InMemoryStore()
        local = NodeRegistry(
            InMemoryBackend(store, "l"),
            Node(name="local", ipv4="192.168.0.1",
                 ipv4_alloc_cidr="10.1.0.0/24"),
        )
        t = TunnelMap()
        t.observe_nodes(local)
        # the local node's own CIDR must never be tunnel-mapped
        assert t.lookup("10.1.0.5") is None
        remote_backend = InMemoryBackend(store, "r")
        NodeRegistry(
            remote_backend,
            Node(name="remote", ipv4="192.168.0.2",
                 ipv4_alloc_cidr="10.2.0.0/24"),
        )
        local.pump()
        assert t.lookup("10.2.0.9") == "192.168.0.2"
        # remote re-registers with a DIFFERENT alloc CIDR: the stale
        # prefix must disappear
        NodeRegistry(
            InMemoryBackend(store, "r2"),
            Node(name="remote", ipv4="192.168.0.2",
                 ipv4_alloc_cidr="10.3.0.0/24"),
        )
        local.pump()
        assert t.lookup("10.3.0.9") == "192.168.0.2"
        assert t.lookup("10.2.0.9") is None


class TestProxymapWiring:
    def test_redirect_records_proxymap_entry(self):
        from cilium_tpu.daemon import Daemon

        d = Daemon()
        d.policy_add(json.dumps([{
            "endpointSelector": {"matchLabels": {"k8s:app": "web"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"k8s:app": "client"}}],
                "toPorts": [{
                    "ports": [{"port": "80", "protocol": "TCP"}],
                    "rules": {"http": [{"method": "GET", "path": "/api/.*"}]},
                }],
            }],
            "labels": ["k8s:policy=l7p"],
        }]))
        d.endpoint_add(7, ["k8s:app=web"], ipv4="10.200.0.7")
        d.endpoint_add(9, ["k8s:app=client"], ipv4="10.200.0.9")
        import numpy as np

        from cilium_tpu.ops.lpm import ip_strings_to_u32

        ep = d.pipeline.endpoint_index(7)
        v, red = d.pipeline.process(
            ip_strings_to_u32(["10.200.0.9"]),
            np.array([ep], np.int32),
            np.array([80], np.int32), np.array([6], np.int32),
            ingress=True, sports=np.array([5555]),
        )
        assert bool(red[0])
        got = d.proxymap.lookup("10.200.0.9", 5555, "10.200.0.7", 80, 6)
        assert got is not None
        assert got.orig_dst_ip == "10.200.0.7" and got.orig_dst_port == 80
        client_identity = d.endpoint_manager.lookup(9).identity.id
        assert got.src_identity == client_identity
        d.shutdown()

    @pytest.mark.parametrize("family", [4, 6])
    @pytest.mark.parametrize("ingress", [True, False], ids=["ingress", "egress"])
    def test_batch_handoff_matches_per_flow(self, l7_daemon, family, ingress, request):
        d = l7_daemon
        device_ct = request.node.callspec.params["l7_daemon"] == "device_ct"
        rng = np.random.default_rng(family + 2 * ingress)
        n = 96
        peer = rng.integers(0, len(L7_PEERS[family]), n)
        ep = rng.integers(0, 2, n)
        dport = np.where(rng.random(n) < 0.7, 80, 81)     # 81: dropped
        sport = rng.integers(20000, 20064, n)
        rows = np.r_[np.arange(n), np.arange(8)]          # 8 repeated 5-tuples
        peer, ep, dport, sport = peer[rows], ep[rows], dport[rows], sport[rows]
        ep_ids = np.array(L7_WEB)[ep]
        before = len(d.proxymap)
        _, red = _submit_l7(d, family, [L7_PEERS[family][p] for p in peer],
                            ep_ids, dport, sport, ingress)
        assert (d.pipeline._device_ct is not None) == device_ct
        assert not red[dport == 81].any()
        assert set(peer[red].tolist()) == {0, 1, 2}       # every kind of peer
        want = {}
        for i in np.nonzero(red)[0]:
            k, v = _per_flow_entry(d, L7_PEERS[family][peer[i]], int(ep_ids[i]),
                                   int(sport[i]), int(dport[i]), ingress, family)
            want[k] = v
        assert {k: d.proxymap.lookup(*k) for k in want} == want
        assert len(d.proxymap) == before + len(want)
        listed = {(e["src"], e["dst"]): e for e in d.proxymap.items()}
        for (sip, sp, dip, dp, proto), v in want.items():
            assert listed[(f"{sip}:{sp}", f"{dip}:{dp}")] == {
                "src": f"{sip}:{sp}", "dst": f"{dip}:{dp}", "proto": proto,
                "orig_dst": f"{v.orig_dst_ip}:{v.orig_dst_port}",
                "src_identity": v.src_identity,
            }
        if ingress:   # /32 or /128, a wider CIDR only, no entry
            assert {v.src_identity for v in want.values()} == {
                d.endpoint_manager.lookup(L7_CLIENT).identity.id, 0}

    def test_one_call_and_one_increment_per_batch(self, l7_daemon, monkeypatch):
        d = l7_daemon
        calls, incs = [], []
        hook = d.pipeline.on_redirect_batch
        monkeypatch.setattr(d.pipeline, "on_redirect_batch",
                            lambda *a: (calls.append(len(a[1])), hook(*a)))
        for fam in (metrics.proxymap_handoff_flows_total,
                    metrics.proxymap_handoff_resolves_total):
            monkeypatch.setattr(fam, "inc", lambda labels=None, value=1.0, _f=fam.name:
                                incs.append((_f, value)))
        peers = ["10.200.0.9", "10.201.3.4", "10.200.0.9", "192.0.2.55", "10.200.0.9"]
        n = len(peers)
        ep_ids = np.full(n, L7_WEB[0])
        before = len(d.proxymap)
        # a batch with no redirect calls nothing and counts nothing
        _, red = _submit_l7(d, 4, peers, ep_ids, np.full(n, 81), np.arange(n), True)
        assert not red.any() and calls == [] and incs == []
        assert len(d.proxymap) == before
        _, red = _submit_l7(d, 4, peers, ep_ids, np.full(n, 80), np.arange(n), True)
        assert red.all() and calls == [n]
        assert incs == [("cilium_tpu_proxymap_handoff_flows_total", n),
                        ("cilium_tpu_proxymap_handoff_resolves_total", 3)]


L7_WEB, L7_CLIENT = (7, 8), 9
# per family: a peer with a host entry, one under a wider CIDR only,
# and one the ipcache does not know (identity 0)
L7_PEERS = {4: ["10.200.0.9", "10.201.3.4", "192.0.2.55"],
            6: ["fd00::9", "fd01::3:4", "2001:db8::55"]}


@pytest.fixture(scope="module", params=["host_ct", "device_ct"])
def l7_daemon(request):
    """A node whose web endpoints redirect port 80 to the proxy, in
    both directions, for the client endpoint, its wider CIDR and the
    world; on the host-CT or the fused device-CT dispatch."""
    from cilium_tpu.daemon import Daemon

    d = Daemon()
    if request.param == "device_ct":
        d.pipeline._device_ct_bits = 10
    l7 = [{"ports": [{"port": "80", "protocol": "TCP"}],
           "rules": {"http": [{"method": "GET", "path": "/api/.*"}]}}]
    client = {"matchLabels": {"k8s:app": "client"}}
    d.policy_add(json.dumps([{
        "endpointSelector": {"matchLabels": {"k8s:app": "web"}},
        "ingress": [{"fromEndpoints": [client], "toPorts": l7},
                    {"fromEntities": ["world"], "toPorts": l7}],
        "egress": [{"toEndpoints": [client], "toPorts": l7},
                   {"toEntities": ["world"], "toPorts": l7}],
        "labels": ["k8s:policy=l7-both-ways"],
    }]))
    for ep_id in L7_WEB:
        d.endpoint_add(ep_id, ["k8s:app=web"], ipv4=f"10.200.0.{ep_id}",
                       ipv6=f"fd00::{ep_id}")
    d.endpoint_add(L7_CLIENT, ["k8s:app=client"], ipv4="10.200.0.9", ipv6="fd00::9")
    ident = d.endpoint_manager.lookup(L7_CLIENT).identity.id
    d.ipcache.upsert("10.201.0.0/16", ident, source="k8s")
    d.ipcache.upsert("fd01::/64", ident, source="k8s")
    yield d
    d.shutdown()


def _submit_l7(d, family, peers, ep_ids, dports, sports, ingress):
    from cilium_tpu.ops.lpm import ip_strings_to_u32, ipv6_to_bytes

    n = len(peers)
    ep_idx = np.array([d.pipeline.endpoint_index(int(e)) for e in ep_ids], np.int32)
    args = (ep_idx, np.asarray(dports, np.int32), np.full(n, 6, np.int32))
    kw = dict(ingress=ingress, sports=np.asarray(sports))
    if family == 4:
        return d.pipeline.submit(ip_strings_to_u32(peers), *args, **kw).result()
    return d.pipeline.submit_v6(ipv6_to_bytes(peers), *args, **kw).result()


def _per_flow_entry(d, peer_ip, ep_id, sport, dport, ingress, family):
    """One redirected flow's proxymap entry, built the plain way."""
    ep = d.endpoint_manager.lookup(ep_id)
    ep_ip = ep.ipv4 if family == 4 else ep.ipv6
    if ingress:
        e = d.ipcache.lookup_by_ip(peer_ip)
        return ((peer_ip, sport, ep_ip, dport, 6),
                ProxyValue(ep_ip, dport, e.identity if e else 0))
    return ((ep_ip, sport, peer_ip, dport, 6),
            ProxyValue(peer_ip, dport, ep.identity.id))


class TestIPAMRestore:
    def test_restore_reclaims_ips(self, tmp_path):
        from cilium_tpu.daemon import Daemon

        d = Daemon(state_dir=str(tmp_path))
        ip = d.ipam.allocate_next("cni")
        d.endpoint_add(7, ["k8s:app=web"], ipv4=ip)
        d.shutdown()
        d2 = Daemon(state_dir=str(tmp_path))
        # the restored endpoint's IP is reserved again — a fresh
        # allocation must not collide with it
        assert d2.ipam.owner_of(ip) is not None
        assert d2.ipam.allocate_next("new") != ip
        d2.shutdown()


class TestIPUtil:
    def test_coalesce(self):
        assert coalesce_cidrs(["10.0.0.0/25", "10.0.0.128/25"]) == ["10.0.0.0/24"]
        assert coalesce_cidrs(["10.0.0.0/8", "10.1.0.0/16"]) == ["10.0.0.0/8"]

    def test_range_to_cidrs(self):
        assert range_to_cidrs("10.0.0.0", "10.0.0.255") == ["10.0.0.0/24"]
        out = range_to_cidrs("10.0.0.1", "10.0.0.6")
        import ipaddress

        covered = set()
        for c in out:
            covered |= set(ipaddress.ip_network(c))
        assert covered == {ipaddress.ip_address(f"10.0.0.{i}") for i in range(1, 7)}

    def test_remove_cidrs(self):
        out = remove_cidrs(["10.0.0.0/24"], ["10.0.0.128/25"])
        assert out == ["10.0.0.0/25"]
        assert remove_cidrs(["10.0.0.0/24"], ["10.0.0.0/16"]) == []

    def test_prefix_lengths_of(self):
        assert prefix_lengths_of(["10.0.0.0/24", "fd00::/64"]) == [
            (4, 24), (6, 64),
        ]


class TestLogging:
    def test_structured_fields_and_json(self):
        buf = io.StringIO()
        setup("debug", as_json=True, stream=buf)
        log = get_logger("policy", endpointID=7)
        log.info("regenerated", fields={"policyRevision": 3})
        rec = json.loads(buf.getvalue())
        assert rec["subsys"] == "policy" and rec["level"] == "info"
        assert rec["endpointID"] == 7 and rec["policyRevision"] == 3
        # plain format carries key=values too
        buf2 = io.StringIO()
        setup("info", as_json=False, stream=buf2)
        log.with_fields(ipAddr="10.0.0.1").warning("drop observed")
        assert "ipAddr=10.0.0.1" in buf2.getvalue()
        setup("info")  # restore default stderr handler


class TestIPAM:
    def test_allocate_release_cycle(self):
        pool = IPAM("10.200.0.0/29", reserve_base=2)  # 8 addrs, tiny
        ips = [pool.allocate_next("a"), pool.allocate_next("b")]
        assert ips == ["10.200.0.2", "10.200.0.3"]
        assert pool.owner_of(ips[0]) == "a"
        # broadcast + reserved are never handed out
        remaining = []
        while True:
            try:
                remaining.append(pool.allocate_next())
            except IPAMError:
                break
        assert "10.200.0.7" not in ips + remaining  # broadcast
        assert "10.200.0.0" not in ips + remaining
        assert pool.release(ips[0]) and not pool.release(ips[0])
        assert pool.allocate_next() == ips[0]  # reuse released

    def test_explicit_allocate(self):
        pool = IPAM("10.200.0.0/24")
        assert pool.allocate("10.200.0.77", "restore") == "10.200.0.77"
        with pytest.raises(IPAMError):
            pool.allocate("10.200.0.77")
        with pytest.raises(IPAMError):
            pool.allocate("10.201.0.1")


class TestCNIAndWorkloads:
    def test_cni_add_del(self):
        from cilium_tpu.daemon import Daemon
        from cilium_tpu.plugins.cni import cni_add, cni_del

        d = Daemon()
        res = cni_add(d, "abc123def456", labels=["container:app=web"])
        assert res.ipv4 and res.endpoint_id >= 4096
        ep = d.endpoint_manager.lookup(res.endpoint_id)
        assert ep is not None and ep.ipv4 == res.ipv4
        assert d.lxcmap.lookup(res.ipv4).endpoint_id == res.endpoint_id
        assert cni_del(d, "abc123def456")
        assert d.endpoint_manager.lookup(res.endpoint_id) is None
        assert d.ipam.owner_of(res.ipv4) is None
        assert not cni_del(d, "abc123def456")  # idempotent
        d.shutdown()

    def test_workload_watcher_sync(self):
        from cilium_tpu.daemon import Daemon
        from cilium_tpu.workloads import (
            ContainerInfo,
            IGNORE_LABEL,
            WorkloadWatcher,
        )

        class FakeRuntime:
            def __init__(self):
                self.live = []

            def containers(self):
                return list(self.live)

        d = Daemon()
        rt = FakeRuntime()
        w = WorkloadWatcher(d, rt)
        rt.live = [
            ContainerInfo(id="c1" * 6, labels={"app": "web"}),
            ContainerInfo(id="c2" * 6, labels={IGNORE_LABEL: "true"}),
        ]
        assert w.sync() == 1  # ignored container skipped
        ep_id = w.endpoint_of("c1" * 6)
        ep = d.endpoint_manager.lookup(ep_id)
        assert any("container:app=web" == str(l) for l in ep.labels)
        # container dies → endpoint removed on next sync
        rt.live = []
        assert w.sync() == 1
        assert d.endpoint_manager.lookup(ep_id) is None
        d.shutdown()

    def test_ipam_rest(self, tmp_path):
        from cilium_tpu.api.client import APIClient
        from cilium_tpu.api.server import APIServer
        from cilium_tpu.daemon import Daemon

        d = Daemon()
        srv = APIServer(d, str(tmp_path / "api.sock"))
        srv.start()
        try:
            c = APIClient(str(tmp_path / "api.sock"))
            out = c.ipam_allocate(owner="cni")
            assert out["ip"].startswith("10.200.")
            assert c.ipam_release(out["ip"])["released"]
        finally:
            srv.stop()
            d.shutdown()
