"""Real interface plumbing + real packets through the enforcement
front-end.

Closes the 'virtual interface' gap: the CNI
layer creates ACTUAL veth pairs into ACTUAL network namespaces
(plugins/netns.py — the cilium-cni.go interface sequence), container
processes send REAL UDP packets, and the wire front-end
(datapath/wire.py) captures them off the host-side lxc* device and
runs them through the DatapathPipeline — netns → veth → AF_PACKET →
5-tuple parse → policy verdict, end to end.

Skips cleanly on hosts without CAP_NET_ADMIN/iproute2.
"""

from __future__ import annotations

import time
import uuid

import pytest

from cilium_tpu.plugins import netns as nsmod

pytestmark = pytest.mark.skipif(
    not nsmod.have_netns(), reason="no netns/veth capability"
)


@pytest.fixture
def world(tmp_path):
    """Daemon + policy: 'web' accepts UDP 9053 from 'client' only."""
    import json

    from cilium_tpu.daemon import Daemon

    d = Daemon(state_dir=str(tmp_path / "state"), pod_cidr="10.77.0.0/24")
    d.policy_add(json.dumps([{
        "endpointSelector": {"matchLabels": {"k8s:app": "web"}},
        "ingress": [{
            "fromEndpoints": [{"matchLabels": {"k8s:app": "client"}}],
            "toPorts": [{"ports": [{"port": "9053", "protocol": "UDP"}]}],
        }],
        "labels": ["k8s:policy=wire"],
    }]))
    containers = []
    namespaces = []
    yield d, containers, namespaces
    from cilium_tpu.plugins.cni import cni_del

    for cid in containers:
        try:
            cni_del(d, cid)
        except Exception:
            pass
    for ns in namespaces:
        nsmod.delete_netns(ns)
    d.shutdown()


def _container(d, containers, namespaces, app: str):
    """netns + real CNI ADD → (container_id, CNIResult, netns name)."""
    from cilium_tpu.plugins.cni import cni_add

    cid = f"{app}-{uuid.uuid4().hex[:8]}"
    ns = f"ctpu-{cid[:10]}"
    nsmod.create_netns(ns)
    namespaces.append(ns)
    res = cni_add(d, cid, labels=[f"k8s:app={app}"], netns=ns)
    containers.append(cid)
    return cid, res, ns


class TestRealInterfaces:
    def test_veth_exists_and_container_connectivity(self, world):
        """ADD plumbs a working interface: the container reaches the
        host end (gateway) with a real UDP datagram."""
        d, containers, namespaces = world
        _cid, res, ns = _container(d, containers, namespaces, "client")
        # host side exists
        assert nsmod._run("link", "show", res.interface).returncode == 0
        # container side carries the allocated address
        out = nsmod.netns_run(ns, ["ip", "-o", "addr", "show", "eth0"])
        assert res.ipv4 in out.stdout
        # a REAL datagram crosses the veth to a host listener bound on
        # the gateway address
        import socket as _socket
        import threading

        got = []
        srv = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        srv.bind((res.gateway, 9999))
        srv.settimeout(5)

        def rx():
            try:
                got.append(srv.recvfrom(1024))
            except OSError:
                pass

        t = threading.Thread(target=rx, daemon=True)
        t.start()
        code = (
            "import socket;"
            "s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM);"
            f"s.sendto(b'hello-wire', ('{res.gateway}', 9999))"
        )
        r = nsmod.netns_run(ns, ["python3", "-c", code])
        assert r.returncode == 0, r.stderr
        t.join(timeout=5)
        srv.close()
        assert got and got[0][0] == b"hello-wire"
        assert got[0][1][0] == res.ipv4  # source = the endpoint address

    def test_del_removes_interface(self, world):
        from cilium_tpu.plugins.cni import cni_del

        d, containers, namespaces = world
        cid, res, _ns = _container(d, containers, namespaces, "client")
        assert nsmod._run("link", "show", res.interface).returncode == 0
        assert cni_del(d, cid)
        assert nsmod._run(
            "link", "show", res.interface, check=False
        ).returncode != 0
        containers.remove(cid)

    def test_failure_rolls_back_interface_and_ip(self, world):
        """Endpoint registration failure must remove the created veth
        and release the address (the reference's error path)."""
        from cilium_tpu.plugins.cni import CNIError, cni_add

        d, containers, namespaces = world
        ns = f"ctpu-rb-{uuid.uuid4().hex[:6]}"
        nsmod.create_netns(ns)
        namespaces.append(ns)
        allocated_before = len(d.ipam)
        real_add = d.endpoint_add
        d.endpoint_add = lambda *a, **k: (_ for _ in ()).throw(
            ValueError("forced registration failure")
        )
        try:
            with pytest.raises(CNIError):
                cni_add(d, "rollback-case", labels=["k8s:app=x"], netns=ns)
        finally:
            d.endpoint_add = real_add
        from cilium_tpu.plugins.cni import endpoint_id_for

        host_if = f"lxc{endpoint_id_for('rollback-case')}"[:15]
        assert nsmod._run(
            "link", "show", host_if, check=False
        ).returncode != 0, "veth leaked after failed ADD"
        assert len(d.ipam) == allocated_before, "IP leaked"


class TestRealPacketsThroughPipeline:
    def test_wire_verdicts_match_policy(self, world):
        """Two containers send real UDP to the web endpoint's address;
        the AF_PACKET front-end on their host veths verdicts every
        captured flow: client allowed, other denied — with CT creation
        for the allowed flow (sports flow through)."""
        from cilium_tpu.datapath import DROP_POLICY, FORWARD
        from cilium_tpu.datapath.wire import VethSniffer, WireEnforcer

        d, containers, namespaces = world
        _c1, res_client, ns_client = _container(
            d, containers, namespaces, "client"
        )
        _c2, res_other, ns_other = _container(
            d, containers, namespaces, "other"
        )
        _c3, res_web, _ns_web = _container(d, containers, namespaces, "web")

        sniffers = [
            VethSniffer(res_client.interface).start(),
            VethSniffer(res_other.interface).start(),
        ]
        enforcer = WireEnforcer(
            d.pipeline, {res_web.ipv4: res_web.endpoint_id}
        )
        try:
            send = (
                "import socket;"
                "s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM);"
                "[s.sendto(b'x', ('{dst}', 9053)) for _ in range(5)]"
            )
            for ns in (ns_client, ns_other):
                r = nsmod.netns_run(
                    ns, ["python3", "-c", send.format(dst=res_web.ipv4)]
                )
                assert r.returncode == 0, r.stderr
            n = enforcer.run_from(sniffers, duration=4.0)
            assert n >= 10, f"only {n} real flows enforced"
            counts = enforcer.verdicts[res_web.endpoint_id]
            # client's packets forwarded, other's dropped by policy
            assert counts.get(int(FORWARD), 0) >= 5, counts
            assert counts.get(int(DROP_POLICY), 0) >= 5, counts
        finally:
            for s in sniffers:
                s.stop()
