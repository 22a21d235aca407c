"""External proxy process e2e: a REAL second process subscribes
NPDS/NPHDS over the xDS socket, enforces HTTP on real TCP connections
(403 on deny), and streams access logs back over the accesslog socket.

Reference analog: the cilium-agent ↔ cilium-envoy split —
pkg/envoy/envoy.go:76-143 (lifecycle), envoy/cilium_l7policy.cc (per-
request enforcement), pkg/envoy/accesslog_server.go:50 (log return
path), pkg/launcher (restart supervision).
"""

from __future__ import annotations

import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from cilium_tpu.proxy.accesslog import AccessLogServer, AccessLogSocketServer
from cilium_tpu.proxy.launcher import ProxyLauncher
from cilium_tpu.proxy.standalone import StandaloneProxy
from cilium_tpu.xds.cache import (
    NETWORK_POLICY_HOSTS_TYPE,
    NETWORK_POLICY_TYPE,
    ResourceCache,
)
from cilium_tpu.xds.server import XDSServer

CLIENT_IDENTITY = 1001


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http_get(port: int, path: str, source: str = "127.0.0.1") -> int:
    """One HTTP/1.1 GET over a raw socket → status code. ``source``
    selects the loopback alias to bind (the NPHDS identity input)."""
    c = socket.socket()
    c.bind((source, 0))
    c.settimeout(60.0)  # generous: on a loaded single-CPU host the
    # child's first request can wait on interpreter start + imports
    c.connect(("127.0.0.1", port))
    c.sendall(
        f"GET {path} HTTP/1.1\r\nHost: svc.local\r\n\r\n".encode()
    )
    data = b""
    while b"\r\n" not in data:
        chunk = c.recv(4096)
        if not chunk:
            break
        data += chunk
    c.close()
    return int(data.split(b" ", 2)[1])


def _try_get(port: int, path: str, source: str = "127.0.0.1"):
    """_http_get, but None while the listener isn't up yet (poll-safe
    for _wait_for conditions)."""
    try:
        return _http_get(port, path, source)
    except OSError:
        return None


def _publish_world(cache: ResourceCache, proxy_port: int, kafka_port: int = 0):
    """NPDS: endpoint 7 allows only /public/* from CLIENT_IDENTITY on
    port 80; NPHDS: 127.0.0.1 = client identity, 127.0.0.2 stays
    unmapped (world)."""
    l7_ports = [{
        "port": 80,
        "ingress": True,
        "parser": "http",
        "proxy_port": proxy_port,
        "http_rules": [
            {"path": "/public/.*", "remote_policies": [CLIENT_IDENTITY]}
        ],
    }]
    if kafka_port:
        l7_ports.append({
            "port": 9092,
            "ingress": True,
            "parser": "kafka",
            "proxy_port": kafka_port,
            "kafka_rules": [
                {"topic": "allowed", "remote_policies": [CLIENT_IDENTITY]}
            ],
        })
    cache.upsert(NETWORK_POLICY_TYPE, "7", {"endpoint_id": 7, "l7_ports": l7_ports})
    cache.upsert(
        NETWORK_POLICY_HOSTS_TYPE, str(CLIENT_IDENTITY),
        {"policy": CLIENT_IDENTITY, "host_addresses": ["127.0.0.1/32"]},
    )


@pytest.fixture
def control_plane(tmp_path):
    """Agent-side xDS server + accesslog receiver."""
    xds_path = str(tmp_path / "xds.sock")
    al_path = str(tmp_path / "accesslog.sock")
    cache = ResourceCache()
    server = XDSServer(cache, xds_path)
    server.start()
    sink = AccessLogServer()
    rx = AccessLogSocketServer(sink, al_path).start()
    yield cache, xds_path, al_path, sink
    rx.stop()
    server.stop()


def _wait_for(cond, timeout: float = 5.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return False


class TestExternalProcess:
    def test_second_process_enforces_403_and_streams_logs(self, control_plane):
        cache, xds_path, al_path, sink = control_plane
        proxy_port = _free_port()
        _publish_world(cache, proxy_port)
        proc = subprocess.Popen(
            [sys.executable, "-m", "cilium_tpu.proxy",
             "--xds", xds_path, "--accesslog", al_path],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
        )
        try:
            assert proc.stdout.readline().strip() == "READY"
            # Each request's record is awaited before the next request:
            # the assertion below is about per-request content, and
            # records from concurrent connections have no defined order
            # (each connection thread logs after its response is sent).
            # allowed: client identity + allowed path
            assert _http_get(proxy_port, "/public/index") == 200
            assert _wait_for(lambda: len(sink.recent()) >= 1, timeout=30)
            # denied path → 403 from the OTHER process
            assert _http_get(proxy_port, "/secret") == 403
            assert _wait_for(lambda: len(sink.recent()) >= 2, timeout=30)
            # denied identity (unmapped 127.0.0.2 → world) → 403
            assert _http_get(proxy_port, "/public/index", source="127.0.0.2") == 403
            # access logs crossed the process boundary
            assert _wait_for(lambda: len(sink.recent()) >= 3, timeout=30)
            recs = sink.recent()
            verdicts = [r.verdict for r in recs[-3:]]
            assert verdicts == ["Forwarded", "Denied", "Denied"]
            assert recs[-3].src_identity == CLIENT_IDENTITY
            assert recs[-3].http["code"] == 200
            assert recs[-2].http["code"] == 403
        finally:
            proc.terminate()
            proc.wait(timeout=5)

    def test_policy_update_swaps_enforcement_live(self, control_plane):
        """NPDS push while the child is running must change verdicts
        without a restart (the ACK'd dynamic-update contract)."""
        cache, xds_path, al_path, sink = control_plane
        proxy_port = _free_port()
        _publish_world(cache, proxy_port)
        proc = subprocess.Popen(
            [sys.executable, "-m", "cilium_tpu.proxy",
             "--xds", xds_path, "--accesslog", al_path],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            assert proc.stdout.readline().strip() == "READY"
            assert _http_get(proxy_port, "/secret") == 403
            # widen the policy: allow everything on the port
            cache.upsert(NETWORK_POLICY_TYPE, "7", {
                "endpoint_id": 7,
                "l7_ports": [{
                    "port": 80, "ingress": True, "parser": "http",
                    "proxy_port": proxy_port, "http_rules": [
                        {"path": "/.*", "remote_policies": [CLIENT_IDENTITY]}
                    ],
                }],
            })
            assert _wait_for(
                lambda: _try_get(proxy_port, "/secret") == 200, timeout=5.0
            )
        finally:
            proc.terminate()
            proc.wait(timeout=5)


class TestLauncher:
    def test_launcher_restarts_killed_child(self, control_plane):
        cache, xds_path, al_path, _sink = control_plane
        proxy_port = _free_port()
        _publish_world(cache, proxy_port)
        launcher = ProxyLauncher(
            xds_path, al_path, restart_backoff_s=0.1
        ).start()
        try:
            assert _wait_for(lambda: launcher.pid() is not None)
            pid1 = launcher.pid()
            assert _wait_for(
                lambda: _try_get(proxy_port, "/public/x") == 200, timeout=10.0
            )
            import os
            import signal as _signal

            os.kill(pid1, _signal.SIGKILL)
            assert _wait_for(
                lambda: launcher.pid() not in (None, pid1), timeout=10.0
            ), "launcher did not respawn the proxy"
            assert launcher.restarts >= 1
            # the respawned child re-subscribes and enforces again
            assert _wait_for(
                lambda: _try_get(proxy_port, "/public/x") == 200, timeout=10.0
            )
        finally:
            launcher.stop()

    def test_sidecar_stays_off_the_accelerator(self, monkeypatch):
        """The agent holds the chip; a sidecar opening it would fail
        or hang, so every child is spawned pinned to the host CPU."""
        from cilium_tpu.proxy import launcher as launcher_mod

        seen = {}

        def fake_popen(argv, **kw):
            seen.update(kw["env"])
            raise OSError("not spawned in this test")

        monkeypatch.setattr(launcher_mod.subprocess, "Popen", fake_popen)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        with pytest.raises(OSError):
            ProxyLauncher("/nonexistent.xds")._spawn()
        assert seen["JAX_PLATFORMS"] == "cpu"


class TestKafkaWire:
    def test_kafka_reject_and_upstream_relay(self, control_plane):
        """Kafka over real sockets: denied topic gets a synthesized
        reject frame; allowed topic is forwarded to the upstream broker
        and its response relayed back (pkg/proxy/kafka.go)."""
        cache, xds_path, al_path, sink = control_plane
        kafka_port = _free_port()
        upstream_port = _free_port()
        _publish_world(cache, _free_port(), kafka_port=kafka_port)

        # fake broker: echo a fixed response frame per request
        def broker():
            srv = socket.socket()
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind(("127.0.0.1", upstream_port))
            srv.listen(4)
            srv.settimeout(5.0)
            try:
                conn, _ = srv.accept()
                while True:
                    hdr = conn.recv(4)
                    if len(hdr) < 4:
                        return
                    (size,) = struct.unpack(">i", hdr)
                    body = b""
                    while len(body) < size:
                        chunk = conn.recv(size - len(body))
                        if not chunk:
                            return
                        body += chunk
                    cid = struct.unpack(">i", body[4:8])[0]
                    resp = struct.pack(">i", cid) + b"BROKER"
                    conn.sendall(struct.pack(">i", len(resp)) + resp)
            except socket.timeout:
                pass
            finally:
                srv.close()

        t = threading.Thread(target=broker, daemon=True)
        t.start()
        proxy = StandaloneProxy(
            xds_path, al_path, upstream=("127.0.0.1", upstream_port)
        )
        try:
            assert proxy.wait_ready()

            def produce(topic: str, cid: int) -> bytes:
                body = struct.pack(">hhi", 0, 0, cid)
                body += struct.pack(">h", 1) + b"c"  # client id
                body += struct.pack(">hi", 1, 30000)  # acks, timeout
                body += struct.pack(">i", 1)
                body += struct.pack(">h", len(topic)) + topic.encode()
                body += struct.pack(">i", 1)
                body += struct.pack(">ii", 0, 4) + b"\x00" * 4
                return struct.pack(">i", len(body)) + body

            c = socket.create_connection(("127.0.0.1", kafka_port), timeout=5)
            # denied topic → reject frame with correlation id + error 29
            c.sendall(produce("forbidden", 42))
            hdr = c.recv(4)
            (size,) = struct.unpack(">i", hdr)
            body = b""
            while len(body) < size:
                body += c.recv(size - len(body))
            assert struct.unpack(">i", body[:4])[0] == 42
            assert struct.pack(">h", 29) in body  # authorization failed
            # allowed topic → relayed broker response
            c.sendall(produce("allowed", 43))
            hdr = c.recv(4)
            (size,) = struct.unpack(">i", hdr)
            body = b""
            while len(body) < size:
                body += c.recv(size - len(body))
            assert struct.unpack(">i", body[:4])[0] == 43
            assert body[4:] == b"BROKER"
            c.close()
            assert _wait_for(lambda: len(sink.recent()) >= 2)
            v = [r.verdict for r in sink.recent()[-2:]]
            assert v == ["Denied", "Forwarded"]
        finally:
            proxy.close()


class TestKeepAlive:
    def test_multiple_requests_one_connection(self, control_plane):
        """HTTP/1.1 keep-alive: one TCP connection carries several
        requests, each policy-checked independently; Connection: close
        ends it."""
        cache, xds_path, al_path, sink = control_plane
        proxy_port = _free_port()
        _publish_world(cache, proxy_port)
        proxy = StandaloneProxy(xds_path, al_path)
        try:
            assert proxy.wait_ready()
            c = socket.create_connection(("127.0.0.1", proxy_port), timeout=10)
            c.settimeout(10)

            def roundtrip(path, body=b"", close=False):
                hdrs = f"POST {path} HTTP/1.1\r\nHost: h\r\n" \
                       f"content-length: {len(body)}\r\n"
                if close:
                    hdrs += "Connection: close\r\n"
                c.sendall(hdrs.encode() + b"\r\n" + body)
                data = b""
                while b"\r\n\r\n" not in data:
                    data += c.recv(4096)
                head, _, rest = data.partition(b"\r\n\r\n")
                clen = int([l for l in head.split(b"\r\n")
                            if l.lower().startswith(b"content-length")][0].split(b":")[1])
                while len(rest) < clen:
                    rest += c.recv(4096)
                return int(head.split(b" ")[1])

            assert roundtrip("/public/a", body=b"xyz") == 200
            assert roundtrip("/secret") == 403  # same connection
            assert roundtrip("/public/b") == 200  # still alive after a 403
            assert roundtrip("/public/c", close=True) == 200
            # server honors Connection: close
            assert c.recv(4096) == b""
            c.close()
        finally:
            proxy.close()

    def test_pipelined_requests(self, control_plane):
        """Two requests sent back-to-back before reading: the carry
        buffer must hand request 2's head to the next iteration."""
        cache, xds_path, al_path, sink = control_plane
        proxy_port = _free_port()
        _publish_world(cache, proxy_port)
        proxy = StandaloneProxy(xds_path, al_path)
        try:
            assert proxy.wait_ready()
            c = socket.create_connection(("127.0.0.1", proxy_port), timeout=10)
            c.settimeout(10)
            c.sendall(b"GET /public/1 HTTP/1.1\r\nHost: h\r\n\r\n"
                      b"GET /secret HTTP/1.1\r\nHost: h\r\n\r\n")
            data = b""
            deadline = time.monotonic() + 10
            while data.count(b"HTTP/1.1") < 2 and time.monotonic() < deadline:
                data += c.recv(4096)
            codes = [int(seg.split(b" ")[0])
                     for seg in data.split(b"HTTP/1.1 ")[1:]]
            assert codes == [200, 403], codes
            c.close()
        finally:
            proxy.close()


def test_pipelined_bytes_never_smuggled_upstream(control_plane):
    """With an upstream configured, the over-read tail of an allowed
    request (a pipelined second request policy would deny) must not be
    relayed upstream unchecked — only the current request's bytes go."""
    cache, xds_path, al_path, sink = control_plane
    proxy_port = _free_port()
    _publish_world(cache, proxy_port)
    # capture-everything upstream
    up_srv = socket.socket()
    up_srv.bind(("127.0.0.1", 0))
    up_srv.listen(1)
    got = []

    def upstream():
        conn, _ = up_srv.accept()
        conn.settimeout(2)
        buf = b""
        try:
            while True:
                chunk = conn.recv(4096)
                if not chunk:
                    break
                buf += chunk
                conn.sendall(b"HTTP/1.1 200 OK\r\ncontent-length: 0\r\n\r\n")
        except socket.timeout:
            pass
        got.append(buf)
        conn.close()

    t = threading.Thread(target=upstream, daemon=True)
    t.start()
    proxy = StandaloneProxy(
        xds_path, al_path, upstream=up_srv.getsockname()
    )
    try:
        assert proxy.wait_ready()
        c = socket.create_connection(("127.0.0.1", proxy_port), timeout=10)
        body = b"xy"
        c.sendall(
            b"POST /public/a HTTP/1.1\r\nHost: h\r\ncontent-length: 2\r\n\r\n"
            + body
            + b"GET /secret HTTP/1.1\r\nHost: h\r\n\r\n"  # pipelined, denied
        )
        time.sleep(1.0)
        c.close()
        t.join(timeout=5)
        assert got, "upstream saw nothing"
        assert b"/public/a" in got[0]
        assert b"/secret" not in got[0], "pipelined request smuggled upstream"
    finally:
        proxy.close()
        up_srv.close()


class TestFramingStrictness:
    def test_policy_update_applies_to_live_keepalive_connection(self, control_plane):
        """An NPDS push must change verdicts for the NEXT request on an
        ALREADY-OPEN keep-alive connection (stale-policy regression)."""
        cache, xds_path, al_path, sink = control_plane
        proxy_port = _free_port()
        _publish_world(cache, proxy_port)
        proxy = StandaloneProxy(xds_path, al_path)
        try:
            assert proxy.wait_ready()
            c = socket.create_connection(("127.0.0.1", proxy_port), timeout=10)
            c.settimeout(10)

            def get(path):
                c.sendall(f"GET {path} HTTP/1.1\r\nHost: h\r\n\r\n".encode())
                d = b""
                while b"\r\n\r\n" not in d:
                    d += c.recv(4096)
                while not (b"OK\n" in d or b"denied" in d):
                    d += c.recv(4096)
                return int(d.split(b" ")[1])

            assert get("/secret") == 403
            # widen policy while the connection stays open
            cache.upsert(NETWORK_POLICY_TYPE, "7", {
                "endpoint_id": 7,
                "l7_ports": [{
                    "port": 80, "ingress": True, "parser": "http",
                    "proxy_port": proxy_port,
                    "http_rules": [{"path": "/.*",
                                    "remote_policies": [CLIENT_IDENTITY]}],
                }],
            })
            deadline = time.monotonic() + 10
            code = 403
            while code != 200 and time.monotonic() < deadline:
                time.sleep(0.2)
                code = get("/secret")
            assert code == 200  # same connection, new policy
            c.close()
        finally:
            proxy.close()

    def test_duplicate_and_invalid_content_length_rejected(self, control_plane):
        """CL.CL smuggling / parser-desync inputs get 400 + close."""
        cache, xds_path, al_path, sink = control_plane
        proxy_port = _free_port()
        _publish_world(cache, proxy_port)
        proxy = StandaloneProxy(xds_path, al_path)
        try:
            assert proxy.wait_ready()
            for bad in (
                b"POST /public/a HTTP/1.1\r\nHost: h\r\n"
                b"content-length: 0\r\ncontent-length: 60\r\n\r\n",
                b"POST /public/a HTTP/1.1\r\nHost: h\r\n"
                b"content-length: -5\r\n\r\n",
                b"POST /public/a HTTP/1.1\r\nHost: h\r\n"
                b"content-length: 5, 5\r\n\r\n",
            ):
                c = socket.create_connection(
                    ("127.0.0.1", proxy_port), timeout=10
                )
                c.settimeout(10)
                c.sendall(bad)
                d = b""
                while b"\r\n\r\n" not in d:
                    chunk = c.recv(4096)
                    if not chunk:
                        break
                    d += chunk
                assert b" 400 " in d, (bad, d)
                assert c.recv(4096) == b""  # connection closed
                c.close()
        finally:
            proxy.close()
