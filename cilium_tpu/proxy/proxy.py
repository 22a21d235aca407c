"""Proxy manager: redirect lifecycle + proxy-port allocation.

Reference: pkg/proxy/proxy.go — port allocator in [10000, 20000)
(:86,122), `CreateOrUpdateRedirect` dispatching per L7 parser kind
(:144), `Redirect`/`RedirectImplementation` (redirect.go:31,36), and
removal with port reuse. The redirect's enforcement engine here is the
compiled HTTPPolicy / KafkaACL (cilium_tpu.l7) instead of an external
Envoy process; `check_http`/`check_kafka` are the per-request hooks the
datapath front-end calls for flows whose policymap entry redirects.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..l7.http_policy import HTTPPolicy, HTTPRequest
from ..l7.kafka_policy import KafkaACL, KafkaRequest
from ..observe.tracer import NOOP_BATCH, Tracer
from ..option import get_config
from .accesslog import (
    AccessLogServer,
    LogRecord,
    TYPE_REQUEST,
    VERDICT_DENIED,
    VERDICT_FORWARDED,
)

PARSER_HTTP = "http"
PARSER_KAFKA = "kafka"


class Redirect:
    """One (endpoint, port, direction) L7 redirect (redirect.go:31)."""

    def __init__(
        self,
        endpoint_id: int,
        dst_port: int,
        parser: str,
        proxy_port: int,
        ingress: bool = True,
    ) -> None:
        self.endpoint_id = endpoint_id
        self.dst_port = dst_port
        self.parser = parser
        self.proxy_port = proxy_port
        self.ingress = ingress
        self.http_policy: Optional[HTTPPolicy] = None
        self.kafka_acl: Optional[KafkaACL] = None
        self.created = time.time()

    @property
    def key(self) -> str:
        d = "ingress" if self.ingress else "egress"
        return f"{self.endpoint_id}:{self.dst_port}:{d}"


class Proxy:
    def __init__(
        self,
        accesslog: Optional[AccessLogServer] = None,
        tracer: Optional[Tracer] = None,
    ) -> None:
        # the daemon passes its pipeline's tracer: while PhaseTracing is
        # on each check_http batch is a ``proxy-http`` trace
        self.tracer = tracer
        cfg = get_config()
        self._port_min = cfg.proxy_port_min
        self._port_max = cfg.proxy_port_max
        self._next_port = self._port_min
        self._lock = threading.RLock()
        self._redirects: Dict[str, Redirect] = {}
        self._ports_in_use: Set[int] = set()
        self.accesslog = accesslog or AccessLogServer()

    # -- port allocator (proxy.go:122 allocatePort) ---------------------
    def _allocate_port(self) -> int:
        with self._lock:
            for _ in range(self._port_max - self._port_min):
                port = self._next_port
                self._next_port += 1
                if self._next_port >= self._port_max:
                    self._next_port = self._port_min
                if port not in self._ports_in_use:
                    self._ports_in_use.add(port)
                    return port
        raise RuntimeError("proxy port range exhausted")

    # -- redirect lifecycle ---------------------------------------------
    def create_or_update_redirect(
        self,
        endpoint_id: int,
        dst_port: int,
        parser: str,
        *,
        ingress: bool = True,
        http_policy: Optional[HTTPPolicy] = None,
        kafka_acl: Optional[KafkaACL] = None,
    ) -> Redirect:
        """CreateOrUpdateRedirect (proxy.go:144): same key updates rules
        in place and keeps the proxy port."""
        with self._lock:
            key = f"{endpoint_id}:{dst_port}:{'ingress' if ingress else 'egress'}"
            r = self._redirects.get(key)
            if r is None:
                r = Redirect(endpoint_id, dst_port, parser, self._allocate_port(), ingress)
                self._redirects[key] = r
            elif r.parser != parser:
                raise ValueError(f"parser conflict on {key}: {r.parser} vs {parser}")
            r.http_policy = http_policy
            r.kafka_acl = kafka_acl
            return r

    def remove_redirect(self, endpoint_id: int, dst_port: int, ingress: bool = True) -> bool:
        with self._lock:
            key = f"{endpoint_id}:{dst_port}:{'ingress' if ingress else 'egress'}"
            r = self._redirects.pop(key, None)
            if r is None:
                return False
            self._ports_in_use.discard(r.proxy_port)
            return True

    def lookup(self, endpoint_id: int, dst_port: int, ingress: bool = True) -> Optional[Redirect]:
        key = f"{endpoint_id}:{dst_port}:{'ingress' if ingress else 'egress'}"
        return self._redirects.get(key)

    def redirects(self) -> Dict[str, Redirect]:
        with self._lock:
            return dict(self._redirects)

    def remove_endpoint(self, endpoint_id: int) -> int:
        """Tear down every redirect of a deleted endpoint, returning
        its proxy ports to the allocator (removeOldRedirects on the
        endpoint-delete path — without this, L7 endpoint churn leaks
        ports until the 10000-20000 range exhausts)."""
        with self._lock:
            doomed = [
                key for key, r in self._redirects.items()
                if r.endpoint_id == endpoint_id
            ]
            for key in doomed:
                r = self._redirects.pop(key)
                self._ports_in_use.discard(r.proxy_port)
            return len(doomed)

    def redirects_for(self, endpoint_id: int) -> List[Redirect]:
        """All live redirects of one endpoint (stable order) — the
        per-endpoint L7 policy view NPDS serializes."""
        with self._lock:
            return sorted(
                (r for r in self._redirects.values()
                 if r.endpoint_id == endpoint_id),
                key=lambda r: (r.dst_port, not r.ingress),
            )

    # -- enforcement hooks ----------------------------------------------
    def check_http(self, redirect: Redirect, requests: Sequence[HTTPRequest]):
        """Batch HTTP enforcement + access logging → [B] bool allow
        (the cilium.l7policy decodeHeaders role). While tracing, the
        batch is a ``proxy-http`` trace: ``encode``, ``overlong`` and
        ``rule_match`` come from the policy's check_batch, then
        ``access_log``."""
        tr = self.tracer
        bt = (
            tr.begin("proxy-http", len(requests))
            if tr is not None and tr.active
            else NOOP_BATCH
        )
        try:
            pol = redirect.http_policy
            allows = (
                pol.check_batch(requests)
                if pol is not None
                else [True] * len(requests)
            )
            with bt.phase("access_log"):
                for req, ok in zip(requests, allows):
                    self.accesslog.log(
                        LogRecord(
                            type=TYPE_REQUEST,
                            verdict=VERDICT_FORWARDED if ok else VERDICT_DENIED,
                            timestamp=time.time(),
                            src_identity=req.src_identity,
                            dst_port=redirect.dst_port,
                            proto="http",
                            http={"method": req.method, "path": req.path,
                                  "host": req.host,
                                  "code": 200 if ok else 403},
                        )
                    )
        finally:
            bt.end()
        return allows

    def handle_kafka_bytes(
        self, redirect: Redirect, data: bytes, src_identity: int = 0
    ):
        """Byte-level ingestion boundary (the transparent TCP proxy of
        pkg/proxy/kafka.go handleRequest): parse one request frame,
        ACL-check every topic (a request passes only if ALL its topics
        pass — pkg/kafka/policy.go iterates GetTopics), and return
        (forward, reply_bytes): forward=True ⇒ reply_bytes is the
        original frame to send upstream; forward=False ⇒ reply_bytes
        is the synthesized reject response for the client (empty for
        unparseable input, which the reference drops)."""
        from ..l7.kafka_wire import (
            KafkaParseError,
            parse_request,
            reject_response,
        )

        try:
            parsed = parse_request(data)
        except KafkaParseError:
            return False, b""
        reqs = [
            KafkaRequest(
                api_key=parsed.api_key,
                api_version=parsed.api_version,
                client_id=parsed.client_id,
                topic=t,
                src_identity=src_identity,
            )
            for t in (parsed.topics or ("",))
        ]
        allows = self.check_kafka(redirect, reqs)
        if all(bool(a) for a in allows):
            return True, parsed.raw
        return False, reject_response(parsed)

    def check_kafka(self, redirect: Redirect, requests: Sequence[KafkaRequest]):
        acl = redirect.kafka_acl
        allows = (
            acl.check_batch(requests)
            if acl is not None
            else [True] * len(requests)
        )
        for req, ok in zip(requests, allows):
            self.accesslog.log(
                LogRecord(
                    type=TYPE_REQUEST,
                    verdict=VERDICT_FORWARDED if ok else VERDICT_DENIED,
                    timestamp=time.time(),
                    src_identity=req.src_identity,
                    dst_port=redirect.dst_port,
                    proto="kafka",
                    kafka={"api_key": req.api_key, "topic": req.topic,
                           "error_code": 0 if ok else 29},  # 29 = TOPIC_AUTHORIZATION_FAILED
                )
            )
        return allows
