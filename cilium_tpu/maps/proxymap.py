"""Proxy map: redirected 5-tuple → original destination + source
identity.

Reference: pkg/maps/proxymap (cilium_proxy4/6) written by the datapath
on redirect verdicts and read by the C++ bpf_metadata listener filter
(envoy/cilium_bpf_metadata.cc) to recover where a proxied connection
was originally headed and who sent it. Here the pipeline records
redirected flows and the L7 layer queries by the flow tuple.

Entries are flat tuples of strs and numbers, never objects: a storm
writes hundreds of thousands of them, and the collector untracks such
tuples, so they do not grow the heap its full collections walk.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Iterable, Optional, Tuple

DEFAULT_LIFETIME = 120.0  # proxymap entries are short-lived handoffs


@dataclasses.dataclass(frozen=True)
class ProxyValue:
    """proxymap.go Proxy4Value: original destination + source identity."""

    orig_dst_ip: str
    orig_dst_port: int
    src_identity: int


Key = Tuple[str, int, str, int, int]  # (sip, sport, dip, dport, proto)
Value = Tuple[str, int, int]  # (orig_dst_ip, orig_dst_port, src_identity)


class ProxyMap:
    def __init__(self, lifetime: float = DEFAULT_LIFETIME) -> None:
        self.lifetime = lifetime
        self._lock = threading.Lock()
        # key → (orig_dst_ip, orig_dst_port, src_identity, expiry)
        self._entries: Dict[Key, Tuple[str, int, int, float]] = {}

    def record_batch(
        self, keys: Iterable[Key], values: Iterable[Value],
        now: Optional[float] = None,
    ) -> None:
        """Write ``keys[i] → values[i]`` under one lock hold, all
        expiring ``lifetime`` after ``now`` (one clock read when None);
        a key repeated in the batch keeps its last value."""
        with self._lock:
            exp = (time.monotonic() if now is None else now) + self.lifetime
            self._entries.update(
                (k, (ip, port, ident, exp))
                for k, (ip, port, ident) in zip(keys, values)
            )

    def lookup(
        self, sip: str, sport: int, dip: str, dport: int, proto: int
    ) -> Optional[ProxyValue]:
        """The bpf_metadata getsockopt(SO_ORIGINAL_DST) analog."""
        now = time.monotonic()
        with self._lock:
            hit = self._entries.get((sip, sport, dip, dport, proto))
        if hit is None or hit[3] <= now:
            return None
        return ProxyValue(hit[0], hit[1], hit[2])

    def items(self) -> list:
        """Readable live entries (cilium bpf proxy list)."""
        now = time.monotonic()
        with self._lock:
            return [
                {
                    "src": f"{k[0]}:{k[1]}", "dst": f"{k[2]}:{k[3]}",
                    "proto": k[4],
                    "orig_dst": f"{ip}:{port}",
                    "src_identity": ident,
                }
                for k, (ip, port, ident, exp) in self._entries.items()
                if exp > now
            ]

    def gc(self) -> int:
        now = time.monotonic()
        with self._lock:
            stale = [k for k, v in self._entries.items() if v[3] <= now]
            for k in stale:
                del self._entries[k]
            return len(stale)

    def __len__(self) -> int:
        now = time.monotonic()
        with self._lock:
            return sum(1 for v in self._entries.values() if v[3] > now)
