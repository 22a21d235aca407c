"""Authoritative userspace IP/CIDR → identity map.

Reference: pkg/ipcache/ipcache.go — `Upsert` with source-priority
overwrite rules (:183,217), `Delete` (:429), lookups by prefix and by
identity (:438-493), and listener fan-out (`IPIdentityMappingListener`,
listener.go) that keeps derived state (the datapath LPM tensors here;
the BPF ipcache map + Envoy NPHDS in the reference) in sync.

The device view: the datapath pipeline rebuilds its LPM tries
(ops/lpm.py — wide 16-bit-stride for IPv4, shared-prefix-elided
stride-8 for IPv6) from ``items()`` whenever ``version`` moves,
mapping prefixes to identity *rows*.
"""

from __future__ import annotations

import dataclasses
import ipaddress
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union


# Source priorities (ipcache.go allowOverwrite: agent-local knowledge
# beats the kvstore, which beats k8s-derived, which beats generated).
SOURCE_AGENT = "agent"
SOURCE_KVSTORE = "kvstore"
SOURCE_K8S = "k8s"
SOURCE_GENERATED = "generated"
_PRIORITY = {SOURCE_AGENT: 3, SOURCE_KVSTORE: 2, SOURCE_K8S: 1, SOURCE_GENERATED: 0}


@dataclasses.dataclass(frozen=True)
class Entry:
    identity: int
    source: str
    host_ip: Optional[str] = None  # tunnel endpoint for remote entries


# fn(cidr, old_entry_or_None, new_entry_or_None)
Listener = Callable[[str, Optional[Entry], Optional[Entry]], None]
# (cidr, old_entry_or_None, new_entry_or_None)
Change = Tuple[str, Optional[Entry], Optional[Entry]]
# fn([change, ...]): every entry one write changed
BatchListener = Callable[[List[Change]], None]


class IPCache:
    # Bounded outward delta ring (the engine DELTA_LOG_CAP pattern):
    # consumed by the datapath pipeline's O(delta) trie patching.
    DELTA_LOG_CAP = 512

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._by_prefix: Dict[str, Entry] = {}
        self._by_identity: Dict[int, set] = {}
        self._listeners: List[Listener] = []
        self._batch_listeners: List[BatchListener] = []
        self.version = 0
        # (version, cidr, old_identity|None, new_identity|None) —
        # appended under the lock by upsert/delete, oldest dropped past
        # the cap
        self._delta_log: List[Tuple[int, str, Optional[int], Optional[int]]] = []

    def _log_delta(
        self, key: str, old: Optional[int], new: Optional[int]
    ) -> None:
        self._delta_log.append((self.version, key, old, new))
        if len(self._delta_log) > self.DELTA_LOG_CAP:
            del self._delta_log[: len(self._delta_log) - self.DELTA_LOG_CAP]

    def deltas_since(self, version: int):
        """Map updates with version > ``version`` (oldest first), or
        None when the ring has been truncated past that point — the
        consumer must rebuild its derived state from ``items()``
        (engine.deltas_since semantics)."""
        with self._lock:
            if version >= self.version:
                return []
            if self._delta_log and self._delta_log[0][0] > version + 1:
                return None
            if not self._delta_log and self.version > version:
                return None
            return [e for e in self._delta_log if e[0] > version]

    # ------------------------------------------------------------------
    def _norm(self, cidr: str) -> str:
        if "/" not in cidr:
            ip = ipaddress.ip_address(cidr)
            cidr = f"{ip}/{32 if ip.version == 4 else 128}"
        return str(ipaddress.ip_network(cidr, strict=False))

    def add_listener(self, fn: Listener, replay: bool = True) -> None:
        """SetListeners (listener fan-out), called once per changed
        entry; replay synthesizes the current state like the
        reference's initial dump."""
        with self._lock:
            self._listeners.append(fn)
            if replay:
                for cidr, e in self._by_prefix.items():
                    fn(cidr, None, e)

    def add_batch_listener(self, fn: BatchListener, replay: bool = True) -> None:
        """A listener called once per write with the list of entries it
        changed: one call for an ``update_many`` batch, where a
        per-entry listener is called once per entry. Replay hands it
        the current state as one batch."""
        with self._lock:
            self._batch_listeners.append(fn)
            if replay and self._by_prefix:
                fn([(cidr, None, e) for cidr, e in self._by_prefix.items()])

    def remove_listener(self, fn) -> bool:
        """Detach a listener of either kind (cluster leave must stop
        announcements)."""
        with self._lock:
            for group in (self._listeners, self._batch_listeners):
                if fn in group:
                    group.remove(fn)
                    return True
            return False

    def _change_locked(
        self, key: str, identity: Optional[int], source: str,
        host_ip: Optional[str] = None,
    ) -> Optional[Change]:
        """Upsert (``identity`` given) or delete one normalised entry
        under the lock, calling the per-entry listeners in map-update
        order (the reference holds the ipcache mutex across
        IPIdentityMappingListener callbacks). → the change, or None
        when a higher-priority source owns the entry (ipcache.go:183
        allowOverwrite) or there is nothing to delete."""
        old = self._by_prefix.get(key)
        if identity is None:
            if old is None or _PRIORITY[old.source] > _PRIORITY[source]:
                return None
            new = None
            del self._by_prefix[key]
        else:
            if old is not None and _PRIORITY[old.source] > _PRIORITY[source]:
                return None
            new = Entry(identity, source, host_ip)
            self._by_prefix[key] = new
        if old is not None:
            s = self._by_identity.get(old.identity)
            if s:
                s.discard(key)
        if new is not None:
            self._by_identity.setdefault(identity, set()).add(key)
        self.version += 1
        self._log_delta(key, old.identity if old else None, identity)
        for fn in self._listeners:
            fn(key, old, new)
        return key, old, new

    def _notify_batch(self, changes: List[Change]) -> None:
        if changes:
            for fn in self._batch_listeners:
                fn(changes)

    def upsert(
        self,
        cidr: str,
        identity: int,
        source: str,
        host_ip: Optional[str] = None,
    ) -> bool:
        """Returns False when a higher-priority source owns the entry
        (ipcache.go:183 allowOverwrite)."""
        key = self._norm(cidr)
        with self._lock:
            ch = self._change_locked(key, identity, source, host_ip)
            self._notify_batch([ch] if ch else [])
        return ch is not None

    def delete(self, cidr: str, source: str) -> bool:
        key = self._norm(cidr)
        with self._lock:
            ch = self._change_locked(key, None, source)
            self._notify_batch([ch] if ch else [])
        return ch is not None

    def update_many(
        self,
        updates: Sequence[Tuple[str, Optional[int], Optional[str]]],
        source: str,
    ) -> int:
        """Apply ``(cidr, identity, host_ip)`` upserts, and deletes
        where ``identity`` is None, in order, as one write: each entry
        follows the rules of ``upsert``/``delete``, and the batch
        listeners are called once with every change. An entry whose
        CIDR does not parse is skipped, as a watcher skips a malformed
        event. → entries changed."""
        keyed = []
        for cidr, identity, host_ip in updates:
            try:
                keyed.append((self._norm(cidr), identity, host_ip))
            except ValueError:
                continue
        with self._lock:
            changes = []
            for key, identity, host_ip in keyed:
                ch = self._change_locked(key, identity, source, host_ip)
                if ch is not None:
                    changes.append(ch)
            self._notify_batch(changes)
        return len(changes)

    # -- lookups --------------------------------------------------------
    def lookup_exact(self, cidr: str) -> Optional[Entry]:
        return self._by_prefix.get(self._norm(cidr))

    def lookup_by_ip(self, ip: str) -> Optional[Entry]:
        """Host-side LPM walk (the datapath does this on device)."""
        return self.lookup_many([ipaddress.ip_address(ip)])[0]

    def lookup_many(
        self, addrs: Sequence[Union[ipaddress.IPv4Address, ipaddress.IPv6Address]]
    ) -> List[Optional[Entry]]:
        """Host-side LPM walk of each address, longest prefix first,
        under one lock hold. Each prefix's key is the address's integer
        masked to that length, so no prefix length parses a string."""
        out = []
        with self._lock:
            get = self._by_prefix.get
            for addr in addrs:
                bits = addr.max_prefixlen
                e = get(f"{addr}/{bits}")
                if e is None:
                    n, cls = int(addr), type(addr)
                    for plen in range(bits - 1, -1, -1):
                        host = bits - plen
                        e = get(f"{cls(n >> host << host)}/{plen}")
                        if e is not None:
                            break
                out.append(e)
        return out

    def prefixes_for_identity(self, identity: int) -> List[str]:
        with self._lock:
            return sorted(self._by_identity.get(identity, ()))

    def __len__(self) -> int:
        return len(self._by_prefix)

    def items(self) -> List[Tuple[str, Entry]]:
        with self._lock:
            return list(self._by_prefix.items())

