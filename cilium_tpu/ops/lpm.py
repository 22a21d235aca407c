"""Longest-prefix-match as stride-8 trie tensors.

Replaces the kernel LPM trie maps (bpf/lib/maps.h cilium_ipcache LPM,
bpf/bpf_xdp.c:54-86 CIDR deny tries) with device-resident node tables
walked by chained row-gathers — the gather pattern TPU executes well
(one bounded-size embedding row per flow per level, no data-dependent
loop trip counts; levels are a static unroll).

Layout (per address family):
    child [M, 256] int32   next node id (0 = none; node 0 is the root)
    info  [M, 256] int32   value at this (node, byte) + 1 (0 = none)

A prefix of length ℓ populates ⌈ℓ/8⌉ levels; the last level writes
``info`` into every byte slot the prefix covers (a /12 writes 16 slots
of its level-2 node), so the walk needs no masking. The deepest
non-zero ``info`` seen along the walk is the longest match — exactly
the LPM_TRIE semantics of the kernel map. IPv4 walks 4 levels, IPv6 16.

Values are small ints (identity rows for ipcache, 1 for deny sets).
"""

from __future__ import annotations

import functools
import ipaddress
import re
import socket
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# RFC 5952 text as Python's ipaddress writes it (lower-case hex, no
# embedded IPv4 dotted quad): where inet_ntop's rendering of such text
# matches, both parsers read the same address
_V6_TEXT = re.compile(r"[0-9a-f:]+")


def parse_cidr(cidr: str) -> Tuple[int, bytes, int]:
    """(version, packed network address, prefix length) of a CIDR, as
    ``ipaddress.ip_network(cidr, strict=False)`` gives them. The
    canonical text the ipcache stores parses through ``inet_pton``
    (a few times faster than building the network object, which
    matters at hundreds of thousands of entries a rebuild); any other
    text takes ``ipaddress`` itself, so both accept the same inputs."""
    addr, sep, plen_s = cidr.partition("/")
    v6 = ":" in addr
    size = 16 if v6 else 4
    try:
        packed = socket.inet_pton(socket.AF_INET6 if v6 else socket.AF_INET, addr)
        plen = int(plen_s) if sep else size * 8
        canonical = (
            socket.inet_ntop(socket.AF_INET6 if v6 else socket.AF_INET, packed) == addr
            and (not sep or plen_s == str(plen))
            and 0 <= plen <= size * 8
            and (not v6 or _V6_TEXT.fullmatch(addr) is not None)
        )
    except (OSError, ValueError):
        canonical = False
    if not canonical:
        net = ipaddress.ip_network(cidr, strict=False)
        return net.version, net.network_address.packed, net.prefixlen
    host = size * 8 - plen
    n = int.from_bytes(packed, "big") >> host << host
    return (6 if v6 else 4), n.to_bytes(size, "big"), plen


def _rows_bucket(m: int) -> int:
    """Rows of a stride-8 node table: ``m`` rounded up to 1/32 of the
    power of two at or above it. A trie over a random prefix set (a
    prefilter blocklist) then keeps its shape from one set to the next
    of about the same size, and the verdict programs that read it come
    from the compile cache; the rows added are zero, so no node points
    at them. At most 1/32 more memory."""
    step = max(1, (1 << max(0, (m - 1).bit_length())) // 32)
    return -(-m // step) * step


class TrieBuilder:
    """Host-side incremental stride-8 trie. Rebuild-on-change is cheap
    (ms for 100k prefixes); the device arrays are immutable snapshots."""

    def __init__(self, levels: int) -> None:
        self.levels = levels
        # node storage: list of dicts byte→child_id / (value+1, plen)
        self._children: List[Dict[int, int]] = [{}]
        self._info: List[Dict[int, Tuple[int, int]]] = [{}]

    def _new_node(self) -> int:
        self._children.append({})
        self._info.append({})
        return len(self._children) - 1

    def _write(self, node: int, slot: int, value: int, plen: int) -> None:
        # Within one level, slots covered by several prefixes keep the
        # longest writer (a /0 expansion must not clobber a /8 entry) —
        # insert-order independence like the kernel LPM trie.
        old = self._info[node].get(slot)
        if old is None or plen >= old[1]:
            self._info[node][slot] = (value + 1, plen)

    def insert(self, prefix_bytes: bytes, prefix_len: int, value: int) -> None:
        """value ≥ 0; stored as value+1 internally."""
        node = 0
        full, rem = divmod(prefix_len, 8)
        for i in range(full):
            b = prefix_bytes[i]
            if rem == 0 and i == full - 1:
                self._write(node, b, value, prefix_len)
                return
            nxt = self._children[node].get(b)
            if nxt is None:
                nxt = self._new_node()
                self._children[node][b] = nxt
            node = nxt
        # partial byte: populate all covered slots at this level
        b = prefix_bytes[full] if full < len(prefix_bytes) else 0
        lo = b & (0xFF << (8 - rem)) & 0xFF
        for slot in range(lo, lo + (1 << (8 - rem))):
            self._write(node, slot, value, prefix_len)

    def arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        m = len(self._children)
        child = np.zeros((_rows_bucket(m), 256), np.int32)
        info = np.zeros((_rows_bucket(m), 256), np.int32)
        for n in range(m):
            for b, c in self._children[n].items():
                child[n, b] = c
            for b, (v, _plen) in self._info[n].items():
                info[n, b] = v
        return child, info


def build_trie(
    prefixes: Iterable[Tuple[str, int]], *, ipv6: bool = False
) -> Tuple[np.ndarray, np.ndarray]:
    """[(cidr_string, value)] → (child, info) arrays for one family."""
    levels = 16 if ipv6 else 4
    t = TrieBuilder(levels)
    for cidr, value in prefixes:
        version, packed, plen = parse_cidr(cidr)
        if (version == 6) != ipv6:
            continue
        t.insert(packed, plen, value)
    return t.arrays()


def build_trie_elided(
    prefixes: Iterable[Tuple[str, int]], *, ipv6: bool = True
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """[(cidr_string, value)] → (child, info, common_bytes) with the
    longest shared whole-byte prefix ELIDED from the trie.

    IPv6 pod allocations share a long prefix (everything under one
    /48-/64), so a full 16-level byte walk wastes most of its chained
    gathers traversing single-child nodes. The shared K bytes come
    back as ``common_bytes`` ([K] int32): the lookup compares them
    against the batch in one vectorized equality (no gathers) and
    walks only the remaining 16-K levels. Elision applies only while
    EVERY prefix is at least K whole bytes long (a shorter deny CIDR
    disables it), and K is capped one byte short so at least one walk
    level remains."""
    size = 16 if ipv6 else 4
    entries = []
    for cidr, value in prefixes:
        version, packed, plen = parse_cidr(cidr)
        if (version == 6) != ipv6:
            continue
        entries.append((packed, plen, value))
    k = 0
    if entries:
        first = entries[0][0]
        k = min(min(p for _, p, _ in entries) // 8, size - 1)
        for packed, _p, _v in entries:
            while k and packed[:k] != first[:k]:
                k -= 1
    t = TrieBuilder(size - k)
    for packed, plen, value in entries:
        t.insert(packed[k:], plen - 8 * k, value)
    child, info = t.arrays()
    common = (
        np.frombuffer(entries[0][0][:k], np.uint8).astype(np.int32)
        if k
        else np.zeros(0, np.int32)
    )
    return child, info, common


@functools.partial(jax.jit, static_argnames=("levels",))
def lpm_lookup(
    child: jnp.ndarray,  # [M, 256] int32
    info: jnp.ndarray,  # [M, 256] int32
    addr_bytes: jnp.ndarray,  # [B, levels] int32 (byte per level)
    levels: int = 4,
) -> jnp.ndarray:
    """→ [B] int32: matched value+1, 0 = no match (longest wins)."""
    b = addr_bytes.shape[0]
    node = jnp.zeros(b, jnp.int32)
    alive = jnp.ones(b, jnp.bool_)
    best = jnp.zeros(b, jnp.int32)
    for lvl in range(levels):
        byte = addr_bytes[:, lvl]
        flat = node * 256 + byte
        # bounded static unroll: `levels` is a jit-static argument (4 or
        # 16), so this traces ONCE into `levels` fused gathers — it is
        # not a per-call dispatch loop
        hit = jnp.take(info.reshape(-1), flat)  # policyd-lint: disable=TPU002
        best = jnp.where(alive & (hit > 0), hit, best)
        nxt = jnp.take(child.reshape(-1), flat)
        alive = alive & (nxt > 0)
        node = jnp.where(alive, nxt, node)
    return best


class _DenseRoot:
    """Shared 16-bit dense first stride (root_info/root_child +
    per-slot plen precedence) for both wide-trie layouts — one copy of
    the masking and longest-prefix tie-break semantics."""

    def __init__(self) -> None:
        self.root_info = np.zeros(65536, np.int32)
        self._root_plen = np.full(65536, -1, np.int32)
        self.root_child = np.zeros(65536, np.int32)

    @staticmethod
    def _mask(addr_u32: int, plen: int) -> int:
        return (
            addr_u32 & ((0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF)
            if plen else 0
        )

    def _root_insert(self, addr_u32: int, plen: int, value: int) -> None:
        """plen ≤ 16: fill the covered root range, longest plen wins."""
        hi = addr_u32 >> 16
        span = 1 << (16 - plen)
        sl = slice(hi, hi + span)
        mask = self._root_plen[sl] <= plen
        self.root_info[sl] = np.where(mask, value + 1, self.root_info[sl])
        self._root_plen[sl] = np.where(mask, plen, self._root_plen[sl])


class WideTrieBuilder(_DenseRoot):
    """IPv4 LPM with a DENSE 16-bit first stride: level 1 is one
    [65536] direct-indexed table (the DIR-24-8 idea, sized 16-8-8 so
    the dense level stays 256KB), levels 2-3 are stride-8 nodes. The
    walk is 3 gathers instead of 4 — measured ~1.8× over the stride-8
    trie at 50k prefixes — and the first gather indexes a small dense
    array, the TPU-friendliest access pattern of the three."""

    def __init__(self) -> None:
        super().__init__()
        # stride-8 node storage (node 0 reserved = "none")
        self._children: List[Dict[int, int]] = [{}]
        self._infos: List[Dict[int, Tuple[int, int]]] = [{}]

    def _new_node(self) -> int:
        self._children.append({})
        self._infos.append({})
        return len(self._children) - 1

    def _write(self, node: int, base: int, span: int, value: int, plen: int) -> None:
        for s in range(base, base + span):
            old = self._infos[node].get(s)
            if old is None or plen >= old[1]:
                self._infos[node][s] = (value + 1, plen)

    def insert(self, addr_u32: int, plen: int, value: int) -> None:
        addr_u32 = self._mask(addr_u32, plen)
        hi = addr_u32 >> 16
        if plen <= 16:
            self._root_insert(addr_u32, plen, value)
            return
        node = self.root_child[hi]
        if node == 0:
            node = self._new_node()
            self.root_child[hi] = node
        b2 = (addr_u32 >> 8) & 0xFF
        rem = plen - 16
        if rem <= 8:
            span = 1 << (8 - rem)
            self._write(node, b2 & (0xFF << (8 - rem)) & 0xFF, span, value, plen)
            return
        nxt = self._children[node].get(b2)
        if nxt is None:
            nxt = self._new_node()
            self._children[node][b2] = nxt
        rem2 = rem - 8
        span = 1 << (8 - rem2)
        base = (addr_u32 & 0xFF) & (0xFF << (8 - rem2)) & 0xFF
        self._write(nxt, base, span, value, plen)

    def arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        m = len(self._children)
        sub_child = np.zeros((_rows_bucket(m), 256), np.int32)
        sub_info = np.zeros((_rows_bucket(m), 256), np.int32)
        for n in range(m):
            for b, c in self._children[n].items():
                sub_child[n, b] = c
            for b, (v, _plen) in self._infos[n].items():
                sub_info[n, b] = v
        return self.root_info.copy(), self.root_child.copy(), sub_child, sub_info


class FlatTrieBuilder(_DenseRoot):
    """IPv4 LPM with TWO dense 16-bit strides: level 1 is the [65536]
    root table, level 2 is one [65536] table per hi-16 that carries
    longer-than-/16 prefixes. The walk is 2 chained gathers (vs 3 for
    the 16-8-8 layout) — the LPM walk is the whole-pipeline bottleneck,
    so one fewer dependent gather is ~1/3 more end-to-end throughput.

    Memory/rebuild cost: 256KB per level-2 node, re-uploaded on every
    trie rebuild (identity row churn included). That is comparable to
    the 16-8-8 layout at production scale — 50k scattered prefixes
    build ~37k stride-8 nodes = ~76MB of child+info arrays, vs ≤33MB
    here at the node budget — so the flat layout is capped where it
    stops being the cheaper transfer, not grown until it fits."""

    def __init__(self) -> None:
        super().__init__()
        # node id → (info [65536], plen [65536]); id 0 reserved = none
        self._nodes: List[Tuple[np.ndarray, np.ndarray]] = []

    def _node(self, hi: int) -> Tuple[np.ndarray, np.ndarray]:
        nid = self.root_child[hi]
        if nid == 0:
            self._nodes.append((
                np.zeros(65536, np.int32), np.full(65536, -1, np.int32)
            ))
            nid = len(self._nodes)  # 1-based
            self.root_child[hi] = nid
        return self._nodes[nid - 1]

    def insert(self, addr_u32: int, plen: int, value: int) -> None:
        addr_u32 = self._mask(addr_u32, plen)
        hi = addr_u32 >> 16
        if plen <= 16:
            self._root_insert(addr_u32, plen, value)
            return
        info, plens = self._node(hi)
        base = addr_u32 & 0xFFFF
        span = 1 << (32 - plen)
        sl = slice(base, base + span)
        mask = plens[sl] <= plen
        info[sl] = np.where(mask, value + 1, info[sl])
        plens[sl] = np.where(mask, plen, plens[sl])

    def arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        m = len(self._nodes) + 1  # row 0 = "no node", all zeros
        sub_info = np.zeros((m, 65536), np.int32)
        for i, (info, _plens) in enumerate(self._nodes):
            sub_info[i + 1] = info
        # sub_child is unused in this layout (its [*, 65536] shape is
        # what routes lpm_lookup_wide onto the 2-gather branch)
        sub_child = np.zeros((1, 65536), np.int32)
        return self.root_info.copy(), self.root_child.copy(), sub_child, sub_info


# level-2 node budget for the flat layout: 128 nodes = 33MB per trie
# (rebuilt + re-uploaded on ipcache/identity churn); past that the
# 16-8-8 pointer structure wins on transfer size
FLAT_TRIE_MAX_NODES = 128


def build_wide_trie(
    prefixes: Iterable[Tuple[str, int]]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """[(v4 cidr_string, value)] → wide-trie arrays (v6 entries are
    skipped — the wide layout is IPv4-only). Picks the 2-gather flat
    16+16 layout when the deep prefixes cluster into few /16s (the
    normal pod-CIDR shape), else the 16-8-8 layout."""
    parsed = []
    deep_hi16 = set()
    for cidr, value in prefixes:
        version, packed, plen = parse_cidr(cidr)
        if version != 4:
            continue
        addr = int.from_bytes(packed, "big")
        parsed.append((addr, plen, value))
        if plen > 16:
            deep_hi16.add(addr >> 16)
    t = (
        FlatTrieBuilder()
        if len(deep_hi16) <= FLAT_TRIE_MAX_NODES
        else WideTrieBuilder()
    )
    for addr, plen, value in parsed:
        t.insert(addr, plen, value)
    return t.arrays()


@jax.jit
def lpm_lookup_wide(
    root_info: jnp.ndarray,  # [65536] int32
    root_child: jnp.ndarray,  # [65536] int32
    sub_child: jnp.ndarray,  # [M, 256] int32
    sub_info: jnp.ndarray,  # [M, 256] int32
    addr_u32: jnp.ndarray,  # [B] uint32/int32 host-order addresses
) -> jnp.ndarray:
    """→ [B] int32: matched value+1, 0 = no match (longest wins).
    Semantics identical to lpm_lookup on the equivalent prefix set.
    The sub-table shape (static at trace time) routes between the
    flat 16+16 layout (2 chained gathers) and 16-8-8 (3)."""
    q = addr_u32.astype(jnp.uint32)
    hi = (q >> 16).astype(jnp.int32)
    if sub_info.shape[-1] == 65536:  # flat second stride
        lo = (q & 0xFFFF).astype(jnp.int32)
        best = jnp.take(root_info, hi)
        node = jnp.take(root_child, hi)
        # named scope: a whole-table relayout shows under this name in
        # the profiler trace
        with jax.named_scope("table_flatten"):
            flat_i = sub_info.reshape(-1)
        v1 = jnp.take(flat_i, node * 65536 + lo)
        return jnp.where((node > 0) & (v1 > 0), v1, best)
    b2 = ((q >> 8) & 0xFF).astype(jnp.int32)
    b3 = (q & 0xFF).astype(jnp.int32)
    best = jnp.take(root_info, hi)
    node = jnp.take(root_child, hi)
    with jax.named_scope("table_flatten"):
        flat_c = sub_child.reshape(-1)
        flat_i = sub_info.reshape(-1)
    idx1 = node * 256 + b2
    v1 = jnp.take(flat_i, idx1)
    n1 = jnp.take(flat_c, idx1)
    best = jnp.where((node > 0) & (v1 > 0), v1, best)
    v2 = jnp.take(flat_i, n1 * 256 + b3)
    best = jnp.where((node > 0) & (n1 > 0) & (v2 > 0), v2, best)
    return best


# -- fused deny+identity walk (v6 stride-8 elided tries) --------------------


class _HostLPM:
    """Host-side LPM oracle over one prefix set: per-plen exact-match
    dicts, queried longest-first. O(#distinct plens) per query — the
    merge below asks it once per union prefix."""

    def __init__(self, entries) -> None:  # [(packed_bytes, plen, value)]
        self._by_plen: Dict[int, Dict[bytes, int]] = {}
        for packed, plen, value in entries:
            masked = _mask_bytes(packed, plen)
            self._by_plen.setdefault(plen, {})[masked] = value
        self._plens = sorted(self._by_plen, reverse=True)

    def lookup(self, packed: bytes, plen: int) -> int:
        """Longest match covering prefix (packed/plen) → value+1, 0 =
        none. Only prefixes of length ≤ plen can cover it."""
        for p in self._plens:
            if p > plen:
                continue
            hit = self._by_plen[p].get(_mask_bytes(packed, p))
            if hit is not None:
                return hit + 1
        return 0


def _mask_bytes(packed: bytes, plen: int) -> bytes:
    full, rem = divmod(plen, 8)
    out = bytearray(len(packed))
    out[:full] = packed[:full]
    if rem and full < len(packed):
        out[full] = packed[full] & (0xFF << (8 - rem)) & 0xFF
    return bytes(out)


def merge_trie_entries(ip_prefixes, deny_prefixes, *, ipv6=True):
    """[(cidr, value)] identity + [(cidr, _)] deny → ONE packed prefix
    list [(cidr, packed_value)] whose LPM equals BOTH sides' LPMs at
    every address: packed = (identity value+1) | DENY_BIT·denied.

    Every union prefix carries the OTHER side's LPM answer at that
    point, so a longer prefix from one side cannot shadow the other
    side's match (the correctness trap of a naive set union). Feed the
    result to build_trie_elided for the fused stride-8 walk."""
    def parse(prefixes):
        out = []
        for cidr, value in prefixes:
            version, packed, plen = parse_cidr(cidr)
            if (version == 6) != ipv6:
                continue
            out.append((packed, plen, value))
        return out

    ip_entries = parse(ip_prefixes)
    deny_entries = parse(deny_prefixes)
    ip_lpm = _HostLPM(ip_entries)
    deny_lpm = _HostLPM(deny_entries)
    union: Dict[Tuple[bytes, int], int] = {}
    for packed, plen, _v in ip_entries + deny_entries:
        key = (_mask_bytes(packed, plen), plen)
        if key in union:
            continue
        ip_v = ip_lpm.lookup(packed, plen)  # value+1, 0 = none
        if ip_v >= int(DENY_BIT) - 1:
            # packing range: the trie stores (ip_v | DENY_BIT) + 1,
            # which must stay inside int32 — the -1 keeps the denied
            # boundary case from overflowing
            return None
        denied = deny_lpm.lookup(packed, plen) > 0
        union[key] = ip_v | (int(DENY_BIT) if denied else 0)
    out = []
    for (packed, plen), pv in union.items():
        addr = ipaddress.ip_address(packed)
        out.append((f"{addr}/{plen}", pv))
    return out


# -- fused deny+identity walk (flat 16+16 layouts only) ---------------------
#
# The datapath's two v4 LPM walks — XDP deny trie and ipcache identity
# trie — consume the same address bytes (bpf_xdp.c:97-156 then
# bpf_netdev.c secctx). When BOTH tries use the dense flat layout their
# tables merge ELEMENT-WISE into one packed table: identity row+1 in
# the low bits, the deny verdict in one high bit — one 2-gather walk
# returns both results, halving the pipeline's gather count.

DENY_BIT = np.int32(1 << 30)
MERGED_VALUE_MASK = np.int32((1 << 30) - 1)


def _flat_value_grid(root_info, root_child, sub_info, his):
    """For each hi16 in ``his`` → [len(his), 65536] resolved LPM values
    (node entry where present, else the root's value — the flat
    layout's exact lookup semantics, vectorized)."""
    nodes = root_child[his]  # [H] node ids (0 = none)
    grid = sub_info[nodes]  # [H, 65536] (row 0 is all-zero)
    root_vals = root_info[his][:, None]  # [H, 1]
    return np.where(grid > 0, grid, root_vals)


def merge_flat_tries(ip_arrays, deny_arrays):
    """(ip flat-trie arrays, deny flat-trie arrays) → merged flat
    arrays, or None when either side uses the 16-8-8 pointer layout
    (merging needs the dense form). Identity values must stay below
    DENY_BIT."""
    # host-side table prep: the merge needs fancy indexing and in-place
    # writes, so pin the inputs to numpy up front — a device array
    # slipping in would otherwise turn every reduction below into a
    # blocking transfer (and int(...) on it into a device sync)
    ip_ri, ip_rc, ip_sc, ip_si = (np.asarray(a) for a in ip_arrays)
    d_ri, d_rc, d_sc, d_si = (np.asarray(a) for a in deny_arrays)
    if ip_si.shape[-1] != 65536 or d_si.shape[-1] != 65536:
        return None
    if (
        np.max(ip_si, initial=0) >= DENY_BIT
        or np.max(ip_ri, initial=0) >= DENY_BIT
    ):
        return None

    # hi16 buckets where either side holds longer-than-/16 prefixes
    his = np.union1d(np.nonzero(ip_rc)[0], np.nonzero(d_rc)[0]).astype(
        np.int64
    )
    if len(his) > FLAT_TRIE_MAX_NODES:
        # the UNION can exceed the per-trie transfer budget even when
        # each side fits — past it, the merged table costs more to
        # rebuild/upload per churn than the second walk saves
        return None
    m = len(his) + 1
    root_info = ip_ri.astype(np.int32).copy()
    root_info |= np.where(d_ri > 0, DENY_BIT, 0).astype(np.int32)
    root_child = np.zeros(65536, np.int32)
    sub_info = np.zeros((m, 65536), np.int32)
    if len(his):
        root_child[his] = np.arange(1, m, dtype=np.int32)
        ip_grid = _flat_value_grid(ip_ri, ip_rc, ip_si, his)
        d_grid = _flat_value_grid(d_ri, d_rc, d_si, his)
        sub_info[1:] = ip_grid | np.where(d_grid > 0, DENY_BIT, 0)
        # a merged node must never fall back to the root (its grid is
        # fully resolved); keep zero cells zero so "no match" stays 0 —
        # they already are, because _flat_value_grid resolves them to
        # the root value, which IS the correct fallback. But a cell
        # whose resolved value is 0 (no identity, no deny) must not
        # shadow the merged ROOT value either — it cannot, because the
        # root fallback only applies when the node cell is 0, and the
        # resolved grid equals that root fallback by construction.
    sub_child = np.zeros((1, 65536), np.int32)  # flat-layout marker
    return root_info, root_child, sub_child, sub_info


# -- O(delta) trie patching (policyd-sparse) --------------------------------
#
# ToFQDN-style small-CIDR storms churn the ipcache a few /32s//128s at a
# time; rebuilding + re-uploading whole tries per change is the
# reference's per-key LPM map write turned into a table rebuild. These
# builders keep HOST mirrors of the device trie tensors plus enough
# writer bookkeeping to insert/delete individual prefixes in place, and
# flush only the touched node rows / dense spans to the device copies —
# O(delta) words per churn instead of the whole trie. Node pools carry
# power-of-two headroom; exhaustion (or a layout/elision violation)
# returns False and the caller falls back to the classic full rebuild.
#
# Correctness bar: for any applied prefix set, the host mirrors are
# value-identical to what build_wide_trie / build_trie_elided would
# produce for that set (modulo zero-padded pool rows, which the walks
# never reach) — (prefix, plen) keys must be unique per trie, which the
# ipcache guarantees (normalized CIDR keys).


@jax.jit
def _patch_trie_rows(
    child: jnp.ndarray,
    info: jnp.ndarray,
    idx: jnp.ndarray,  # [k] int32 node rows (pow2-padded, dup = last)
    cvals: jnp.ndarray,  # [k, 256]
    ivals: jnp.ndarray,  # [k, 256]
):
    """Scatter dirty stride-8 node rows into both trie tensors in ONE
    dispatch (duplicate indices carry identical values). No donation:
    concurrent LPM walks may hold the old buffers."""
    return child.at[idx].set(cvals), info.at[idx].set(ivals)


@jax.jit
def _patch_span1(a: jnp.ndarray, start: jnp.ndarray, vals: jnp.ndarray):
    """Dense-root span update (flat v4 layout): spans are naturally
    power-of-two (1 << (16 - plen)), so widths bound the program count;
    the traced start keeps one program per width."""
    return jax.lax.dynamic_update_slice(a, vals, (start,))


@jax.jit
def _patch_span_row(
    a: jnp.ndarray, row: jnp.ndarray, start: jnp.ndarray, vals: jnp.ndarray
):
    return jax.lax.dynamic_update_slice(a, vals[None, :], (row, start))


@jax.jit
def _patch_elems(a: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray):
    return a.at[idx].set(vals)


def _pow2_pad_rows(rows: np.ndarray) -> np.ndarray:
    """Pad a row-index list to a power-of-two bucket (min 8) by
    repeating the last row — the engine _pow2_rows discipline."""
    k = rows.shape[0]
    bucket = 8
    while bucket < k:
        bucket <<= 1
    if bucket == k:
        return rows
    return np.concatenate([rows, np.repeat(rows[-1:], bucket - k)])


class PatchableElidedTrie:
    """Patchable host mirror of one build_trie_elided trie (v6 ip
    tries; also correct for v4 stride-8, unused there). Per-(node,
    slot) writers keyed by plen make deletes exact: at one slot of the
    final level, distinct covering prefixes necessarily carry distinct
    plens (same plen + same covered slot ⇒ same masked prefix ⇒ same
    ipcache key), so the remaining longest plen is the new winner."""

    def __init__(self, prefixes: Iterable[Tuple[str, int]], *, ipv6: bool = True):
        size = 16 if ipv6 else 4
        self._ipv6 = ipv6
        entries = []
        for cidr, value in prefixes:
            version, packed, plen = parse_cidr(cidr)
            if (version == 6) != ipv6:
                continue
            entries.append((packed, plen, value))
        k = 0
        if entries:
            first = entries[0][0]
            k = min(min(p for _, p, _ in entries) // 8, size - 1)
            for packed, _p, _v in entries:
                while k and packed[:k] != first[:k]:
                    k -= 1
        self._k = k
        self._levels = size - k
        self._common = entries[0][0][:k] if k else b""
        # node storage: byte→child dicts + per-slot {plen: value} writers
        self._children: List[Dict[int, int]] = [{}]
        self._writers: List[Dict[int, Dict[int, int]]] = [{}]
        self._live = False  # arrays not materialized yet
        self.child_h = np.zeros((0, 256), np.int32)
        self.info_h = np.zeros((0, 256), np.int32)
        self._dirty: set = set()
        for packed, plen, value in entries:
            self._ins(packed[k:], plen - 8 * k, value)
        m = len(self._children)
        cap = 8
        while cap < m + 1:  # ≥1 spare row for live inserts
            cap <<= 1
        self.child_h = np.zeros((cap, 256), np.int32)
        self.info_h = np.zeros((cap, 256), np.int32)
        for n in range(m):
            for b, c in self._children[n].items():
                self.child_h[n, b] = c
            for slot, w in self._writers[n].items():
                if w:
                    self.info_h[n, slot] = w[max(w)] + 1
        self._live = True

    # -- host structure ------------------------------------------------
    def _new_node(self) -> Optional[int]:
        nid = len(self._children)
        if self._live and nid >= self.child_h.shape[0]:
            return None  # pool exhausted → caller full-rebuilds
        self._children.append({})
        self._writers.append({})
        return nid

    def _write(self, node: int, slot: int, value: int, plen: int) -> None:
        w = self._writers[node].setdefault(slot, {})
        w[plen] = value
        if self._live:
            self.info_h[node, slot] = w[max(w)] + 1
            self._dirty.add(node)

    def _unwrite(self, node: int, slot: int, plen: int) -> None:
        w = self._writers[node].get(slot)
        if not w or plen not in w:
            return
        del w[plen]
        self.info_h[node, slot] = (w[max(w)] + 1) if w else 0
        self._dirty.add(node)

    def _ins(self, pb: bytes, plen: int, value: int) -> bool:
        node = 0
        full, rem = divmod(plen, 8)
        for i in range(full):
            b = pb[i]
            if rem == 0 and i == full - 1:
                self._write(node, b, value, plen)
                return True
            nxt = self._children[node].get(b)
            if nxt is None:
                nxt = self._new_node()
                if nxt is None:
                    return False
                self._children[node][b] = nxt
                if self._live:
                    self.child_h[node, b] = nxt
                    self._dirty.add(node)
            node = nxt
        b = pb[full] if full < len(pb) else 0
        lo = b & (0xFF << (8 - rem)) & 0xFF
        for slot in range(lo, lo + (1 << (8 - rem))):
            self._write(node, slot, value, plen)
        return True

    # -- public ops ----------------------------------------------------
    def _parse(self, cidr: str):
        version, packed, plen = parse_cidr(cidr)
        if (version == 6) != self._ipv6:
            return None
        return packed, plen

    def insert(self, cidr: str, value: int) -> bool:
        """Upsert one prefix. False → not expressible in place (family
        mismatch, elision violation, node-pool exhaustion): rebuild."""
        p = self._parse(cidr)
        if p is None:
            return False
        packed, plen = p
        if self._k and (plen < 8 * self._k or packed[: self._k] != self._common):
            return False  # would break the elided shared prefix
        return self._ins(packed[self._k:], plen - 8 * self._k, value)

    def delete(self, cidr: str) -> bool:
        """Remove one prefix (no-op when absent — e.g. its identity
        never had a device row). Deletes cannot violate elision or grow
        the pool, so this never demands a rebuild."""
        p = self._parse(cidr)
        if p is None:
            return True
        packed, plen = p
        if self._k and (plen < 8 * self._k or packed[: self._k] != self._common):
            return True  # was never inserted
        pb = packed[self._k:]
        plen -= 8 * self._k
        node = 0
        full, rem = divmod(plen, 8)
        for i in range(full):
            b = pb[i]
            if rem == 0 and i == full - 1:
                self._unwrite(node, b, plen)
                return True
            nxt = self._children[node].get(b)
            if nxt is None:
                return True  # path absent → prefix absent
            node = nxt
        b = pb[full] if full < len(pb) else 0
        lo = b & (0xFF << (8 - rem)) & 0xFF
        for slot in range(lo, lo + (1 << (8 - rem))):
            self._unwrite(node, slot, plen)
        return True

    @property
    def dirty(self) -> bool:
        return bool(self._dirty)

    def arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(child, info, common_bytes) — build_trie_elided layout with
        the pow2-padded node pool (zero rows the walk never reaches)."""
        common = (
            np.frombuffer(self._common, np.uint8).astype(np.int32)
            if self._k
            else np.zeros(0, np.int32)
        )
        return self.child_h.copy(), self.info_h.copy(), common

    def flush(self, child_dev, info_dev):
        """Scatter the dirty node rows into the device copies →
        ((child, info), logical h2d bytes), or None when the device
        shape does not match the mirror (caller re-places wholesale)."""
        if not self._dirty:
            return (child_dev, info_dev), 0
        if tuple(getattr(child_dev, "shape", ())) != self.child_h.shape:
            return None
        rows = _pow2_pad_rows(np.asarray(sorted(self._dirty), np.int32))
        cvals = self.child_h[rows]
        ivals = self.info_h[rows]
        child_dev, info_dev = _patch_trie_rows(
            child_dev, info_dev, jnp.asarray(rows), jnp.asarray(cvals),
            jnp.asarray(ivals),
        )
        self._dirty.clear()
        nbytes = int(rows.nbytes) + int(cvals.nbytes) + int(ivals.nbytes)
        return (child_dev, info_dev), nbytes


class _FlatNode:
    """One level-2 dense node of the patchable flat v4 trie: resolved
    info/plen arrays + the raw entry dict the delete path recomputes
    spans from."""

    __slots__ = ("info", "plen", "entries")

    def __init__(self) -> None:
        self.info = np.zeros(65536, np.int32)
        self.plen = np.full(65536, -1, np.int16)
        self.entries: Dict[Tuple[int, int], int] = {}


class PatchableFlatTrie:
    """Patchable host mirror of one flat 16+16 v4 trie
    (FlatTrieBuilder layout). Root precedence keeps a per-plen [17,
    65536] value table (≤16 plens ⇒ winner recompute is 17 vectorized
    selects over the touched span); deep nodes recompute deleted spans
    from their entry dicts. Dirty state flushes as power-of-two dense
    spans (dynamic_update_slice — one program per span width)."""

    def __init__(self, prefixes: Iterable[Tuple[int, int, int]]):
        # prefixes: parsed (addr_u32, plen, value) v4 entries
        self._root_by_plen = np.zeros((17, 65536), np.int32)  # value+1
        self.root_info = np.zeros(65536, np.int32)
        self.root_child = np.zeros(65536, np.int32)
        self._nodes: List[_FlatNode] = []
        entries = list(prefixes)
        n_deep = len({a >> 16 for a, p, _v in entries if p > 16})
        cap = 4
        while cap < n_deep + 2:  # ≥1 spare node row (row 0 = none)
            cap <<= 1
        self._cap_rows = min(cap, FLAT_TRIE_MAX_NODES * 2)
        # (start, pow2 width) dense-root spans / node ids / (nid, base,
        # pow2 width) node spans touched since the last flush
        self._dirty_root: Dict[Tuple[int, int], None] = {}
        self._dirty_child: Dict[int, None] = {}
        self._dirty_sub: Dict[Tuple[int, int, int], None] = {}
        for addr, plen, value in entries:
            ok = self._ins(addr, plen, value)
            assert ok  # cap covers the build set by construction
        self._clear_dirty()

    def _clear_dirty(self) -> None:
        self._dirty_root.clear()
        self._dirty_child.clear()
        self._dirty_sub.clear()

    @staticmethod
    def _mask(addr_u32: int, plen: int) -> int:
        return (
            addr_u32 & ((0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF)
            if plen else 0
        )

    def _root_recompute(self, sl: slice) -> None:
        out = np.zeros(sl.stop - sl.start, np.int32)
        for p in range(17):  # ascending: longer plen overwrites
            v = self._root_by_plen[p, sl]
            out = np.where(v > 0, v, out)
        self.root_info[sl] = out

    def _ins(self, addr: int, plen: int, value: int) -> bool:
        addr = self._mask(addr, plen)
        hi = addr >> 16
        if plen <= 16:
            span = 1 << (16 - plen)
            sl = slice(hi, hi + span)
            self._root_by_plen[plen, sl] = value + 1
            self._root_recompute(sl)
            self._dirty_root[(hi, span)] = None
            return True
        nid = int(self.root_child[hi])
        if nid == 0:
            if (
                len(self._nodes) + 2 > self._cap_rows
                or len(self._nodes) >= FLAT_TRIE_MAX_NODES
            ):
                return False  # pool exhausted / past the flat budget
            self._nodes.append(_FlatNode())
            nid = len(self._nodes)
            self.root_child[hi] = nid
            self._dirty_child[hi] = None
        node = self._nodes[nid - 1]
        node.entries[(addr, plen)] = value
        base = addr & 0xFFFF
        span = 1 << (32 - plen)
        sl = slice(base, base + span)
        m = node.plen[sl] <= plen
        node.info[sl] = np.where(m, value + 1, node.info[sl])
        node.plen[sl] = np.where(m, np.int16(plen), node.plen[sl])
        self._dirty_sub[(nid, base, span)] = None
        return True

    # -- public ops ----------------------------------------------------
    @staticmethod
    def _parse(cidr: str):
        version, packed, plen = parse_cidr(cidr)
        if version != 4:
            return None
        return int.from_bytes(packed, "big"), plen

    def insert(self, cidr: str, value: int) -> bool:
        p = self._parse(cidr)
        if p is None:
            return False
        return self._ins(p[0], p[1], value)

    def delete(self, cidr: str) -> bool:
        """Remove one prefix (no-op when absent). Never demands a
        rebuild: spans recompute from the surviving writers."""
        p = self._parse(cidr)
        if p is None:
            return True
        addr, plen = self._mask(p[0], p[1]), p[1]
        hi = addr >> 16
        if plen <= 16:
            span = 1 << (16 - plen)
            sl = slice(hi, hi + span)
            if not self._root_by_plen[plen, sl].any():
                return True  # absent
            self._root_by_plen[plen, sl] = 0
            self._root_recompute(sl)
            self._dirty_root[(hi, span)] = None
            return True
        nid = int(self.root_child[hi])
        if nid == 0:
            return True
        node = self._nodes[nid - 1]
        if node.entries.pop((addr, plen), None) is None:
            return True
        base = addr & 0xFFFF
        span = 1 << (32 - plen)
        sl = slice(base, base + span)
        node.info[sl] = 0
        node.plen[sl] = -1
        for (a2, p2), v2 in node.entries.items():
            b2 = a2 & 0xFFFF
            s2 = 1 << (32 - p2)
            lo, hi2 = max(base, b2), min(base + span, b2 + s2)
            if lo < hi2:
                ssl = slice(lo, hi2)
                m = node.plen[ssl] <= p2
                node.info[ssl] = np.where(m, v2 + 1, node.info[ssl])
                node.plen[ssl] = np.where(m, np.int16(p2), node.plen[ssl])
        self._dirty_sub[(nid, base, span)] = None
        return True

    @property
    def dirty(self) -> bool:
        return bool(self._dirty_root or self._dirty_child or self._dirty_sub)

    def arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """build_wide_trie flat-layout arrays with the pow2-padded node
        pool (zero rows resolve to the root fallback, exactly like an
        unallocated node)."""
        sub_info = np.zeros((self._cap_rows, 65536), np.int32)
        for i, node in enumerate(self._nodes):
            sub_info[i + 1] = node.info
        sub_child = np.zeros((1, 65536), np.int32)  # flat-layout marker
        return (
            self.root_info.copy(), self.root_child.copy(), sub_child,
            sub_info,
        )

    def flush(self, root_info_dev, root_child_dev, sub_child_dev, sub_info_dev):
        """Upload the dirty spans → ((root_info, root_child, sub_child,
        sub_info), logical h2d bytes), or None on a device/mirror shape
        mismatch (caller re-places wholesale)."""
        if not self.dirty:
            return (root_info_dev, root_child_dev, sub_child_dev, sub_info_dev), 0
        if tuple(getattr(sub_info_dev, "shape", ())) != (self._cap_rows, 65536):
            return None
        nbytes = 0
        for start, span in self._dirty_root:
            vals = np.ascontiguousarray(self.root_info[start:start + span])
            root_info_dev = _patch_span1(
                # bounded control-plane unroll: one dispatch per dirty
                # root span (spans coalesce adjacent edits), at rebuild
                # cadence — never per flow
                root_info_dev, jnp.int32(start), jnp.asarray(vals)  # policyd-lint: disable=TPU002
            )
            nbytes += int(vals.nbytes) + 4
        if self._dirty_child:
            idx = _pow2_pad_rows(
                np.asarray(sorted(self._dirty_child), np.int32)
            )
            vals = self.root_child[idx]
            root_child_dev = _patch_elems(
                root_child_dev, jnp.asarray(idx), jnp.asarray(vals)
            )
            nbytes += int(idx.nbytes) + int(vals.nbytes)
        for nid, base, span in self._dirty_sub:
            vals = np.ascontiguousarray(
                self._nodes[nid - 1].info[base:base + span]
            )
            sub_info_dev = _patch_span_row(
                # bounded control-plane unroll: one dispatch per dirty
                # sub-node span, bounded by the patch budget before the
                # mirror falls back to a full rebuild
                sub_info_dev, jnp.int32(nid), jnp.int32(base),  # policyd-lint: disable=TPU002
                jnp.asarray(vals),
            )
            nbytes += int(vals.nbytes) + 8
        self._clear_dirty()
        return (
            (root_info_dev, root_child_dev, sub_child_dev, sub_info_dev),
            nbytes,
        )


def make_patchable_wide(
    prefixes: Iterable[Tuple[str, int]]
) -> Optional[PatchableFlatTrie]:
    """PatchableFlatTrie over the v4 entries, or None when
    build_wide_trie would pick the 16-8-8 pointer layout (too many
    deep /16 buckets) — that layout is not patched; callers fall back
    to full rebuilds."""
    parsed = []
    deep_hi16 = set()
    for cidr, value in prefixes:
        version, packed, plen = parse_cidr(cidr)
        if version != 4:
            continue
        addr = int.from_bytes(packed, "big")
        parsed.append((addr, plen, value))
        if plen > 16:
            deep_hi16.add(addr >> 16)
    if len(deep_hi16) > FLAT_TRIE_MAX_NODES:
        return None
    return PatchableFlatTrie(parsed)


def place_table(a, sharding=None):
    """Upload one trie array to device. With a ``NamedSharding`` the
    array is committed REPLICATED across the verdict mesh (every LPM
    walk reads the whole trie regardless of which flow shard it
    serves); without one this is the classic single-device upload.
    Centralized here so every trie consumer places tables the same way
    under VerdictSharding."""
    if sharding is None:
        return jnp.asarray(a)
    return jax.device_put(np.asarray(a), sharding)


def ipv4_to_bytes(addrs: np.ndarray) -> np.ndarray:
    """[B] uint32 host-order IPv4 → [B, 4] int32 big-endian bytes."""
    a = addrs.astype(np.uint32)
    return np.stack(
        [(a >> 24) & 0xFF, (a >> 16) & 0xFF, (a >> 8) & 0xFF, a & 0xFF], axis=1
    ).astype(np.int32)


def ip_strings_to_u32(ips: Iterable[str]) -> np.ndarray:
    return np.array([int(ipaddress.IPv4Address(ip)) for ip in ips], np.uint32)


def ipv6_to_bytes(ips: Iterable[str]) -> np.ndarray:
    return np.array(
        [list(ipaddress.IPv6Address(ip).packed) for ip in ips], np.int32
    )
