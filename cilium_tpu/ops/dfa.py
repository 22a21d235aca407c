"""Batched multi-pattern DFA execution on device.

The L7 HTTP matcher: strings (method/path/host) walk a combined DFA
(l7/regex_compile.py) whose accept sets are per-state pattern bitmasks.
The walk is a static unroll of chained row-index gathers — length is
shape-bucketed, no data-dependent trip counts. Accept masks come back
as two uint32 words (pattern bit i = pattern i matches).

This is the "vmapped NFA tables" piece of the north star
(BASELINE.json): regex evaluation for a whole request batch in one
dispatch instead of per-request Envoy regex calls
(envoy/cilium_l7policy.cc AccessFilter::decodeHeaders).

policyd-l7batch additions: field DFAs for one policy stack into a
single FusedDFA (per-field start states over one padded transition
tensor) so method/path/host classify in ONE dispatch; walks are
length-bucketed (L7_LEN_LADDER) instead of always unrolling the field
cap; small automata carry a stride-2 pair-transition table that halves
gather depth; and device residence is interned by pattern-set key so N
endpoints with the same policy share one table.
"""
# policyd: hot

from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import TYPE_CHECKING, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics

if TYPE_CHECKING:  # annotation-only: a runtime import would cycle
    # (l7/__init__ imports http_policy, which imports this module)
    from ..l7.regex_compile import MultiDFA


# Length rungs for the bucketed walk (PR 5 ladder discipline: a FIXED
# rung set so jit keys only on rung shapes, never on live batch dims).
# Strings longer than the top rung walk at the field cap rung.
L7_LEN_LADDER: Tuple[int, ...] = (16, 32, 64, 128)

# Pair-walk pad symbol: alphabet index 256 is the identity transition,
# so a padded tail byte leaves the state untouched in-kernel and the
# packed buffers stay 0-padded (shared with the single-byte walk).
PAIR_ALPHA = 257
PAIR_PAD = 256

# A fused automaton gets a [Q, 257*257] pair table only when it fits
# this element cap (int32 words) — 1<<23 ≈ 32 MiB, i.e. Q ≲ 126.
# Real policies compile to a few dozen states; pathological ones just
# stay on the single-byte walk.
PAIR_TABLE_CAP_ELEMS = 1 << 23


def _pack_u8(strings: Sequence[bytes], max_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """Shared packer core → ([B, max_len] uint8, [B] int32 lengths).

    Vectorized: numpy's fixed-width bytes dtype copies every string
    into a zero-padded row in one C-level pass (embedded NULs are
    preserved — only the Python ``len`` is authoritative, so a string
    ending in \\x00 still walks its full length). Overlong strings are
    truncated by the dtype; their rows are zeroed and marked length -1
    (never match — fail closed)."""
    b = len(strings)
    if not b:
        return np.zeros((0, max_len), np.uint8), np.zeros(0, np.int32)
    raw_lens = np.fromiter(map(len, strings), np.int64, b)
    out = (
        np.array(strings, dtype=f"S{max_len}")
        .view(np.uint8)
        .reshape(b, max_len)
    )
    over = raw_lens > max_len
    if over.any():
        out[over] = 0
    lens = np.where(over, -1, raw_lens).astype(np.int32)
    return out, lens


def strings_to_batch(strings: Sequence[bytes], max_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """→ (bytes [B, max_len] int32, lengths [B] int32); overlong strings
    are marked length -1 (never match — fail closed). Packs every
    request batch on the proxy hot path — vectorized, no per-string
    Python loop."""
    out, lens = _pack_u8(strings, max_len)
    return out.astype(np.int32), lens


def strings_to_batch_u8(strings: Sequence[bytes], max_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """uint8 variant for the fused kernels: half the host packing work
    and a quarter of the host→device transfer of the int32 batch (the
    kernels widen on device). The int32 ``strings_to_batch`` stays the
    pre-PR contract for the unfused programs."""
    return _pack_u8(strings, max_len)


@functools.partial(jax.jit, static_argnames=("max_len",))
def dfa_match_batch(
    trans: jnp.ndarray,  # [Q, 256] int32 (state 0 = dead)
    accept_lo: jnp.ndarray,  # [Q] uint32
    accept_hi: jnp.ndarray,  # [Q] uint32
    start: jnp.ndarray,  # [] int32
    str_bytes: jnp.ndarray,  # [B, max_len] int32
    lengths: jnp.ndarray,  # [B] int32 (-1 = fail closed)
    max_len: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """→ (mask_lo [B] uint32, mask_hi [B] uint32)."""
    b = str_bytes.shape[0]
    flat = trans.reshape(-1)
    state = jnp.full((b,), start, jnp.int32)

    def step(lvl, state):
        byte = str_bytes[:, lvl]
        nxt = jnp.take(flat, state * 256 + byte)
        return jnp.where(lvl < lengths, nxt, state)

    state = jax.lax.fori_loop(0, max_len, step, state)
    ok = lengths >= 0
    lo = jnp.where(ok, jnp.take(accept_lo, state), jnp.uint32(0))
    hi = jnp.where(ok, jnp.take(accept_hi, state), jnp.uint32(0))
    return lo, hi


def device_dfa(dfa: MultiDFA) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Host MultiDFA → device arrays (accept u64 split into u32 words)."""
    lo = (dfa.accept & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (dfa.accept >> np.uint64(32)).astype(np.uint32)
    return (
        jnp.asarray(dfa.trans),
        jnp.asarray(lo),
        jnp.asarray(hi),
        jnp.asarray(np.int32(dfa.start)),
    )


def match_patterns(
    dfa: MultiDFA, strings: Sequence[bytes], max_len: int = 128
) -> np.ndarray:
    """Convenience host API → [B] uint64 accept masks."""
    sb, lens = strings_to_batch(strings, max_len)
    lo, hi = dfa_match_batch(
        *device_dfa(dfa), jnp.asarray(sb), jnp.asarray(lens), max_len
    )
    return np.asarray(lo).astype(np.uint64) | (np.asarray(hi).astype(np.uint64) << np.uint64(32))


# ---------------------------------------------------------------------------
# policyd-l7batch: fused multi-field tables + length-bucketed walks
# ---------------------------------------------------------------------------


def len_rung(needed: int, cap: int) -> int:
    """Smallest ladder rung covering ``needed`` bytes; batches whose
    longest string exceeds the top rung walk at the field cap (itself a
    fixed shape — one extra rung per policy, not per batch)."""
    for rung in L7_LEN_LADDER:
        if needed <= rung and rung <= cap:
            return rung
    return cap


@dataclasses.dataclass(frozen=True)
class FusedDFA:
    """Per-field automata stacked into one transition tensor.

    Field f's states live in rows [f*q_pad, (f+1)*q_pad); transitions
    are rebased to absolute row ids so the flat chained gather of the
    single-DFA walk works unchanged — only the START state becomes
    per-row instead of scalar. ``pair`` (optional) is the stride-2
    table: pair[q, a*257 + b] = trans[trans[q, a], b] with symbol 256
    the identity pad."""

    trans: np.ndarray  # [F*q_pad, 256] int32, absolute row ids
    accept: np.ndarray  # [F*q_pad] uint64
    starts: np.ndarray  # [F] int32 absolute start states
    q_pad: int
    n_fields: int
    pair: Optional[np.ndarray]  # [F*q_pad, 257*257] int32 or None

    @property
    def n_states(self) -> int:
        return self.n_fields * self.q_pad


def _pair_table(trans: np.ndarray) -> np.ndarray:
    """[Q, 256]-step table → [Q, 257*257] double-step table, built
    host-side in one fancy-index composition: two walk levels collapse
    into one gather, halving the chained-gather depth on device."""
    q = trans.shape[0]
    p = np.empty((q, PAIR_ALPHA, PAIR_ALPHA), np.int32)
    p[:, :256, :256] = trans[trans]  # trans[trans[q, a], b]
    p[:, :256, 256] = trans  # (byte, pad): single step
    p[:, 256, :256] = trans  # unreachable mid-string pad; keep total
    p[:, 256, 256] = np.arange(q, dtype=np.int32)  # (pad, pad): identity
    return p.reshape(q, PAIR_ALPHA * PAIR_ALPHA)


def fuse_dfas(
    dfas: Sequence["MultiDFA"], pair_cap_elems: int = PAIR_TABLE_CAP_ELEMS
) -> FusedDFA:
    """Stack one policy's field DFAs (method/path/host, or kafka
    topic/client-id) into a FusedDFA so every field of a request batch
    classifies in a single dispatch."""
    if not dfas:
        raise ValueError("fuse_dfas needs at least one automaton")
    q_pad = max(d.trans.shape[0] for d in dfas)
    f = len(dfas)
    trans = np.empty((f * q_pad, 256), np.int32)
    accept = np.zeros(f * q_pad, np.uint64)
    starts = np.empty(f, np.int32)
    for i, d in enumerate(dfas):
        q = d.trans.shape[0]
        base = i * q_pad
        trans[base : base + q] = d.trans + base
        # padding rows are unreachable; self-loop them into the block's
        # dead state so every row id stays inside its field block
        trans[base + q : base + q_pad] = base
        accept[base : base + q] = d.accept
        starts[i] = base + d.start
    pair = None
    if f * q_pad * PAIR_ALPHA * PAIR_ALPHA <= pair_cap_elems:
        pair = _pair_table(trans)
    return FusedDFA(
        trans=trans, accept=accept, starts=starts, q_pad=q_pad,
        n_fields=f, pair=pair,
    )


def _walk_single(trans, accept_lo, accept_hi, starts, str_bytes, lengths,
                 max_len: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The single-byte walk's body, shared by its three-array and
    packed entry points. Reads only the first ``max_len`` columns of
    ``str_bytes``."""
    with jax.named_scope("table_flatten"):
        flat = trans.reshape(-1)
    state = starts

    def step(lvl, state):
        with jax.named_scope("dfa_step"):
            byte = str_bytes[:, lvl].astype(jnp.int32)
            nxt = jnp.take(flat, state * 256 + byte)
            return jnp.where(lvl < lengths, nxt, state)

    with jax.named_scope("dfa_walk"):
        state = jax.lax.fori_loop(0, max_len, step, state)
    ok = lengths >= 0
    lo = jnp.where(ok, jnp.take(accept_lo, state), jnp.uint32(0))
    hi = jnp.where(ok, jnp.take(accept_hi, state), jnp.uint32(0))
    return lo, hi


def _walk_pair(pair, accept_lo, accept_hi, starts, str_bytes, lengths,
               max_len: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The stride-2 walk's body, shared by its three-array and packed
    entry points. A column at or past ``max_len`` is read only where
    the row's length masks it to the pad symbol."""
    with jax.named_scope("table_flatten"):
        flat = pair.reshape(-1)
    state = starts
    pad = jnp.int32(PAIR_PAD)

    def step(i, state):
        with jax.named_scope("dfa_step"):
            lvl = 2 * i
            b0 = jnp.where(lvl < lengths, str_bytes[:, lvl].astype(jnp.int32), pad)
            b1 = jnp.where(lvl + 1 < lengths, str_bytes[:, lvl + 1].astype(jnp.int32), pad)
            return jnp.take(flat, (state * PAIR_ALPHA + b0) * PAIR_ALPHA + b1)

    with jax.named_scope("dfa_walk"):
        state = jax.lax.fori_loop(0, (max_len + 1) // 2, step, state)
    ok = lengths >= 0
    lo = jnp.where(ok, jnp.take(accept_lo, state), jnp.uint32(0))
    hi = jnp.where(ok, jnp.take(accept_hi, state), jnp.uint32(0))
    return lo, hi


@functools.partial(jax.jit, static_argnames=("max_len",))
def dfa_match_batch_fused(
    trans: jnp.ndarray,  # [Q, 256] int32 (stacked fields, absolute ids)
    accept_lo: jnp.ndarray,  # [Q] uint32
    accept_hi: jnp.ndarray,  # [Q] uint32
    starts: jnp.ndarray,  # [B] int32 per-row start state
    str_bytes: jnp.ndarray,  # [B, max_len] uint8 (or int32)
    lengths: jnp.ndarray,  # [B] int32 (-1 = fail closed)
    max_len: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-byte walk with PER-ROW start states: one dispatch
    classifies every field of the whole batch against its own
    sub-automaton of the stacked table. Named scopes label the device
    ops: ``table_flatten``, ``dfa_walk`` (the loop) and ``dfa_step``."""
    return _walk_single(trans, accept_lo, accept_hi, starts, str_bytes,
                        lengths, max_len)


@functools.partial(jax.jit, static_argnames=("max_len",))
def dfa_match_batch_pair(
    pair: jnp.ndarray,  # [Q, 257*257] int32 stride-2 table
    accept_lo: jnp.ndarray,  # [Q] uint32
    accept_hi: jnp.ndarray,  # [Q] uint32
    starts: jnp.ndarray,  # [B] int32 per-row start state
    str_bytes: jnp.ndarray,  # [B, max_len] uint8 (or int32), 0-padded
    lengths: jnp.ndarray,  # [B] int32 (-1 = fail closed)
    max_len: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stride-2 walk: ceil(max_len/2) chained gathers instead of
    max_len. Tail bytes past the string length are substituted with the
    identity symbol IN-KERNEL, so the packed buffers stay 0-padded and
    no post-step select is needed. Named scopes label the device ops:
    ``table_flatten``, ``dfa_walk`` (the loop) and ``dfa_step``."""
    return _walk_pair(pair, accept_lo, accept_hi, starts, str_bytes,
                      lengths, max_len)


# ---------------------------------------------------------------------------
# Packed walks: one buffer up, one result down per dispatch
# ---------------------------------------------------------------------------

# A packed walk row is the string's max_len 0-padded bytes followed by
# PACK_HEADER bytes: its length, then its start state, each a
# little-endian int32. Pad rows carry length -1 (empty accept mask).
# One array per dispatch instead of three: each upload and each output
# buffer costs a fixed ~0.1-0.35 ms on a TPU v5e, whatever its size.
PACK_HEADER = 8


def pack_walk_rows(str_bytes: np.ndarray, lengths: np.ndarray,
                   starts: np.ndarray, rows: int) -> np.ndarray:
    """[n, max_len] uint8 bytes, [n] lengths and [n] start states →
    uint8 [rows, max_len + PACK_HEADER], rows n.. being pad rows."""
    n, max_len = str_bytes.shape
    buf = np.zeros((rows, max_len + PACK_HEADER), np.uint8)
    buf[:n, :max_len] = str_bytes
    header = buf[:, max_len:].view("<i4")
    header[:n, 0] = lengths
    header[n:, 0] = -1
    header[:n, 1] = starts
    return buf


def _unpack_header(packed: jnp.ndarray, max_len: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """→ (lengths [B] int32, starts [B] int32) of a packed buffer."""
    b = packed.shape[0]
    words = packed[:, max_len : max_len + PACK_HEADER].reshape(b, 2, 4)
    header = jax.lax.bitcast_convert_type(words, jnp.int32)
    return header[:, 0], header[:, 1]


@functools.partial(jax.jit, static_argnames=("max_len",))
def dfa_match_packed_fused(
    trans: jnp.ndarray,  # [Q, 256] int32 (stacked fields, absolute ids)
    accept_lo: jnp.ndarray,  # [Q] uint32
    accept_hi: jnp.ndarray,  # [Q] uint32
    packed: jnp.ndarray,  # [B, max_len + PACK_HEADER] uint8 (pack_walk_rows)
    max_len: int,
) -> jnp.ndarray:
    """``dfa_match_batch_fused`` on one packed buffer → uint32 [2, B]:
    the accept masks' low and high words."""
    lengths, starts = _unpack_header(packed, max_len)
    return jnp.stack(_walk_single(trans, accept_lo, accept_hi, starts,
                                  packed, lengths, max_len))


@functools.partial(jax.jit, static_argnames=("max_len",))
def dfa_match_packed_pair(
    pair: jnp.ndarray,  # [Q, 257*257] int32 stride-2 table
    accept_lo: jnp.ndarray,  # [Q] uint32
    accept_hi: jnp.ndarray,  # [Q] uint32
    packed: jnp.ndarray,  # [B, max_len + PACK_HEADER] uint8 (pack_walk_rows)
    max_len: int,
) -> jnp.ndarray:
    """``dfa_match_batch_pair`` on one packed buffer → uint32 [2, B]:
    the accept masks' low and high words."""
    lengths, starts = _unpack_header(packed, max_len)
    return jnp.stack(_walk_pair(pair, accept_lo, accept_hi, starts,
                                packed, lengths, max_len))


class DeviceDFATable:
    """Device residence of one FusedDFA (interned — see below).

    Holds the transfer-once device arrays plus the host-side start
    vector from which per-batch start columns are built."""

    __slots__ = (
        "key", "trans", "accept_lo", "accept_hi", "pair",
        "starts_host", "n_states", "n_fields", "q_pad", "has_pair",
        "device_bytes",
    )

    def __init__(self, key: Tuple, fused: FusedDFA) -> None:
        lo = (fused.accept & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (fused.accept >> np.uint64(32)).astype(np.uint32)
        self.key = key
        self.trans = jnp.asarray(fused.trans)
        self.accept_lo = jnp.asarray(lo)
        self.accept_hi = jnp.asarray(hi)
        self.pair = jnp.asarray(fused.pair) if fused.pair is not None else None
        self.starts_host = np.asarray(fused.starts, np.int32)
        self.n_states = fused.n_states
        self.n_fields = fused.n_fields
        self.q_pad = fused.q_pad
        self.has_pair = fused.pair is not None
        # policyd-prof memory ledger: device-resident bytes of this
        # table (replicated — every device walks the whole automaton)
        self.device_bytes = (
            int(self.trans.nbytes)
            + int(self.accept_lo.nbytes)
            + int(self.accept_hi.nbytes)
            + (int(self.pair.nbytes) if self.pair is not None else 0)
        )


# Interned device tables, keyed by pattern-set key: N endpoints with
# the same policy share ONE device table instead of N copies. Bounded
# LRU — a changed pattern set produces a new key (the PR 7 delta
# discipline: content-addressed, so invalidation is just eviction of
# entries nothing references anymore).
DFA_INTERN_CAP = 32
_intern_lock = threading.Lock()
_interned: "OrderedDict[Tuple, DeviceDFATable]" = OrderedDict()


def intern_fused_table(key: Tuple, build: Callable[[], FusedDFA]) -> DeviceDFATable:
    with _intern_lock:
        tab = _interned.get(key)
        if tab is not None:
            _interned.move_to_end(key)
            metrics.l7_dfa_intern_total.inc({"result": "hit"})
            return tab
    # build + transfer outside the lock (subset construction and the
    # pair-table composition can be slow for big automata)
    tab = DeviceDFATable(key, build())
    with _intern_lock:
        raced = _interned.get(key)
        if raced is not None:
            _interned.move_to_end(key)
            metrics.l7_dfa_intern_total.inc({"result": "hit"})
            return raced
        _interned[key] = tab
        metrics.l7_dfa_intern_total.inc({"result": "miss"})
        while len(_interned) > DFA_INTERN_CAP:
            _interned.popitem(last=False)
            metrics.l7_dfa_intern_total.inc({"result": "evict"})
        metrics.l7_dfa_tables_interned.set(len(_interned))
        # policyd-prof memory ledger: total interned DFA residence
        metrics.device_table_bytes.set(
            float(sum(t.device_bytes for t in _interned.values())),
            {"family": "dfa", "placement": "replicated"},
        )
    return tab


def dfa_intern_stats() -> Tuple[int, int]:
    """→ (live interned tables, cap)."""
    with _intern_lock:
        return len(_interned), DFA_INTERN_CAP


def _reset_intern_for_tests() -> None:
    with _intern_lock:
        _interned.clear()
        metrics.l7_dfa_tables_interned.set(0)
        metrics.device_table_bytes.set(
            0.0, {"family": "dfa", "placement": "replicated"}
        )
