"""Policymap materialization: full verdict engine → realized lookup state.

The TPU replacement for the reference's hottest control-plane loop,
computeDesiredL3PolicyMapEntries (pkg/endpoint/policy.go:317-389): for
every local endpoint, evaluate the full policy for *every known
identity* (and every L4 slot) and emit the column-bitmap lookup tables
of ops/lookup.py plus host-visible policymap entries
(pkg/maps/policymap key format) for the datapath front-end.

The whole sweep — endpoints × identities × (L3 + each L4 slot) — is
flattened into ONE batched device call, so a full regeneration costs a
single dispatch regardless of endpoint count (the reference pays a
per-endpoint per-identity Go loop; we pay one kernel launch of int8
matmuls).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from typing import Optional

from ..compiler.program import CompiledPolicy, PROTO_TCP_N
from .bitmap import pack_bool_bits, unpack_bits_u32
from .lookup import PolicymapTables, patch_bitmap_cols
from .verdict import ALLOW, AttribTables, DevicePolicy, _mm, verdict_batch

TRAFFIC_INGRESS = 0
TRAFFIC_EGRESS = 1


@dataclasses.dataclass(frozen=True)
class PolicyKey:
    """pkg/maps/policymap PolicyKey (policymap.go:64): identity, dport
    (0 = L3-only), nexthdr (0 = L3-only), traffic direction."""

    identity: int
    dport: int
    nexthdr: int
    direction: int


@dataclasses.dataclass
class EndpointPolicySnapshot:
    """Desired policymap for one endpoint + its slot layout. Entry value
    is the proxy-redirect flag (proxy port binding happens at the proxy
    layer, pkg/proxy/proxy.go port allocator)."""

    entries: Dict[PolicyKey, int]
    slots: List[Tuple[int, int]]


def _endpoint_slots(compiled: CompiledPolicy, subj_sel_row: np.ndarray, ingress: bool):
    """Distinct (port, proto) L4 slots this endpoint's policy can
    reference: L4 entries whose subject selector matches, plus
    L7-parser ports (always TCP)."""
    d = compiled.ingress if ingress else compiled.egress

    def sel_hit(sids: np.ndarray) -> np.ndarray:
        return (subj_sel_row[sids >> 5] >> (sids & 31)) & 1

    slots = set()
    if d.e_subj.size:
        hit = sel_hit(d.e_subj.astype(np.int64)) == 1
        for port, proto in zip(d.e_port[hit], d.e_proto[hit]):
            slots.add((int(port), int(proto)))
    if d.l7_subj.size:
        hit = sel_hit(d.l7_subj.astype(np.int64)) == 1
        for port in d.l7_port[hit]:
            slots.add((int(port), PROTO_TCP_N))
    return sorted(slots)


@dataclasses.dataclass
class MaterializedState:
    """Host mirror of the realized policymap: unpacked column bitmaps +
    metadata, enabling **row patches** for identity churn (the
    incremental half of syncPolicyMap, pkg/endpoint/endpoint.go:2572)
    without re-sweeping every (endpoint, identity) pair."""

    tables: PolicymapTables
    snapshots: List[EndpointPolicySnapshot]
    ingress: bool
    endpoint_identity_ids: List[int]
    ep_rows: np.ndarray  # [E] int32
    ep_slots: List[List[Tuple[int, int]]]
    allow_nc: np.ndarray  # [N, C_pad] bool (host, mutable)
    red_nc: np.ndarray  # [N, C_pad] bool
    n_cols: int
    # Verdict attribution (policyd-flows): per-(identity row, column)
    # deciding-rule index from an attrib=True sweep — EXACT per-peer
    # attribution for the pipeline's lookup path (-1 = no rule; deny
    # drops carry the deny rule even though their allow bit is 0).
    # None when the sweep ran without attribution (FlowAttribution off
    # or snapshot-restored compile with no rule-origin state).
    rule_nc: Optional[np.ndarray] = None  # [N, C_pad] int32 (host)
    rule_tab: Optional[jnp.ndarray] = None  # [N, C_pad] int32 (device)


def materialize_endpoints(
    compiled: CompiledPolicy,
    device: DevicePolicy,
    endpoint_identity_ids: Sequence[int],
    *,
    ingress: bool = True,
    block: int = 8192,
) -> Tuple[PolicymapTables, List[EndpointPolicySnapshot]]:
    st = materialize_endpoints_state(
        compiled, device, endpoint_identity_ids, ingress=ingress, block=block
    )
    return st.tables, st.snapshots


def _seg_bucket(n_seg: int) -> int:
    """Power-of-two segment count, at least 128: a node's first hundred
    endpoints, added one at a time, share one compiled sweep."""
    b = 128
    while b < n_seg:
        b <<= 1
    return b


@functools.partial(jax.jit, static_argnames=("n", "ingress", "block"))
def _sweep_device(
    policy: DevicePolicy,
    seg_row: jnp.ndarray,  # [n_seg] int32
    seg_port: jnp.ndarray,
    seg_proto: jnp.ndarray,
    seg_l4: jnp.ndarray,  # [n_seg] bool
    n: int,
    ingress: bool,
    block: int,
):
    """The endpoints × identities × slots sweep with the flattened
    index arrays generated ON DEVICE and results bit-packed before
    leaving it — the host⇄device traffic is [n_seg] in and
    3 × [n_seg, n/32] out instead of 5 × [n_seg·n] in and
    3 × [n_seg·n] out (the host-built repeat/tile arrays made the
    sweep upload-bound: ~600MB at the 100k-identity stretch scale)."""
    n_seg = seg_row.shape[0]
    subj = jnp.repeat(seg_row, n)
    peer = jnp.tile(jnp.arange(n, dtype=jnp.int32), n_seg)
    v = verdict_batch(
        policy,
        subj,
        peer,
        jnp.repeat(seg_port, n),
        jnp.repeat(seg_proto, n),
        jnp.repeat(seg_l4, n),
        ingress=ingress,
        block=block,
    )
    allow = pack_bool_bits((v.decision == ALLOW).reshape(n_seg, n))
    l3a = pack_bool_bits((v.l3 == 1).reshape(n_seg, n))
    red = pack_bool_bits(v.l7_redirect.reshape(n_seg, n))
    return allow, l3a, red


@functools.partial(
    jax.jit, static_argnames=("n", "ingress", "block", "n_rules")
)
def _sweep_device_attrib(
    policy: DevicePolicy,
    seg_row: jnp.ndarray,
    seg_port: jnp.ndarray,
    seg_proto: jnp.ndarray,
    seg_l4: jnp.ndarray,
    origin: AttribTables,
    n: int,
    ingress: bool,
    block: int,
    n_rules: int,
):
    """_sweep_device plus the attribution tail: also returns the
    [n_seg, n] int32 deciding-rule index per (segment, identity row) —
    the source of MaterializedState.rule_tab. A SEPARATE jitted entry
    so the attribution-off sweep keeps its exact original program."""
    n_seg = seg_row.shape[0]
    subj = jnp.repeat(seg_row, n)
    peer = jnp.tile(jnp.arange(n, dtype=jnp.int32), n_seg)
    v, at, _hits = verdict_batch(
        policy,
        subj,
        peer,
        jnp.repeat(seg_port, n),
        jnp.repeat(seg_proto, n),
        jnp.repeat(seg_l4, n),
        ingress=ingress,
        block=block,
        attrib=True,
        origin=origin,
        n_rules=n_rules,
    )
    allow = pack_bool_bits((v.decision == ALLOW).reshape(n_seg, n))
    l3a = pack_bool_bits((v.l3 == 1).reshape(n_seg, n))
    red = pack_bool_bits(v.l7_redirect.reshape(n_seg, n))
    return allow, l3a, red, at.rule.reshape(n_seg, n)


@functools.partial(jax.jit, static_argnames=("n", "ingress", "nblock"))
def _sweep_device_matrix(
    policy: DevicePolicy,
    seg_row: jnp.ndarray,  # [g] int32
    seg_port: jnp.ndarray,
    seg_proto: jnp.ndarray,
    seg_l4: jnp.ndarray,  # [g] bool
    n: int,
    ingress: bool,
    nblock: int,
):
    """Identity-major matrix formulation of the segment sweep.

    The flow-major sweep evaluates each (segment, identity) pair as an
    independent flow: every peer row re-contracts the [S, S]/[S, K1]
    relation matrices per segment, costing O(g·N·S²). But within one
    sweep the segment side (subject selector row, port one-hot, combo
    and L7-filter coverage) is FIXED per segment — so hoist it: compute
    the per-peer term vectors once per identity block (O(N·S²) total)
    and contract them against the [·, g] segment matrices (O(g·N·S)).
    At the 100k-identity stretch scale that is a ~n_seg× FLOP cut over
    the flow sweep for identical outputs.

    Bit-identity with _verdict_block: every reduction here is
    ``any(a ∧ b) == (Σ a·b) > 0`` over 0/1 int8 operands with int32
    accumulation (S < 2³¹, no overflow), and the one per-flow data
    dependence — group_ok folding req_ok — is handled by evaluating
    both req_ok phases and selecting per (peer, segment) cell on the
    deny matrix. Returns the same packed (allow, l3, redirect)
    [g, ceil(n/32)] words as _sweep_device."""
    t = policy.ingress if ingress else policy.egress
    subj8 = unpack_bits_u32(jnp.take(policy.sel_match, seg_row, axis=0))  # [g, S]
    pp = (
        (seg_port[:, None] == t.ports[None, :])
        & (seg_proto[:, None] == t.protos[None, :])
        & seg_l4[:, None]
    ).astype(jnp.int8)  # [g, P4]
    subj_t8 = subj8.T  # [S, g]
    combo_t = (_mm(subj8, t.s1_mat) & _mm(pp, t.p1_mat)).astype(jnp.int8).T  # [K1, g]
    sp7_t = (_mm(subj8, t.s7_mat) & _mm(pp, t.p7_mat)).astype(jnp.int8).T  # [K7, g]
    has_l4 = seg_l4[None, :]  # [1, g]

    n_pad = -(-n // nblock) * nblock
    row_blocks = jnp.arange(n_pad, dtype=jnp.int32).reshape(-1, nblock)

    def blk(rows):
        # (jnp.take clips the padded tail rows; their outputs are
        # sliced off below)
        peer8 = unpack_bits_u32(jnp.take(policy.sel_match, rows, axis=0))  # [nb, S]
        peer_deny = _mm(jnp.int8(1) - peer8, t.deny_t).astype(jnp.int8)  # [nb, S]
        peer_allow = _mm(peer8, t.allow_t).astype(jnp.int8)
        peer_en = _mm(peer8, t.en_t).astype(jnp.int8)  # [nb, K1]
        peer_ee = _mm(peer8, t.ee_t).astype(jnp.int8)
        deny = _mm(peer_deny, subj_t8)  # [nb, g] bool
        l3_allow = _mm(peer_allow, subj_t8)
        en_any = _mm(peer_en, combo_t)  # [nb, g]
        ee_any = _mm(peer_ee, combo_t)
        l4_allow = en_any | (~deny & ee_any)

        gpn_hit = _mm(peer8, t.gpn_mat)  # [nb, G]
        gpe_hit = _mm(peer8, t.gpe_mat)
        gok_true = (gpn_hit | gpe_hit | t.group_no_peers[None, :]).astype(jnp.int8)
        gok_false = (gpn_hit | t.group_no_peers[None, :]).astype(jnp.int8)
        l7_true = _mm(_mm(gok_true, t.g7_mat).astype(jnp.int8), sp7_t)  # [nb, g]
        l7_false = _mm(_mm(gok_false, t.g7_mat).astype(jnp.int8), sp7_t)
        l7_present = jnp.where(deny, l7_false, l7_true)

        l3_pass = l3_allow & ~deny
        allow_b = l3_pass | (has_l4 & l4_allow)
        red_b = has_l4 & l4_allow & l7_present
        return allow_b, l3_pass, red_b

    allow_b, l3_b, red_b = jax.lax.map(blk, row_blocks)  # [blocks, nb, g]

    def fin(x):
        return pack_bool_bits(x.reshape(n_pad, -1)[:n].T)

    return fin(allow_b), fin(l3_b), fin(red_b)


def _unpack_rows(words: np.ndarray, n: int) -> np.ndarray:
    """[n_seg, ceil(n/32)] uint32 → [n_seg, n] bool (pack_bool_bits
    inverse, host-side)."""
    words = np.ascontiguousarray(words)
    bits = np.unpackbits(
        words.view(np.uint8).reshape(words.shape[0], -1),
        axis=1,
        bitorder="little",
    )
    return bits[:, :n].astype(bool)


# Identity rows per matrix-sweep block: bounds the [nblock, S]
# peer-term activations while keeping the MXU contraction dims full.
_MATRIX_NBLOCK = 1024
# Segments per matrix-sweep dispatch: its three stacked bool outputs
# are [N, chunk] each (~150 MB apiece at 100k identities)
_MATRIX_SEG_CHUNK = 512


def _sweep_segments(
    device: DevicePolicy,
    sr: np.ndarray,  # [n_seg] int32 subject rows
    sp: np.ndarray,  # [n_seg] int32 ports
    spr: np.ndarray,  # [n_seg] int32 protos
    sl: np.ndarray,  # [n_seg] bool has_l4
    n: int,
    *,
    ingress: bool,
    block: int,
    attrib_origin: Optional[AttribTables] = None,
    n_rules: int = 0,
    sweep: str = "auto",
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Chunked segments × all-identities sweep shared by the full
    materializer and the delta column-patch path → unpacked
    (allow_sn, l3_sn, red_sn) [n_seg, n] bool + rule_sn [n_seg, n]
    int32 (-1 when no attribution ran).

    ``sweep`` picks the kernel: "auto" routes attribution-free sweeps
    through the identity-major matrix kernel (_sweep_device_matrix —
    the O(N·S²) formulation); "flow" forces the original per-flow
    kernel (the parity suite diffs the two bit-for-bit). Attribution
    sweeps always take the flow kernel: the first-match rule tail needs
    the per-flow term vectors the matrix form contracts away."""
    n_seg = len(sr)
    use_matrix = sweep != "flow" and attrib_origin is None
    # Chunk the segment axis, then pad each chunk to a bucket (dummy L3
    # segs against row 0) so repeated materializations reuse the
    # compiled sweep. The flow kernel flattens segments x identities
    # into one batch, so its chunk keeps that row count bounded
    # (~big-batch sized) regardless of endpoint count x identity
    # capacity. The matrix kernel's cost is its peer terms, recomputed
    # for every chunk whatever its size, so it takes the most segments
    # its stacked [blocks, nb, chunk] outputs hold comfortably.
    if use_matrix:
        seg_chunk = _MATRIX_SEG_CHUNK
    else:
        budget = max(8, (1 << 23) // max(1, n))
        seg_chunk = 1 << (budget.bit_length() - 1)  # power of two ≤ budget
    seg_chunk = min(seg_chunk, _seg_bucket(n_seg))
    # No sweep reads the identities' label bits, whose word count grows
    # with the label vocabulary: left in, they would recompile every
    # sweep each time a new label adds a word.
    device = device.replace(id_bits=None)
    aw_parts: List[np.ndarray] = []
    l3_parts: List[np.ndarray] = []
    rw_parts: List[np.ndarray] = []
    rl_parts: List[np.ndarray] = []
    for lo in range(0, n_seg, seg_chunk):
        hi = min(lo + seg_chunk, n_seg)
        pad = min(_seg_bucket(hi - lo), seg_chunk) - (hi - lo)
        chunk = (
            # control-plane rebuild: VRAM-bounded chunking over the
            # segment sweep — a handful of large device calls, not a
            # per-flow dispatch loop (the serving path never runs this)
            jnp.asarray(np.pad(sr[lo:hi], (0, pad))),  # policyd-lint: disable=TPU002
            jnp.asarray(np.pad(sp[lo:hi], (0, pad))),
            jnp.asarray(np.pad(spr[lo:hi], (0, pad))),
            jnp.asarray(np.pad(sl[lo:hi], (0, pad))),
        )
        if attrib_origin is not None:
            aw, l3w, rw, rl = _sweep_device_attrib(
                device, *chunk, attrib_origin, n, ingress, block, n_rules
            )
            # control-plane rebuild pull, same cadence as the aw/l3w
            # pulls below (baselined) — never on the serving path
            rl_parts.append(np.asarray(rl)[: hi - lo])  # policyd-lint: disable=TPU001
        elif use_matrix:
            aw, l3w, rw = _sweep_device_matrix(
                device, *chunk, n, ingress, _MATRIX_NBLOCK
            )
        else:
            aw, l3w, rw = _sweep_device(device, *chunk, n, ingress, block)
        aw_parts.append(np.asarray(aw)[: hi - lo])
        l3_parts.append(np.asarray(l3w)[: hi - lo])
        rw_parts.append(np.asarray(rw)[: hi - lo])
    if aw_parts:
        allow_sn = _unpack_rows(np.concatenate(aw_parts), n)
        l3_sn = _unpack_rows(np.concatenate(l3_parts), n)
        red_sn = _unpack_rows(np.concatenate(rw_parts), n)
    else:  # zero endpoints: nothing to sweep
        allow_sn = l3_sn = red_sn = np.zeros((0, n), bool)
    rule_sn = (
        np.concatenate(rl_parts)
        if rl_parts
        else np.full((n_seg, n), -1, np.int32)
    )
    return allow_sn, l3_sn, red_sn, rule_sn


# policyd: refresh-path
def materialize_endpoints_state(
    compiled: CompiledPolicy,
    device: DevicePolicy,
    endpoint_identity_ids: Sequence[int],
    *,
    ingress: bool = True,
    block: int = 8192,
    attrib_origin: Optional[AttribTables] = None,
    n_rules: int = 0,
    sweep: str = "auto",
) -> MaterializedState:
    """``attrib_origin`` (with ``n_rules``) switches the sweep to the
    attribution kernel variant: the result additionally carries
    rule_nc/rule_tab, the exact per-(identity row, column) deciding-rule
    index the pipeline's lookup path gathers from under
    FlowAttribution. Off (None), the sweep and its jit program are
    untouched."""
    n = compiled.id_bits.shape[0]
    ep_rows = compiled.rows_for(endpoint_identity_ids)
    # Bounded [E, S/32] pull of just the endpoint subject rows — never
    # the full [N, S/32] matrix (at the 100k stretch that pull alone
    # moved ~1.2GB per `policy explain`). The row list is padded to a
    # power-of-two bucket (row 0 repeated), so a node adding endpoints
    # one at a time compiles the gather once per bucket, not once per
    # endpoint count.
    ep_take = np.zeros(_seg_bucket(len(ep_rows)), np.int32)
    ep_take[: len(ep_rows)] = ep_rows
    ep_sel = np.asarray(  # policyd-lint: disable=TPU001,TPU005
        jnp.take(device.sel_match, jnp.asarray(ep_take), axis=0)
    )[: len(ep_rows)]
    live = compiled.row_live
    direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS

    # Flatten (endpoint L3 sweep) + (endpoint, slot) sweeps into one batch.
    ep_slots: List[List[Tuple[int, int]]] = [
        _endpoint_slots(compiled, ep_sel[i], ingress) for i in range(len(ep_rows))
    ]
    seg_row: List[int] = []
    seg_port: List[int] = []
    seg_proto: List[int] = []
    seg_l4: List[bool] = []
    for e, row in enumerate(ep_rows):
        seg_row.append(int(row))
        seg_port.append(0)
        seg_proto.append(0)
        seg_l4.append(False)
        for port, proto in ep_slots[e]:
            seg_row.append(int(row))
            seg_port.append(port)
            seg_proto.append(proto)
            seg_l4.append(True)

    n_seg = len(seg_row)
    allow_sn, l3_sn, red_sn, rule_sn = _sweep_segments(
        device,
        np.asarray(seg_row, np.int32),
        np.asarray(seg_port, np.int32),
        np.asarray(seg_proto, np.int32),
        np.asarray(seg_l4, bool),
        n,
        ingress=ingress,
        block=block,
        attrib_origin=attrib_origin,
        n_rules=n_rules,
        sweep=sweep,
    )

    # Column layout: one column per (endpoint, L3) + (endpoint, slot).
    col_ep: List[int] = []
    col_port: List[int] = []
    col_proto: List[int] = []
    col_is_l3: List[bool] = []
    col_allow: List[np.ndarray] = []
    col_red: List[np.ndarray] = []
    col_rule: List[np.ndarray] = []
    snapshots: List[EndpointPolicySnapshot] = []

    seg = 0
    for e, row in enumerate(ep_rows):
        l3_allow = l3_sn[seg] & live
        col_rule.append(rule_sn[seg])
        seg += 1
        col_ep.append(e)
        col_port.append(0)
        col_proto.append(0)
        col_is_l3.append(True)
        col_allow.append(l3_allow)
        col_red.append(np.zeros(n, bool))
        entries: Dict[PolicyKey, int] = {}
        for r_idx in np.nonzero(l3_allow)[0]:
            entries[PolicyKey(int(compiled.row_ids[r_idx]), 0, 0, direction)] = 0
        for port, proto_n in ep_slots[e]:
            allow = allow_sn[seg] & live
            redirect = red_sn[seg] & live
            col_rule.append(rule_sn[seg])
            seg += 1
            col_ep.append(e)
            col_port.append(port)
            col_proto.append(proto_n)
            col_is_l3.append(False)
            col_allow.append(allow)
            col_red.append(redirect)
            # Exact {id, port, proto} entries: the datapath consults the
            # exact key first (bpf/lib/policy.h:46), so L3-allowed
            # identities still need one when the filter redirects.
            for r_idx in np.nonzero(allow & (~l3_allow | redirect))[0]:
                key = PolicyKey(int(compiled.row_ids[r_idx]), port, proto_n, direction)
                # ``redirect`` is the host np column computed above —
                # no device RTT, just a scalar off the sweep result
                entries[key] = int(redirect[r_idx])  # policyd-lint: disable=TPU005
        snapshots.append(EndpointPolicySnapshot(entries=entries, slots=ep_slots[e]))

    c = len(col_ep)
    c_pad = max(32, ((c + 31) // 32) * 32)
    pad = c_pad - c
    allow_nc = np.zeros((n, c_pad), bool)
    red_nc = np.zeros((n, c_pad), bool)
    rule_nc = None
    if attrib_origin is not None:
        rule_nc = np.full((n, c_pad), -1, np.int32)
    if c:
        allow_nc[:, :c] = np.stack(col_allow, axis=1)
        red_nc[:, :c] = np.stack(col_red, axis=1)
        if rule_nc is not None:
            rule_nc[:, :c] = np.stack(col_rule, axis=1)

    tables = PolicymapTables(
        col_ep=jnp.asarray(np.pad(np.asarray(col_ep, np.int32), (0, pad), constant_values=-1)),
        col_port=jnp.asarray(np.pad(np.asarray(col_port, np.int32), (0, pad))),
        col_proto=jnp.asarray(np.pad(np.asarray(col_proto, np.int32), (0, pad))),
        col_is_l3=jnp.asarray(np.pad(np.asarray(col_is_l3, bool), (0, pad))),
        # allow ‖ redirect in one table: the lookup kernel's row gather
        # lowers to a single one-hot matmul serving both bitmaps. Packed
        # here, on the host, and uploaded once.
        id_bits=jnp.asarray(_pack_rows(np.concatenate([allow_nc, red_nc], axis=1))),
    )
    return MaterializedState(
        tables=tables,
        snapshots=snapshots,
        ingress=ingress,
        endpoint_identity_ids=list(endpoint_identity_ids),
        ep_rows=ep_rows,
        ep_slots=ep_slots,
        allow_nc=allow_nc,
        red_nc=red_nc,
        n_cols=c,
        rule_nc=rule_nc,
        rule_tab=jnp.asarray(rule_nc) if rule_nc is not None else None,
    )


def state_from_snapshot(row_ids: np.ndarray, fields: dict) -> MaterializedState:
    """Rebuild a MaterializedState from compiler/snapshot.py fields —
    the restore half of the pinned-map persistence analog. The column
    bitmaps are authoritative; per-endpoint snapshots (policymap dump
    surface) are re-derived from them, and the device tables re-packed
    and uploaded. No policy sweep runs: this is a load, not a derive."""
    allow_nc = np.asarray(fields["allow_nc"], bool)
    red_nc = np.asarray(fields["red_nc"], bool)
    col_ep = np.asarray(fields["col_ep"], np.int32)
    col_port = np.asarray(fields["col_port"], np.int32)
    col_proto = np.asarray(fields["col_proto"], np.int32)
    col_is_l3 = np.asarray(fields["col_is_l3"], bool)
    ep_slots = fields["ep_slots"]
    ingress = bool(fields["ingress"])
    direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS

    snapshots: List[EndpointPolicySnapshot] = []
    col = 0
    for e, slots in enumerate(ep_slots):
        l3_allow = allow_nc[:, col]
        col += 1
        entries: Dict[PolicyKey, int] = {}
        for r_idx in np.nonzero(l3_allow)[0]:
            entries[PolicyKey(int(row_ids[r_idx]), 0, 0, direction)] = 0
        for port, proto_n in slots:
            allow = allow_nc[:, col]
            redirect = red_nc[:, col]
            col += 1
            for r_idx in np.nonzero(allow & (~l3_allow | redirect))[0]:
                key = PolicyKey(int(row_ids[r_idx]), port, proto_n, direction)
                entries[key] = int(redirect[r_idx])
        snapshots.append(EndpointPolicySnapshot(entries=entries, slots=slots))

    tables = PolicymapTables(
        col_ep=jnp.asarray(col_ep),
        col_port=jnp.asarray(col_port),
        col_proto=jnp.asarray(col_proto),
        col_is_l3=jnp.asarray(col_is_l3),
        id_bits=jnp.asarray(_pack_rows(np.concatenate([allow_nc, red_nc], axis=1))),
    )
    return MaterializedState(
        tables=tables,
        snapshots=snapshots,
        ingress=ingress,
        endpoint_identity_ids=list(fields["endpoint_identity_ids"]),
        ep_rows=np.asarray(fields["ep_rows"], np.int32),
        ep_slots=ep_slots,
        allow_nc=allow_nc,
        red_nc=red_nc,
        n_cols=int(fields["n_cols"]),
    )


@jax.jit
def _patch_bitmap_rows(
    id_bits: jnp.ndarray,
    idx: jnp.ndarray,
    comb_rows: jnp.ndarray,
):
    return id_bits.at[idx].set(comb_rows)


@dataclasses.dataclass
class PlacedTables:
    """Mutable holder for the mesh-placed copies of a materialized
    direction's device tables (the pipeline's per-direction cache).
    The patch paths scatter the SAME idx/vals into these copies so the
    O(delta) discipline survives placement: a jit ``.at[].set`` on a
    sharded operand keeps the operand's sharding (GSPMD propagates it
    through the scatter), so a row patch under ``P("ident", None)``
    stays O(delta) per device — no re-place, no all-gather."""

    tables: PolicymapTables
    rule_tab: Optional[jnp.ndarray] = None


def patch_identity_rows(
    state: MaterializedState,
    compiled: CompiledPolicy,
    device: DevicePolicy,
    row_events: Sequence[Tuple[int, int, bool]],
    *,
    block: int = 8192,
    attrib_origin: Optional[AttribTables] = None,
    n_rules: int = 0,
    placed: Optional[PlacedTables] = None,
) -> None:
    """Apply identity-churn row updates to a materialized policymap.

    ``row_events``: (row, identity_id, live) in order. Dead rows zero
    out; live rows get a fresh verdict sweep over every column segment
    of every endpoint — n_seg × k flows instead of the full n_seg × N
    re-materialization. Snapshots (host policymap dicts) are patched in
    place, so fastpath caches holding references see the update.

    When the state carries attribution (rule_nc/rule_tab) the patch
    sweep runs the attrib kernel variant too (pass ``attrib_origin``/
    ``n_rules`` from the engine); without an origin the patched rows'
    rule entries degrade to -1 (unattributed) rather than going stale."""
    if not row_events:
        return
    direction = TRAFFIC_INGRESS if state.ingress else TRAFFIC_EGRESS
    # last event per row wins for the verdict sweep; all ids seen on a
    # row get their stale snapshot entries dropped
    stale_ids = {int(ident) for _r, ident, _l in row_events}
    final: Dict[int, Tuple[int, bool]] = {}
    for row, ident, live in row_events:
        final[int(row)] = (int(ident), bool(live))

    for snap in state.snapshots:
        for key in [k for k in snap.entries if k.identity in stale_ids]:
            del snap.entries[key]

    rows = sorted(final)
    live_rows = [r for r in rows if final[r][1]]
    if live_rows:
        seg_subj: List[int] = []
        seg_port: List[int] = []
        seg_proto: List[int] = []
        seg_l4: List[bool] = []
        seg_col: List[int] = []
        seg_ep: List[int] = []
        col = 0
        for e, ep_row in enumerate(state.ep_rows):
            seg_subj.append(int(ep_row))
            seg_port.append(0)
            seg_proto.append(0)
            seg_l4.append(False)
            seg_col.append(col)
            seg_ep.append(e)
            col += 1
            for port, proto in state.ep_slots[e]:
                seg_subj.append(int(ep_row))
                seg_port.append(port)
                seg_proto.append(proto)
                seg_l4.append(True)
                seg_col.append(col)
                seg_ep.append(e)
                col += 1
        n_seg = len(seg_subj)
        k = len(live_rows)
        # Fit the verdict-batch block to the sweep: a single-identity
        # patch is n_seg·k ≈ E·(1+slots) flows, and padding that to the
        # dispatch-sized 8192 block makes the [block, S] matmuls ~100×
        # larger than the work (policyd-sparse: the O(k) update budget
        # is dominated by exactly this pad waste). Pow2 buckets (min
        # 64) keep the jit program count bounded by the ladder between
        # 64 and ``block``.
        block = min(block, max(64, _seg_bucket(n_seg * k)))
        peer = np.tile(np.asarray(live_rows, np.int32), n_seg)
        sweep_args = (
            device,
            jnp.asarray(np.repeat(np.asarray(seg_subj, np.int32), k)),
            jnp.asarray(peer),
            jnp.asarray(np.repeat(np.asarray(seg_port, np.int32), k)),
            jnp.asarray(np.repeat(np.asarray(seg_proto, np.int32), k)),
            jnp.asarray(np.repeat(np.asarray(seg_l4, bool), k)),
        )
        rl = None
        if state.rule_nc is not None and attrib_origin is not None:
            v, at, _hits = verdict_batch(
                *sweep_args,
                ingress=state.ingress,
                block=block,
                attrib=True,
                origin=attrib_origin,
                n_rules=n_rules,
            )
            # patch-path pull, same cadence as the dec/l3d/red pulls
            # below (baselined) — control plane, never per-flow
            rl = np.asarray(at.rule).reshape(n_seg, k)  # policyd-lint: disable=TPU001
        else:
            v = verdict_batch(*sweep_args, ingress=state.ingress, block=block)
        dec = np.asarray(v.decision).reshape(n_seg, k)
        l3d = np.asarray(v.l3).reshape(n_seg, k)
        red = np.asarray(v.l7_redirect).reshape(n_seg, k)

    for r in rows:
        state.allow_nc[r] = False
        state.red_nc[r] = False
        if state.rule_nc is not None:
            state.rule_nc[r] = -1

    if live_rows:
        row_pos = {r: i for i, r in enumerate(live_rows)}
        # per-endpoint L3 allow for the exact-entry condition
        ep_l3 = {}
        seg_i = 0
        for e in range(len(state.ep_rows)):
            ep_l3[e] = l3d[seg_i] == 1
            seg_i += 1 + len(state.ep_slots[e])
        seg_i = 0
        for e in range(len(state.ep_rows)):
            snap = state.snapshots[e]
            l3_allow = ep_l3[e]
            # L3 column
            ci = seg_col[seg_i]
            for r in live_rows:
                i = row_pos[r]
                allowed = l3_allow[i]
                state.allow_nc[r, ci] = allowed
                if rl is not None:
                    state.rule_nc[r, ci] = rl[seg_i, i]
                if allowed:
                    ident = final[r][0]
                    snap.entries[PolicyKey(ident, 0, 0, direction)] = 0
            seg_i += 1
            for port, proto in state.ep_slots[e]:
                ci = seg_col[seg_i]
                for r in live_rows:
                    i = row_pos[r]
                    allowed = dec[seg_i, i] == ALLOW
                    redir = bool(red[seg_i, i])
                    state.allow_nc[r, ci] = allowed
                    state.red_nc[r, ci] = allowed and redir
                    if rl is not None:
                        state.rule_nc[r, ci] = rl[seg_i, i]
                    if allowed and (not l3_allow[i] or redir):
                        ident = final[r][0]
                        snap.entries[PolicyKey(ident, port, proto, direction)] = int(redir)
                seg_i += 1

    idx = np.asarray(rows, np.int32)
    comb_rows = _pack_rows(
        np.concatenate([state.allow_nc[idx], state.red_nc[idx]], axis=1)
    )
    new_bits = _patch_bitmap_rows(
        state.tables.id_bits, jnp.asarray(idx), jnp.asarray(comb_rows)
    )
    state.tables = state.tables.replace(id_bits=new_bits)
    if placed is not None:
        # same scatter onto the mesh-placed copy: sharding propagates
        # through .at[].set, so the placed tables stay placed
        placed.tables = placed.tables.replace(
            id_bits=_patch_bitmap_rows(
                placed.tables.id_bits,
                jnp.asarray(idx),
                jnp.asarray(comb_rows),
            )
        )
    if state.rule_nc is not None and state.rule_tab is not None:
        rvals = jnp.asarray(state.rule_nc[idx])
        state.rule_tab = _patch_bitmap_rows(
            state.rule_tab, jnp.asarray(idx), rvals
        )
        if placed is not None and placed.rule_tab is not None:
            placed.rule_tab = _patch_bitmap_rows(
                placed.rule_tab, jnp.asarray(idx), rvals
            )


def _pack_rows(rows_bool: np.ndarray) -> np.ndarray:
    """[k, C_pad] bool → [k, C_pad/32] uint32 (C_pad is a multiple of
    32 by construction)."""
    packed = np.packbits(rows_bool, axis=1, bitorder="little")
    return packed.view(np.uint32).reshape(rows_bool.shape[0], rows_bool.shape[1] // 32)


def _pack_col_word(cols_bool: np.ndarray) -> np.ndarray:
    """[N, ≤32] bool column block → [N] uint32 (one packed id_bits
    word; short tails zero-pad, matching pack_bool_bits)."""
    n, w = cols_bool.shape
    if w < 32:
        cols_bool = np.concatenate(
            [cols_bool, np.zeros((n, 32 - w), bool)], axis=1
        )
    return np.packbits(cols_bool, axis=1, bitorder="little").view(np.uint32)[:, 0]


def _pad_cols_pow2(idx: np.ndarray, vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a column scatter to a power-of-two width by repeating the
    LAST column (duplicate .set with identical values is deterministic)
    so patch_bitmap_cols compiles per bucket, not per delta width."""
    k = idx.shape[0]
    bucket = 1
    while bucket < k:
        bucket <<= 1
    if bucket == k:
        return idx, vals
    return (
        np.concatenate([idx, np.repeat(idx[-1:], bucket - k)]),
        np.concatenate(
            [vals, np.repeat(vals[:, -1:], bucket - k, axis=1)], axis=1
        ),
    )


def patch_endpoints_state(
    state: MaterializedState,
    compiled: CompiledPolicy,
    device: DevicePolicy,
    touched_sids: Sequence[int],
    *,
    block: int = 8192,
    attrib_origin: Optional[AttribTables] = None,
    n_rules: int = 0,
    sweep: str = "auto",
    placed: Optional[PlacedTables] = None,
) -> bool:
    """O(delta) column rematerialization for a rule append/delete.

    Every verdict term is gated on the SUBJECT selector (deny/allow
    cells, combos through s1, L7 filters through s7 — see
    _verdict_block), so a rule delta can only change policymap cells in
    columns belonging to endpoints whose identity matches one of the
    rule's subject selectors (``touched_sids``, from the engine's delta
    log). Re-sweep exactly those endpoints' column segments against the
    already-patched device tables and scatter the changed id_bits words
    / rule_tab columns — O(affected · N) instead of the full
    E × N re-materialization.

    Returns False when the delta is NOT expressible as a column patch
    and the caller must fall back to ``materialize_endpoints_state``:
    identity row capacity moved, attribution state mismatched, or an
    affected endpoint's slot set GREW (a new (port, proto) needs new
    columns — shrunken slot sets keep their stale columns, which
    re-sweep to the correct now-denied values). Snapshots of affected
    endpoints are rebuilt in place so fastpath caches holding
    references observe the update, mirroring patch_identity_rows."""
    n = compiled.id_bits.shape[0]
    if state.allow_nc.shape[0] != n:
        return False  # row-bucket crossing — full rebuild
    if (state.rule_nc is not None) != (attrib_origin is not None):
        return False
    sids = sorted({int(s) for s in touched_sids})
    n_ep = len(state.ep_rows)
    if not sids or n_ep == 0:
        return True
    s_words = device.sel_match.shape[1]
    if any(s >> 5 >= s_words for s in sids):
        return False  # selector axis outgrew the device tables

    # Affected endpoints: subject row matches any touched selector.
    # Bounded [E, S/32] control-plane pull of just the endpoint rows —
    # the O(delta) point of this path (never the [N, S/32] matrix).
    ep_sel = np.asarray(  # policyd-lint: disable=TPU001
        jnp.take(
            device.sel_match, jnp.asarray(state.ep_rows, np.int32), axis=0
        )
    )
    word = np.asarray([s >> 5 for s in sids])
    bit = np.asarray([s & 31 for s in sids], np.uint32)
    hit = ((ep_sel[:, word] >> bit[None, :]) & 1).astype(bool).any(axis=1)
    affected = np.nonzero(hit)[0]
    if affected.size == 0:
        return True  # no local endpoint matches the rule's subject

    # Canonical column offsets (the materializer's layout: one L3
    # column then one per slot, endpoint-major).
    col_of = np.zeros(n_ep + 1, np.int64)
    for e in range(n_ep):
        col_of[e + 1] = col_of[e] + 1 + len(state.ep_slots[e])
    if int(col_of[n_ep]) != state.n_cols:
        return False

    # Slot-layout guard: the patch reuses the existing columns.
    for e in affected:
        new_slots = _endpoint_slots(compiled, ep_sel[e], state.ingress)
        if not set(new_slots) <= set(state.ep_slots[e]):
            return False

    seg_row: List[int] = []
    seg_port: List[int] = []
    seg_proto: List[int] = []
    seg_l4: List[bool] = []
    for e in affected:
        row = int(state.ep_rows[e])
        seg_row.append(row)
        seg_port.append(0)
        seg_proto.append(0)
        seg_l4.append(False)
        for port, proto in state.ep_slots[e]:
            seg_row.append(row)
            seg_port.append(port)
            seg_proto.append(proto)
            seg_l4.append(True)

    allow_sn, l3_sn, red_sn, rule_sn = _sweep_segments(
        device,
        np.asarray(seg_row, np.int32),
        np.asarray(seg_port, np.int32),
        np.asarray(seg_proto, np.int32),
        np.asarray(seg_l4, bool),
        n,
        ingress=state.ingress,
        block=block,
        attrib_origin=attrib_origin,
        n_rules=n_rules,
        sweep=sweep,
    )

    live = compiled.row_live
    direction = TRAFFIC_INGRESS if state.ingress else TRAFFIC_EGRESS
    touched_cols: List[int] = []
    seg = 0
    for e in affected:
        snap = state.snapshots[e]
        l3_allow = l3_sn[seg] & live
        ci = int(col_of[e])
        state.allow_nc[:, ci] = l3_allow
        state.red_nc[:, ci] = False
        if state.rule_nc is not None:
            state.rule_nc[:, ci] = rule_sn[seg]
        touched_cols.append(ci)
        seg += 1
        entries: Dict[PolicyKey, int] = {}
        for r_idx in np.nonzero(l3_allow)[0]:
            entries[PolicyKey(int(compiled.row_ids[r_idx]), 0, 0, direction)] = 0
        for j, (port, proto_n) in enumerate(state.ep_slots[e]):
            allow = allow_sn[seg] & live
            redirect = red_sn[seg] & live
            cj = ci + 1 + j
            state.allow_nc[:, cj] = allow
            state.red_nc[:, cj] = redirect
            if state.rule_nc is not None:
                state.rule_nc[:, cj] = rule_sn[seg]
            touched_cols.append(cj)
            seg += 1
            for r_idx in np.nonzero(allow & (~l3_allow | redirect))[0]:
                key = PolicyKey(int(compiled.row_ids[r_idx]), port, proto_n, direction)
                entries[key] = int(redirect[r_idx])
        # in-place: fastpath caches hold references to this dict
        snap.entries.clear()
        snap.entries.update(entries)

    # Device scatter: only the packed words the touched columns live
    # in. Allow word w holds columns 32w..32w+31; the redirect copy of
    # word w sits c_pad/32 words later (id_bits = allow ‖ redirect).
    c_pad = state.allow_nc.shape[1]
    word_idx: List[int] = []
    word_vals: List[np.ndarray] = []
    for w in sorted({c >> 5 for c in touched_cols}):
        cols = slice(w * 32, min((w + 1) * 32, c_pad))
        word_idx.append(w)
        word_vals.append(_pack_col_word(state.allow_nc[:, cols]))
        word_idx.append(c_pad // 32 + w)
        word_vals.append(_pack_col_word(state.red_nc[:, cols]))
    idx, vals = _pad_cols_pow2(
        np.asarray(word_idx, np.int32), np.stack(word_vals, axis=1)
    )
    state.tables = state.tables.replace(
        id_bits=patch_bitmap_cols(
            state.tables.id_bits, jnp.asarray(idx), jnp.asarray(vals)
        )
    )
    if placed is not None:
        placed.tables = placed.tables.replace(
            id_bits=patch_bitmap_cols(
                placed.tables.id_bits, jnp.asarray(idx), jnp.asarray(vals)
            )
        )
    if state.rule_nc is not None and state.rule_tab is not None:
        ridx, rvals = _pad_cols_pow2(
            np.asarray(touched_cols, np.int32),
            state.rule_nc[:, touched_cols],
        )
        state.rule_tab = patch_bitmap_cols(
            state.rule_tab, jnp.asarray(ridx), jnp.asarray(rvals)
        )
        if placed is not None and placed.rule_tab is not None:
            placed.rule_tab = patch_bitmap_cols(
                placed.rule_tab, jnp.asarray(ridx), jnp.asarray(rvals)
            )
    return True


# -- sparse sel_match patching (policyd-sparse) -----------------------------
#
# The engine keeps the authoritative device sel_match; the pipeline keeps
# PLACED copies (replicated or P("ident")-sharded under MeshSharding2D).
# These helpers re-apply the engine's delta-log events to a placed copy
# as O(k) scatters instead of re-placing the full [N, S/32] matrix: a
# jit ``.at[].set`` on a sharded operand keeps the operand's sharding
# (GSPMD propagates it through the scatter), so the patch is O(delta)
# per device and the placed jit caches survive.


@jax.jit
def _scatter_sel_rows(
    sel_match: jnp.ndarray,
    idx: jnp.ndarray,  # [k] int32
    rows: jnp.ndarray,  # [k, S/32] uint32
) -> jnp.ndarray:
    # No donation: concurrent verdict readers may hold the old buffer.
    return sel_match.at[idx].set(rows)


@jax.jit
def _scatter_sel_cols(
    sel_match: jnp.ndarray,
    rows: jnp.ndarray,  # [k] int32
    cols: jnp.ndarray,  # [w] int32
    vals: jnp.ndarray,  # [k, w] uint32
) -> jnp.ndarray:
    return sel_match.at[rows[:, None], cols[None, :]].set(vals)


def _pow2_rows_vals(
    rows: np.ndarray, vals: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a (row indices, per-row values) scatter to a power-of-two
    bucket (min 8) by repeating the LAST entry — duplicate indices with
    identical values keep the scatter deterministic, and the bucket
    bounds jit recompiles to O(log k) programs per value width."""
    k = rows.shape[0]
    bucket = 8
    while bucket < k:
        bucket <<= 1
    if bucket == k:
        return rows, vals
    return (
        np.concatenate([rows, np.repeat(rows[-1:], bucket - k)]),
        np.concatenate([vals, np.repeat(vals[-1:], bucket - k, axis=0)]),
    )


def patch_selector_rows(
    sel_match: jnp.ndarray,
    ident_rows: Sequence[int],
    row_words: np.ndarray,  # [k, S/32] uint32 final-state packed rows
) -> jnp.ndarray:
    """Scatter whole packed sel_match rows (identity-churn deltas:
    engine ``"rows"`` events) into a device/placed copy. O(k · S/32)
    payload; returns the patched array (same placement as the input)."""
    rows = np.asarray(ident_rows, np.int32)
    if rows.size == 0:
        return sel_match
    vals = np.ascontiguousarray(row_words, dtype=np.uint32)
    rows, vals = _pow2_rows_vals(rows, vals)
    return _scatter_sel_rows(sel_match, jnp.asarray(rows), jnp.asarray(vals))


def patch_selector_cols(
    sel_match: jnp.ndarray,
    ident_rows: Sequence[int],
    word_cols: Sequence[int],
    vals: np.ndarray,  # [k, w] uint32 final-state packed words
) -> jnp.ndarray:
    """Scatter a CSR column-delta (selector-append deltas: engine
    ``"cols"`` events, built by compiler.selectors.selector_col_delta)
    into a device/placed sel_match copy: k touched identity rows × the
    appended selectors' word window. O(k · w) payload — for a selector
    matching k identities at N=1M this moves kilobytes where the dense
    re-place moved the full [N, S/32] matrix."""
    rows = np.asarray(ident_rows, np.int32)
    cols = np.asarray(word_cols, np.int32)
    if rows.size == 0 or cols.size == 0:
        return sel_match
    v = np.ascontiguousarray(vals, dtype=np.uint32)
    rows, v = _pow2_rows_vals(rows, v)
    return _scatter_sel_cols(
        sel_match, jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(v)
    )
