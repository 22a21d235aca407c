"""Batched flow pipeline: conntrack → prefilter → identity → verdict.

Mirrors the per-packet path of the reference, hoisted to batches:

    bpf_lxc.c lb4_local (:444-455)    → device VIP→backend translate
                                        (lb/device.py, egress only)
    bpf/lib/conntrack.h ct_lookup     → vectorized host CT pre-pass
                                        (established/reply bypass)
    bpf_xdp.c check_filters (:158)    → deny-trie LPM on peer address
    bpf_netdev.c secctx from ipcache  → identity-trie LPM (world if miss)
    bpf_lxc.c tail_ipv4_policy (:931) → ingress policymap lookup
    bpf_lxc.c policy_can_egress4(:505)→ egress policymap lookup

plus per-endpoint forwarded/dropped counters (the metricsmap role,
pkg/maps/metricsmap). Both traffic directions are materialized
(ingress AND egress policymaps — bpf_lxc.c enforces both), IPv4 and
IPv6 tries are live (4- vs 16-level LPM walks), and the conntrack
pre-pass means established-heavy batches dispatch only their CT-miss
tail to the device — the batch-level analog of the kernel's
one-hash-probe fast path for established flows.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import chex
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .admission import (
    AdmissionController,
    N_SHED_CLASSES,
    REASON_SHED_DEADLINE,
    REASON_SHED_PREFILTER,
    Watchdog,
    compile_shed_table,
    flow_class,
)
from .placement import EMPTY_PLAN, MeshPlan, PlacementConfig, resolve_plan
from .. import faults as _faults
from .. import metrics as _metrics
from ..engine import PolicyEngine
from ..identity.model import ID_WORLD
from ..observe.flows import SAMPLE_CAP as _FLOW_SAMPLE_CAP, FlowRecord, FlowRing
from ..observe.tracer import NOOP_BATCH as _NOOP_BATCH, Tracer
from ..ipcache.ipcache import IPCache
from ..ipcache.prefilter import PreFilter
from ..ops.lookup import (
    PolicymapTables,
    lookup_batch,
    replicate_tables,
    shard_tables_ident,
)
from ..ops.lpm import (
    DENY_BIT,
    MERGED_VALUE_MASK,
    PatchableElidedTrie,
    build_trie_elided,
    build_wide_trie,
    ipv4_to_bytes,
    lpm_lookup,
    lpm_lookup_wide,
    make_patchable_wide,
    merge_flat_tries,
    merge_trie_entries,
    place_table,
)
from ..compiler.selectors import selector_word_window
from ..ops.materialize import (
    EndpointPolicySnapshot,
    MaterializedState,
    PlacedTables,
    TRAFFIC_EGRESS,
    TRAFFIC_INGRESS,
    materialize_endpoints_state,
    patch_endpoints_state,
    patch_identity_rows,
    patch_selector_cols,
    patch_selector_rows,
)
from ..lb.device import flow_hash32, lb_translate
from ..utils.backoff import Backoff
from .conntrack import CT_NEW, FlowConntrack, pack_keys
from .tuner import DepthTuner

FORWARD = 1
DROP_POLICY = 2
DROP_PREFILTER = 3
DROP_NO_SERVICE = 4  # frontend matched but zero backends (lb4_local)
# policyd-failsafe: the pipeline could not verdict this flow (device
# fault exhausted its bounded retries) and FailOpen is off — the
# fail-closed deny. Maps to monitor drop reason 155 (STABLE taxonomy).
DROP_DEGRADED = 5

# verdict code → metrics outcome label (metricsmap REASON strings)
_OUTCOME_NAMES = (
    (FORWARD, "forwarded"),
    (DROP_POLICY, "dropped_policy"),
    (DROP_PREFILTER, "dropped_prefilter"),
    (DROP_NO_SERVICE, "dropped_no_service"),
    (DROP_DEGRADED, "dropped_degraded"),
)

# degradation-ladder levels (policyd-failsafe): index = ladder level.
# Level 0 is the full device complement (sharded across the verdict
# mesh when VerdictSharding is on), 1 re-forms the mesh down to a
# single healthy device, 2 verdicts on host numpy.
_MODE_NAMES = ("sharded", "single-device", "host")


@chex.dataclass(frozen=True)
class DatapathTables:
    """Device state for one address family + one traffic direction.
    Trie arrays are shared between the two directions' instances.
    ``*_common`` carry each trie's elided shared prefix bytes ([K]
    int32, [0] = no elision) — compared vectorized, not walked.
    ``merged_*`` carry the fused deny+identity trie (one walk, both
    answers — ops/lpm.py merge_trie_entries); presence is signalled by
    the caller's static ``fused`` flag, the placeholders only keep the
    pytree shape stable."""

    pf_child: jnp.ndarray
    pf_info: jnp.ndarray
    pf_common: jnp.ndarray
    ip_child: jnp.ndarray
    ip_info: jnp.ndarray
    ip_common: jnp.ndarray
    merged_child: jnp.ndarray
    merged_info: jnp.ndarray
    merged_common: jnp.ndarray
    world_row: jnp.ndarray  # [] int32
    policymap: PolicymapTables


@chex.dataclass(frozen=True)
class WideDatapathTables:
    """IPv4 device state using the dense-16-bit-first-stride tries
    (ops/lpm.py WideTrieBuilder) — 3 gathers per LPM instead of 4,
    measured ~1.8× on the identity-derivation stage.

    ``merged_*`` carry the FUSED deny+identity flat trie when both
    sides use the dense layout (ops/lpm.py merge_flat_tries): one
    2-gather walk yields the identity row AND the prefilter verdict,
    halving the chain's gather count. A [1,1] merged_sub_info marks
    "no merged table" (shape is static at trace time, so the jit
    routes without a flag)."""

    pf_root_info: jnp.ndarray  # [65536] int32
    pf_root_child: jnp.ndarray
    pf_sub_child: jnp.ndarray  # [M, 256] int32
    pf_sub_info: jnp.ndarray
    ip_root_info: jnp.ndarray
    ip_root_child: jnp.ndarray
    ip_sub_child: jnp.ndarray
    ip_sub_info: jnp.ndarray
    merged_root_info: jnp.ndarray  # [65536] int32 (packed) or [1]
    merged_root_child: jnp.ndarray
    merged_sub_child: jnp.ndarray
    merged_sub_info: jnp.ndarray  # [M, 65536] or [1, 1]
    world_row: jnp.ndarray  # [] int32
    policymap: PolicymapTables


def _elided_lpm(
    child: jnp.ndarray,
    info: jnp.ndarray,
    common: jnp.ndarray,
    addr_bytes: jnp.ndarray,
    levels: int,
) -> jnp.ndarray:
    """LPM walk with the trie's shared prefix compared (one vectorized
    equality, zero gathers) instead of walked — K is static from the
    common array's shape, so each table set compiles its own depth."""
    k = common.shape[0]
    hit = lpm_lookup(child, info, addr_bytes[:, k:], levels=levels - k)
    if k:
        ok = jnp.all(addr_bytes[:, :k] == common[None, :], axis=1)
        hit = jnp.where(ok, hit, 0)
    return hit


def _v4_lpm_stage(t, peer_u32, prefilter: bool):
    """→ (denied_pf [B] bool, identity hit [B] int32 value+1).

    Routes on the (static) merged-table shape: with the fused
    deny+identity flat trie present and the prefilter stage active, ONE
    walk answers both questions (bpf_xdp.c check_filters + the ipcache
    secctx derivation in a single pass); otherwise the two classic
    walks run (and the deny walk only when the stage is active).

    Device stages carry the named scopes ``lpm_v4`` and, for a separate
    deny walk, ``prefilter`` (the fused walk answers both under
    ``lpm_v4``): names in the profiler trace, not in the program."""
    fused = t.merged_sub_info.shape[-1] == 65536
    with jax.named_scope("lpm_v4"):
        if prefilter and fused:
            packed = lpm_lookup_wide(
                t.merged_root_info, t.merged_root_child, t.merged_sub_child,
                t.merged_sub_info, peer_u32,
            )
            denied_pf = (packed & jnp.int32(DENY_BIT)) != 0
            hit = packed & jnp.int32(MERGED_VALUE_MASK)
            return denied_pf, hit
        if prefilter:
            with jax.named_scope("prefilter"):
                denied_pf = lpm_lookup_wide(
                    t.pf_root_info, t.pf_root_child, t.pf_sub_child,
                    t.pf_sub_info, peer_u32,
                ) > 0
        else:
            denied_pf = jnp.zeros(peer_u32.shape[0], jnp.bool_)
        hit = lpm_lookup_wide(
            t.ip_root_info, t.ip_root_child, t.ip_sub_child, t.ip_sub_info,
            peer_u32,
        )
    return denied_pf, hit


def _verdict_tail(
    policymap: PolicymapTables,
    denied_pf: jnp.ndarray,
    peer_row: jnp.ndarray,
    ep_idx: jnp.ndarray,
    dport: jnp.ndarray,
    proto: jnp.ndarray,
    ep_count: int,
    block: int,
    attrib: bool = False,
    rule_tab: Optional[jnp.ndarray] = None,
    n_rules: int = 0,
    ident_gather: bool = False,
):
    """Shared post-LPM tail (policy lookup, prefilter override,
    counter matmul) — traced inside both jitted entry points so the
    v4/v6 paths cannot diverge.

    ``attrib=True`` (static on the jitted callers; the off path keeps
    its exact original program) appends per-flow attribution: the
    deciding-rule index gathered from ``rule_tab`` (-1 = none; masked
    for prefilter drops, which never reached the policymap), whether
    any L4 column covered the flow (the no-L4 vs no-L3 drop
    discriminator), and the on-device [R] rule-hit segment-sum —
    pulled d2h only in the completion half, like the counters.

    Named scopes ``policymap`` (lookup and prefilter override) and
    ``counters`` (the matmul and the rule-hit sums)."""
    with jax.named_scope("policymap"):
        if not attrib:
            dec, red = lookup_batch(
                policymap, ep_idx, peer_row, dport, proto, block=block,
                ident_gather=ident_gather,
            )
        else:
            dec, red, rule, l4x = lookup_batch(
                policymap, ep_idx, peer_row, dport, proto, block=block,
                attrib=True, rule_tab=rule_tab, ident_gather=ident_gather,
            )
        verdict = jnp.where(denied_pf, jnp.int8(DROP_PREFILTER), dec)
        redirect = red & ~denied_pf

    with jax.named_scope("counters"):
        # counters via one-hot matmul [B, EP]ᵀ @ [B, 3]
        ep_oh = (
            ep_idx[:, None] == jnp.arange(ep_count)[None, :]
        ).astype(jnp.int8)
        cls = jnp.stack(
            [verdict == FORWARD, verdict == DROP_POLICY,
             verdict == DROP_PREFILTER],
            axis=1,
        ).astype(jnp.int8)
        counters = jax.lax.dot_general(
            ep_oh, cls, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        if not attrib:
            return verdict, redirect, counters
        rule = jnp.where(denied_pf, jnp.int32(-1), rule)
        idx = jnp.clip(rule, 0, max(n_rules - 1, 0))
        hits = (
            jnp.zeros((max(n_rules, 1),), jnp.int32)
            .at[idx]
            .add((rule >= 0).astype(jnp.int32))
        )
    return verdict, redirect, counters, rule, l4x, hits


def _v6_lpm_stage(t, peer_bytes, levels: int, prefilter: bool, fused: bool):
    """→ (denied_pf, hit) — the v6 twin of _v4_lpm_stage: with the
    fused trie present and the deny stage active, ONE elided stride-8
    walk answers both questions; ``fused`` is a static flag because the
    stride-8 shapes can't disambiguate presence the way the flat
    layout's 65536 width can. Named scopes as in _v4_lpm_stage:
    ``lpm_v6`` and ``prefilter``."""
    with jax.named_scope("lpm_v6"):
        if prefilter and fused:
            raw = _elided_lpm(
                t.merged_child, t.merged_info, t.merged_common, peer_bytes,
                levels,
            )
            packed = jnp.where(raw > 0, raw - 1, 0)
            denied_pf = (packed & jnp.int32(DENY_BIT)) != 0
            hit = packed & jnp.int32(MERGED_VALUE_MASK)
            return denied_pf, hit
        if prefilter:
            with jax.named_scope("prefilter"):
                denied_pf = _elided_lpm(
                    t.pf_child, t.pf_info, t.pf_common, peer_bytes, levels
                ) > 0
        else:
            denied_pf = jnp.zeros(peer_bytes.shape[0], jnp.bool_)
        hit = _elided_lpm(
            t.ip_child, t.ip_info, t.ip_common, peer_bytes, levels
        )
    return denied_pf, hit


@functools.partial(
    jax.jit,
    static_argnames=(
        "ep_count", "block", "levels", "prefilter", "fused", "attrib",
        "n_rules", "ident_gather",
    ),
)
def process_flows(
    t: DatapathTables,
    peer_bytes: jnp.ndarray,  # [B, levels] int32 address bytes
    ep_idx: jnp.ndarray,  # [B] int32
    dport: jnp.ndarray,  # [B] int32
    proto: jnp.ndarray,  # [B] int32
    ep_count: int = 1,
    block: int = 16384,  # measured-fastest lookup block (ops/lookup.py)
    levels: int = 4,
    prefilter: bool = True,
    fused: bool = False,
    row_override: Optional[jnp.ndarray] = None,  # [B] int32, -1 = LPM
    attrib: bool = False,
    rule_tab: Optional[jnp.ndarray] = None,  # [N, C_pad] int32
    n_rules: int = 0,
    ident_gather: bool = False,
):
    """→ (verdict[B] int8, redirect[B] bool, counters [EP, 3] int32);
    with ``attrib=True`` additionally (rule[B] int32, l4_covered[B]
    bool, hits[R] int32) — see _verdict_tail.

    ``peer_bytes`` is the remote address of each flow: the SOURCE for
    ingress traffic (bpf_netdev.c:376 resolves src identity), the
    DESTINATION for egress (bpf_lxc.c:497 resolves dst identity).
    ``prefilter`` guards the XDP deny-trie stage — the reference runs
    it only on traffic entering the node (bpf_xdp.c), not on egress.
    ``row_override`` carries the overlay path's identity-from-tunnel-
    key (bpf_overlay.c: decap reads the security identity from the
    encap key and trusts it over an ipcache walk): flows with a
    non-negative row skip BOTH the identity LPM and the prefilter (the
    XDP prefilter inspects outer headers, which decap already shed).

    counters[e] = (forwarded, dropped_policy, dropped_prefilter) — the
    metricsmap accumulation, computed with a one-hot matmul so the
    scatter stays on the MXU.
    """
    denied_pf, hit = _v6_lpm_stage(t, peer_bytes, levels, prefilter, fused)
    peer_row = jnp.where(hit > 0, hit - 1, t.world_row)
    if row_override is not None:
        trusted = row_override >= 0
        peer_row = jnp.where(trusted, row_override, peer_row)
        denied_pf = denied_pf & ~trusted
    return _verdict_tail(
        t.policymap, denied_pf, peer_row, ep_idx, dport, proto, ep_count,
        block, attrib=attrib, rule_tab=rule_tab if attrib else None,
        n_rules=n_rules, ident_gather=ident_gather,
    )


# Backwards-compatible alias for the IPv4 path.
process_ipv4 = process_flows


@functools.partial(
    jax.jit, static_argnames=("ep_count", "block", "prefilter", "attrib",
                              "n_rules", "ident_gather")
)
def process_flows_wide(
    t: WideDatapathTables,
    peer_u32: jnp.ndarray,  # [B] uint32 host-order peer addresses
    ep_idx: jnp.ndarray,
    dport: jnp.ndarray,
    proto: jnp.ndarray,
    ep_count: int = 1,
    block: int = 16384,
    prefilter: bool = True,
    row_override: Optional[jnp.ndarray] = None,  # [B] int32, -1 = LPM
    attrib: bool = False,
    rule_tab: Optional[jnp.ndarray] = None,  # [N, C_pad] int32
    n_rules: int = 0,
    ident_gather: bool = False,
):
    """IPv4 fast path over the wide tries — semantics identical to
    process_flows(levels=4), including the overlay row_override and
    the attrib variant."""
    denied_pf, hit = _v4_lpm_stage(t, peer_u32, prefilter)
    peer_row = jnp.where(hit > 0, hit - 1, t.world_row)
    if row_override is not None:
        trusted = row_override >= 0
        peer_row = jnp.where(trusted, row_override, peer_row)
        denied_pf = denied_pf & ~trusted
    return _verdict_tail(
        t.policymap, denied_pf, peer_row, ep_idx, dport, proto, ep_count,
        block, attrib=attrib, rule_tab=rule_tab if attrib else None,
        n_rules=n_rules, ident_gather=ident_gather,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "ep_count", "block", "prefilter", "levels", "family", "fused"
    ),
    donate_argnums=(1,),
)
def process_flows_ct(
    t,  # WideDatapathTables (family 4) | DatapathTables (family 6)
    ct,  # DeviceCTState — DONATED: updated in place on device
    peer: jnp.ndarray,  # family 4: [B] uint32; family 6: [B, 16] int32
    ep_idx: jnp.ndarray,
    dport: jnp.ndarray,
    proto: jnp.ndarray,
    sport: jnp.ndarray,
    direction: jnp.ndarray,  # [] int32 0 ingress / 1 egress
    now: jnp.ndarray,  # [] int32 seconds (monotonic)
    valid: jnp.ndarray,  # [B] bool — False for shape-bucket padding
    ep_count: int = 1,
    block: int = 16384,
    prefilter: bool = True,
    levels: int = 4,
    family: int = 4,
    fused: bool = False,  # v6 merged-trie presence (v4 routes by shape)
):
    """The FUSED datapath step with device-resident conntrack: CT
    probe (fwd + reply) → deny LPM → identity LPM → policymap lookup →
    CT insert, ONE device program per batch (datapath/device_ct.py).
    Established flows take FORWARD regardless of the policy stages —
    the bpf/lib/conntrack.h bypass, computed branch-free (the verdict
    stages run for every lane anyway; SIMD lanes are not saved by
    host-side subsetting).

    → (verdict [B] int8, redirect [B] bool, counters [EP, 3] int32,
    new_ct_state)."""
    from .device_ct import _ct_step_impl, pack_kc_words

    if family == 4:
        denied_pf, hit = _v4_lpm_stage(t, peer, prefilter)
        z = jnp.zeros_like(peer)
        ka_w, kb_w = (z, z), (z, peer)
    else:
        denied_pf, hit = _v6_lpm_stage(t, peer, levels, prefilter, fused)
        b32 = peer.astype(jnp.uint32)

        def word(i):
            return (
                (b32[:, i] << 24) | (b32[:, i + 1] << 16)
                | (b32[:, i + 2] << 8) | b32[:, i + 3]
            )

        ka_w, kb_w = (word(0), word(4)), (word(8), word(12))
    peer_row = jnp.where(hit > 0, hit - 1, t.world_row)
    dec, red = lookup_batch(
        t.policymap, ep_idx, peer_row, dport, proto, block=block
    )
    policy_fwd = dec == jnp.int8(FORWARD)
    # padded lanes must never create CT state (their zero-keys would
    # otherwise become real, long-lived entries)
    allow_new = policy_fwd & ~denied_pf & ~red & valid

    kc_w = pack_kc_words(
        ep_idx, sport, dport, proto, jnp.broadcast_to(direction, ep_idx.shape)
    )
    new_ct, established = _ct_step_impl(
        ct, ka_w, kb_w, kc_w, proto, now, allow_new
    )

    verdict = jnp.where(
        established,
        jnp.int8(FORWARD),
        jnp.where(denied_pf, jnp.int8(DROP_PREFILTER), dec),
    )
    redirect = red & ~denied_pf & ~established

    ep_oh = (ep_idx[:, None] == jnp.arange(ep_count)[None, :]).astype(jnp.int8)
    cls = (
        jnp.stack(
            [
                verdict == FORWARD,
                verdict == DROP_POLICY,
                verdict == DROP_PREFILTER,
            ],
            axis=1,
        )
        & valid[:, None]
    ).astype(jnp.int8)
    counters = jax.lax.dot_general(
        ep_oh, cls, (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    return verdict, redirect, counters, new_ct


def _bucket(n: int, floor: int = 1024) -> int:
    """Next power-of-two ≥ n (min ``floor``) — shape buckets so the
    CT-miss tail reuses compiled XLA programs."""
    b = floor
    while b < n:
        b <<= 1
    return b


def _pack_v4_u32(peer_bytes: np.ndarray) -> np.ndarray:
    """[B, 4] address bytes → [B] uint32 host-order (the wide-trie
    query word). One definition for every dispatch path."""
    b = peer_bytes.astype(np.uint32)
    return (b[:, 0] << 24) | (b[:, 1] << 16) | (b[:, 2] << 8) | b[:, 3]


# -- policyd-overload: prefilter shed walk ---------------------------------
# The coarse admission prefilter (PAPER.md layer 1's XDP prefilter
# role, drop reason 144): ONE identity LPM walk + ONE gather from the
# [N, 9] drop table compiled by admission.compile_shed_table. Runs only
# from the admission gate when the queue is over budget — it is not on
# the normal verdict path, so the Prefilter OFF program set is exactly
# the pre-option one. Deliberately skips the deny-trie stage and the
# policymap: cheapness is the point (shed rate must be a multiple of
# full-pipeline rate on deny-heavy mixes), and the table alone is
# deny-for-sure so skipping stages can only shed less, never wrongly.


@jax.jit
def shed_flows_wide(
    t: "WideDatapathTables",
    shed_tab: jnp.ndarray,  # [N, 9] uint8 (admission.compile_shed_table)
    peer_u32: jnp.ndarray,  # [B] uint32 host-order peer addresses
    dport: jnp.ndarray,  # [B] int32
    proto: jnp.ndarray,  # [B] int32
) -> jnp.ndarray:
    """→ shed[B] bool: every flagged flow is deny-for-sure under the
    current realized policy (IPv4)."""
    _, hit = _v4_lpm_stage(t, peer_u32, False)
    row = jnp.where(hit > 0, hit - 1, t.world_row)
    cls = flow_class(dport, proto).astype(jnp.int32)
    return jnp.take(shed_tab.reshape(-1), row * N_SHED_CLASSES + cls) != 0


@functools.partial(jax.jit, static_argnames=("levels",))
def shed_flows(
    t: "DatapathTables",
    shed_tab: jnp.ndarray,
    peer_bytes: jnp.ndarray,  # [B, levels] int32 address bytes
    dport: jnp.ndarray,
    proto: jnp.ndarray,
    levels: int = 16,
) -> jnp.ndarray:
    """IPv6 twin of shed_flows_wide (stride-8 elided identity walk)."""
    _, hit = _v6_lpm_stage(t, peer_bytes, levels, False, False)
    row = jnp.where(hit > 0, hit - 1, t.world_row)
    cls = flow_class(dport, proto).astype(jnp.int32)
    return jnp.take(shed_tab.reshape(-1), row * N_SHED_CLASSES + cls) != 0


def _pad_flows(pad: int, peer_bytes, *arrays, row_override=None):
    """Zero-pad a flow batch's arrays to a shape bucket (row_override
    pads with -1: padded lanes must derive-by-LPM, never trust)."""
    if pad:
        peer_bytes = np.pad(peer_bytes, ((0, pad), (0, 0)))
        arrays = tuple(np.pad(a, (0, pad)) for a in arrays)
        if row_override is not None:
            row_override = np.pad(row_override, (0, pad), constant_values=-1)
    return (peer_bytes, *arrays, row_override)


def _bucket_multiple(n: int, ndev: int, floor: int = 1024) -> int:
    """_bucket(), then rounded up to a multiple of the mesh device
    count so a flow-sharded batch splits evenly (P("flows") shards
    dim 0; an uneven split would compile per-remainder programs)."""
    b = _bucket(n, floor)
    return b + ((-b) % ndev)


# policyd-autotune bucket ladder: the ONLY padded shapes the bucketed
# (CT-miss tail) dispatch path ever compiles. Fixed — not derived from
# traffic — so the jit shape-bucket count is bounded by construction at
# len(BUCKET_LADDER) per static-arg combination, and a rung warmed by
# any batch stays reusable by every later batch. STABLE CONTRACT
# (ROADMAP): the rungs live in cilium_tpu/contracts.py (single source
# of truth, machine-checked by rule API001) because changing them
# invalidates every warm compiled program and the staging-pool sizing.
from ..contracts import BUCKET_LADDER


def _ladder_rungs(ndev: int, ladder: Tuple[int, ...] = BUCKET_LADDER):
    """Ladder rungs rounded up to mesh-device multiples (same reason as
    _bucket_multiple: P("flows") must split each chunk evenly)."""
    if ndev <= 1:
        return ladder
    return tuple(r + ((-r) % ndev) for r in ladder)


@functools.lru_cache(maxsize=512)
def _tail_cover(m: int, rungs: Tuple[int, ...]) -> Tuple[int, int, Tuple[int, ...]]:
    """Minimum-padded-lane rung cover of an m-flow tail (m ≤ top rung
    after full-top-rung stripping): returns (lanes, chunks, plan) with
    the plan sorted largest-first so only the final chunk carries pad.
    Lanes are minimized first, chunk count second (each chunk is one
    h2d + enqueue), and on full ties the larger leading rung wins —
    e.g. an 1100-flow tail covers with one 2048 chunk, not 1024+1024,
    and a 3000-flow tail with 2048+1024 (3072 lanes) instead of one
    4096 chunk (the single-warm-bucket scheme's ~37% extra pad).
    Exact, not greedy: ndev-rounded rungs are not mutual multiples, so
    greedy largest-fit can strand a worse tail. Depth is bounded by
    top/floor (≤ 8 recursions)."""
    best = None
    for r in reversed(rungs):  # largest first → wins full ties
        if r >= m:
            cand = (r, 1, (r,))
        else:
            lanes, chunks, plan = _tail_cover(m - r, rungs)
            cand = (
                r + lanes,
                chunks + 1,
                tuple(sorted((r,) + plan, reverse=True)),
            )
        if best is None or (cand[0], cand[1]) < (best[0], best[1]):
            best = cand
    return best


class PendingBatch:
    """Handle for one batch accepted by ``DatapathPipeline.submit()``.
    Batches complete strictly FIFO; ``result()`` blocks until this
    batch's host pull + accounting have run (completing any older
    in-flight batches first, preserving event/conntrack order)."""

    __slots__ = ("_pipe", "_event", "_value", "_exc")

    def __init__(self, pipe: "DatapathPipeline") -> None:
        self._pipe = pipe
        self._event = threading.Event()
        self._value = None
        self._exc: Optional[BaseException] = None

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def result(self):
        if not self._event.is_set():
            self._pipe._complete_until(self)
            # Timed loop, not a bare wait: if the completing thread
            # wedges on a stuck device pull, the watchdog resolves this
            # batch degraded and sets the event — but a daemon run
            # without a watchdog must still never park a caller
            # unwakeably on a lost completion (policyd-overload
            # ROBUST002 discipline: no untimed blocking waits on the
            # hot path).
            while not self._event.wait(0.5):
                pass
        if self._exc is not None:
            raise self._exc
        return self._value


class _InFlight:
    """One submitted batch: its handle, the completion closure (host
    pull + counters + CT create + events), and the trace that must end
    when the batch COMPLETES. ``finish=None`` marks a batch that ran
    synchronously (the donated-state device-CT path)."""

    __slots__ = (
        "pending", "finish", "bt", "enq_ns", "occ", "b", "rev", "t0",
        "abandoned",
    )

    def __init__(
        self, pending: PendingBatch, finish, bt,
        b: int = 0, rev: bool = False,
    ) -> None:
        self.pending = pending
        self.finish = finish
        self.bt = bt
        # depth-tuner observations (populated only while DispatchAutoTune
        # is on): enqueue-half wall ns, queue occupancy at admission.
        # enq_ns == 0 marks "not observed".
        self.enq_ns = 0
        self.occ = 0
        # batch size + rev-NAT flag: always populated — the failsafe
        # quarantine path synthesizes a shape-correct degraded result
        # from these when the finish closure is unrecoverable
        self.b = b
        self.rev = rev
        # policyd-overload: submit time (monotonic; 0 = not tracked —
        # set only while admission control or the watchdog is on) and
        # the watchdog's abandonment mark. Once abandoned, the batch is
        # already resolved degraded — a late-returning finish must not
        # overwrite the published result.
        self.t0 = 0.0
        self.abandoned = False


class _GatedPending(PendingBatch):
    """Admission-gate handle for a PARTIALLY shed batch: the deny-for-
    sure flows were resolved at the gate (reason 144), the kept flows
    ride an inner PendingBatch through the unchanged submit path.
    result() merges the two back into the caller's original [B] order —
    from the outside the batch is indistinguishable from an ungated
    one, just with some lanes pre-verdicted."""

    __slots__ = ("_inner", "_keep", "_shed_v", "_b", "_rev", "_merge")

    def __init__(
        self,
        pipe: "DatapathPipeline",
        inner: PendingBatch,
        keep_idx: np.ndarray,  # [K] indices of kept flows in the batch
        shed_verdict: np.ndarray,  # [B] int8, shed lanes pre-filled
        b: int,
        rev: bool,
    ) -> None:
        super().__init__(pipe)
        self._inner = inner
        self._keep = keep_idx
        self._shed_v = shed_verdict
        self._b = b
        self._rev = rev
        self._merge = threading.Lock()

    @property
    def done(self) -> bool:
        return self._event.is_set() or self._inner.done

    def result(self):
        with self._merge:
            if not self._event.is_set():
                try:
                    out = self._inner.result()
                except BaseException as e:
                    self._exc = e
                    self._event.set()
                    raise
                v = self._shed_v
                red = np.zeros(self._b, bool)
                v[self._keep] = out[0]
                red[self._keep] = out[1]
                if self._rev:
                    rev = np.zeros(self._b, np.uint16)
                    rev[self._keep] = out[2]
                    self._value = (v, red, rev)
                else:
                    self._value = (v, red)
                self._event.set()
        if self._exc is not None:
            raise self._exc
        return self._value


class _Enqueued:
    """Un-pulled device results of one dispatch: per-chunk (verdict,
    redirect, counters) device arrays plus the spans that produced
    them — (…, rule, l4_covered, hits) 6-tuples when ``attrib``.
    ``exact`` marks device counters (and rule-hit sums) usable as-is
    (no padded lanes polluted them)."""

    __slots__ = (
        "chunks", "spans", "b", "exact", "ndev", "attrib", "staging", "host",
        "psample",
    )

    def __init__(
        self, chunks, spans, b, exact, ndev, attrib=False, staging=(),
        host=None, psample=None,
    ) -> None:
        self.chunks = chunks
        self.spans = spans
        self.b = b
        self.exact = exact
        self.ndev = ndev
        self.attrib = attrib
        # staging tuples pinned under this dispatch's device inputs;
        # released back to the pipeline's pool at the host pull
        self.staging = staging
        # ladder level 2 (host fallback): (verdict, redirect) computed
        # synchronously on host numpy — no device arrays to pull
        self.host = host
        # policyd-prof: the live _DispatchSample when this dispatch was
        # the profiler's Nth batch (None otherwise) — the completion
        # half times the d2h pull into it and retires it
        self.psample = psample


class DatapathPipeline:
    """Host orchestrator: owns the device snapshot of prefilter +
    ipcache + materialized policymaps for a set of local endpoints, and
    re-materializes when any input version moves (the regeneration
    trigger role of pkg/endpoint/policy.go:812)."""

    def __init__(
        self,
        engine: PolicyEngine,
        ipcache: IPCache,
        prefilter: Optional[PreFilter] = None,
        conntrack: Optional[FlowConntrack] = None,
        lb=None,  # Optional[lb.service.ServiceManager]
        monitor=None,  # Optional[monitor.hub.MonitorHub]
        device_ct_bits: Optional[int] = None,
        tracer: Optional[Tracer] = None,
        pipeline_depth: int = 2,
        sharding: bool = False,
        flow_ring: Optional[FlowRing] = None,
        pipeline_max_depth: int = 4,
        autotune: bool = False,
        epoch_swap: bool = False,
        placement: Optional[PlacementConfig] = None,
        mesh_2d: bool = False,
        admission: bool = False,
        prefilter_shed: bool = False,
        sparse_deltas: bool = False,
        deadline_ms: float = 0.0,
        stall_ms: float = 0.0,
        profiling: bool = False,
        profile_sample_every: int = 64,
    ) -> None:
        self.engine = engine
        self.ipcache = ipcache
        self.prefilter = prefilter or PreFilter()
        self.conntrack = conntrack
        # Device-resident conntrack (datapath/device_ct.py): the CT
        # table lives in HBM and the whole batch runs as ONE fused
        # device program. Takes precedence over the host CT for flows
        # it can serve; falls back to the host path when an LB table
        # is active for the batch's family+direction (VIP translation
        # precedes CT and is host-fused today).
        self._device_ct_bits = device_ct_bits
        self._device_ct = None  # lazily-created DeviceCTState
        if device_ct_bits is not None and self.conntrack is None:
            # Batches the device CT cannot serve (active LB tables,
            # overlay tunnel identities) fall back to the host CT
            # domain; without one they would silently lose conntrack
            self.conntrack = FlowConntrack(capacity_bits=max(10, device_ct_bits))
        self.lb = lb
        self.monitor = monitor
        # policyd-trace span tracer (observe/): off by default — the
        # verdict path pays one `tracer.active` attribute read per
        # batch (the hub's `active` pattern) until enabled
        self.tracer = tracer if tracer is not None else Tracer()
        # policyd-flows ring (observe/flows.py): sampled FlowRecords
        # from the completion half while FlowAttribution is on. Same
        # cost model as the tracer: one `ring.active` read per batch.
        self.flow_ring = flow_ring if flow_ring is not None else FlowRing()
        # optional identity → labels resolver for flow records:
        # fn(identity_id) -> tuple of label strings (the daemon points
        # this at its IdentityRegistry)
        self.identity_labels = None
        # jit-cache key shapes already dispatched (tracing telemetry:
        # a new member ≈ one XLA recompile)
        self._seen_shapes: set = set()
        # called once per batch that redirects a flow with a known
        # 5-tuple, with the redirected rows as arrays:
        # fn(peer_bytes[R, 4|16], ep_idx[R], sports[R], dports[R],
        # protos[R], ingress, family) — the cilium_proxy4/6 write hook
        # (bpf_lxc.c inserts a proxymap entry when the verdict is a
        # proxy port)
        self.on_redirect_batch = None
        # TraceNotify for forwarded flows is opt-in (the reference
        # gates trace events behind the TraceNotify endpoint option);
        # DropNotify defaults on while a listener is attached, gated
        # by the DropNotification runtime option.
        self.trace_enabled = False
        self.drop_notifications = True
        # PolicyVerdictNotify for EVERY flow (allowed included) is
        # opt-in (PolicyVerdictNotification runtime option) — it walks
        # the whole batch, so it stays off unless asked for
        self.verdict_notifications = False
        # optional per-endpoint option resolver:
        # fn(endpoint_id, option_name, default) -> bool. The daemon
        # points this at each endpoint's OptionMap so `cilium endpoint
        # config` overrides actually gate that endpoint's events
        # (applyOptsLocked inheritance, pkg/option).
        self.endpoint_options = None
        self._lb_tables: Dict[int, object] = {}
        self._lb_version = -1
        self._lock = threading.Lock()
        self._endpoints: List[int] = []  # identity ids of local endpoints
        self._endpoint_ids: List[int] = []  # endpoint ids (same order)
        self._tables: Dict[Tuple[int, int], DatapathTables] = {}
        # direction → MaterializedState (TRAFFIC_INGRESS / TRAFFIC_EGRESS)
        self._mat: Dict[int, MaterializedState] = {}
        self._mat_sig: Tuple = ()  # endpoint list the policymap was built for
        self._last_delta_seq = 0  # engine delta cursor
        self._trie_versions: Tuple = ()  # (ipcache.version, prefilter.revision)
        # (v4_empty, v6_empty) for the COMPILED prefilter tries: an
        # empty deny set skips the whole deny-LPM walk (which would
        # otherwise cost as much as the identity walk — half the
        # end-to-end pipeline), matching the reference's no-op empty
        # XDP maps. Updated together with self._tables.
        # REBUILD-INTERNAL: these two feed the _dp_state snapshot below
        # and are only safe to read directly in single-threaded contexts
        # (tests, benchmark setup). Dispatch paths MUST read _dp_state —
        # a separate-attribute read can pair a fresh flag with old
        # tables.
        self._pf_empty: Tuple[bool, bool] = (True, True)
        self._v6_fused = False  # v6 merged deny+identity trie present
        # ATOMIC read snapshot for the lock-free dispatch paths:
        # (tables, pf_empty, v6_fused, flow_sharding, ndev, attrib,
        # ident2d, shed) swap together — reading them as separate attributes
        # could pair a new flag with old tables (e.g. fused=True against
        # placeholder merged arrays, which would resolve every v6 flow
        # to world with no denies, or a flow sharding against tables
        # placed for a different mesh, or a rule table from an older
        # rule set against newer policymaps). ``attrib`` is None (off)
        # or ({direction: rule_tab [N, C_pad]}, n_rules). ``ident2d``
        # selects the ident-sharded gather program — it must pair with
        # tables actually placed under P("ident"), never cross-read.
        # ``ndev`` is the FLOWS-axis size, not the total device count:
        # on a 2D mesh a batch splits over flows only.
        # (policyd-overload widened the tuple with the placed prefilter
        # shed table — None while the Prefilter option is off.)
        self._dp_state: Tuple = (
            {}, (True, True), False, None, 1, None, False, None,
        )
        self._tries: Optional[Tuple] = None  # ((pf4, ip4), (pf6, ip6), world_row)
        self.counters = np.zeros((0, 3), np.int64)
        # -- bounded in-flight dispatch queue -------------------------
        # submit() enqueues the device program and defers the host pull
        # (+ counters/ct_create/events) until completion; depth bounds
        # how many batches sit un-pulled so host prep of batch N+1
        # overlaps device execution of batch N. Depth 1 = synchronous.
        self.pipeline_depth = max(1, int(pipeline_depth))
        self._inflight: deque = deque()  # FIFO of _InFlight
        self._queue_lock = threading.Lock()  # guards _inflight only
        # conntrack basis epoch: bumped on every CT flush so a batch
        # completing AFTER a basis move (policy/ipcache change raced
        # its in-flight window) cannot create entries verdicted under
        # the old basis
        self._ct_epoch = 0
        # set when a basis move is DETECTED, cleared only after the
        # flush+epoch-advance completes: table versions commit above
        # this block, so a fault between commit and flush must not let
        # a retried rebuild skip the flush (policyd-failsafe)
        self._ct_flush_pending = False
        # basis (revision, identity_version, vocab_version) of the
        # generation the SERVED conntrack entries were verdicted under
        # (policyd-survive): committed by rebuild, read by the CT
        # snapshot writer — between a recompile and the next rebuild
        # the live entries still belong to THIS basis, not the
        # engine's newest compile
        self._mat_basis: Optional[Tuple[int, int, int]] = None
        # ladder rungs already dispatched (telemetry: the chunker's
        # shape set is the fixed BUCKET_LADDER; a rung joins this set
        # the first time a batch actually compiles/warms it)
        self._warm_buckets: set = set()
        # -- policyd-autotune: pre-pinned staging + depth tuner --------
        # (rung, peer_width) → free-list of rung-sized int32 host
        # staging tuples (peer_bytes, ep_idx, dports, protos, row_ov).
        # The bucketed pad half writes into these instead of np.pad
        # allocations per chunk. A tuple leaves the list at enqueue and
        # returns at the batch's host pull — never earlier: JAX CPU can
        # alias aligned numpy memory zero-copy, so reuse before
        # completion could race the device program's reads.
        self._staging: Dict[Tuple[int, int], list] = {}
        self._staging_lock = threading.Lock()
        # depth auto-tuner (DispatchAutoTune): OFF by default — the
        # dispatch path then pays one `self._tuner is None` read per
        # batch and pipeline_depth never moves (static-depth behavior
        # preserved exactly). _static_depth is what set_autotune(False)
        # restores.
        self._static_depth = self.pipeline_depth
        self.pipeline_max_depth = max(self.pipeline_depth, int(pipeline_max_depth))
        self._tuner: Optional[DepthTuner] = None
        if autotune:
            self.set_autotune(True)
        _metrics.pipeline_depth_current.set(float(self.pipeline_depth))
        # -- multi-device flow sharding (VerdictSharding) -------------
        # active mesh → tables replicated, flow batches split over the
        # "flows" axis. The dispatch-visible sharding rides _dp_state
        # so it can never pair with tables placed for a different mesh.
        self._sharding_requested = bool(sharding)
        # -- placement subsystem (datapath/placement.py) --------------
        # the resolved MeshPlan owns mesh construction, axis shardings
        # and the generation counter; _mesh/_flow_sharding/
        # _table_sharding are kept as synced mirrors (tests and older
        # call sites read them directly).
        self._placement = placement
        self._mesh2d_requested = bool(mesh_2d)
        self._plan: MeshPlan = EMPTY_PLAN
        self._mesh: Optional[Mesh] = None
        self._flow_sharding: Optional[NamedSharding] = None
        self._table_sharding: Optional[NamedSharding] = None
        # direction → (plan generation, source policymap, placed copy):
        # re-place when materialization swaps the source object OR the
        # plan generation moved (a ladder demotion / placement change
        # must never serve tables placed on a stale mesh)
        self._placed_pm: Dict[int, Tuple[int, object, object]] = {}
        # source sel_match → (generation, ident-placed copy): the 2D
        # plan row-shards the [N, S/32] selector-match bitmaps the
        # materializer sweeps gather from
        self._placed_sel: Tuple[int, object, object] = (0, None, None)
        # -- verdict attribution (FlowAttribution) --------------------
        # requested state; takes effect on the next rebuild (the sweep
        # must re-run with the attribution kernel variant to populate
        # the per-(row, column) rule table)
        self._attrib_requested = False
        self._attrib_n_rules = 0
        # rule index → origin label (repo.origin_names()), refreshed
        # with the rule tables; read lock-free in the completion half
        self._attrib_names: List[str] = []
        # direction → (plan generation, source rule_tab, placed copy) —
        # the _placed_pm pattern for the attribution gather table
        self._placed_rt: Dict[int, Tuple[int, object, object]] = {}
        # -- policyd-failsafe: self-healing / degradation ladder ------
        # ladder level (index into _MODE_NAMES): 0 = full device
        # complement, 1 = single-device, 2 = host fallback. Transitions
        # take self._lock; dispatch paths read the int lock-free
        # (GIL-atomic, same rule as pipeline_depth).
        self._ladder_level = 0
        # FailOpen runtime option: what an UNRESOLVABLE batch returns.
        # Off (default) = fail-closed: DROP_DEGRADED verdicts, monitor
        # reason 155. On = forward unverdicted traffic.
        self._fail_open = False
        # device ids the mesh must exclude (populated on a sharded →
        # single-device descent; consulted by _refresh_mesh_locked)
        self._excluded_devices: set = set()
        # circuit breaker: quarantines increment _breaker_faults and a
        # clean-batch streak clears them; at the threshold the ladder
        # descends one level. recover_after_clean clean batches at a
        # degraded level probe one level back up. Both knobs are plain
        # attributes so tests shrink the windows.
        self.breaker_threshold = 3
        self.recover_after_clean = 32
        self._breaker_faults = 0
        self._clean_batches = 0
        # bounded retry of classified-transient failures (completion
        # pull / enqueue): retry_limit attempts spaced by a fresh
        # Backoff(retry_min_s → retry_max_s) per failure
        self.retry_limit = 2
        self.retry_min_s = 0.005
        self.retry_max_s = 0.1
        self._quarantined = 0  # batches resolved degraded (lifetime)
        # direction → (source policymap, host numpy copy) for the
        # ladder-level-2 fallback — pulled once per materialization,
        # not per batch
        self._host_pm: Dict[int, Tuple[object, Tuple]] = {}
        # -- policyd-delta: epoch-swapped device tables ---------------
        # Opt-in (EpochSwap runtime option): a full re-materialization
        # demanded by the delta log builds its policymaps on a SHADOW
        # thread while dispatches keep serving the current generation;
        # the finished generation installs under self._lock and becomes
        # dispatch-visible through the NEXT rebuild's single _dp_state
        # publish — the atomic batch-boundary swap, riding the same
        # transactional _ct_flush_pending block (and SITE_CT_EPOCH
        # fault site) as every other basis move. _swap_gen is the
        # abandonment guard: any event that invalidates the basis the
        # shadow bound to (quarantine, ladder move, endpoint/sharding/
        # attribution change, swap-off) bumps it, and a finishing
        # shadow whose generation no longer matches is discarded — a
        # swap mid-quarantine must not resurrect the abandoned epoch.
        self._epoch_swap = bool(epoch_swap)
        self._policy_epoch = 0  # generations actually swapped in
        self._swap_gen = 0  # basis generation a shadow build binds to
        self._shadow_thread: Optional[threading.Thread] = None
        self._shadow_exc: Optional[BaseException] = None
        # -- policyd-overload: admission control + watchdog -----------
        # AdmissionControl runtime option: None (off) keeps _submit at
        # one `self._admission is None` read per batch — the exact
        # pre-option path. The deadline is boot config, consulted only
        # while the controller exists.
        self.deadline_ms = max(0.0, float(deadline_ms))
        # policyd-journal: lifecycle-event emission slot. None while
        # the LifecycleJournal option is off (every site pays one
        # attribute read); the daemon installs the journal's bound
        # emit — called as ``oj(kind=..., severity=..., attrs=...)``
        # with OBS003-checked kind literals, always OUTSIDE this
        # pipeline's locks. Initialized before the admission boot
        # toggle below, which forwards it to the controller.
        self.on_journal = None
        self._admission: Optional[AdmissionController] = None
        if admission:
            self.set_admission(True)
        # Prefilter runtime option: when on, rebuild() compiles the
        # coarse [identity, proto/port-class] drop table from the
        # ingress policymap mirror and publishes it THROUGH _dp_state
        # (placed with the same table sharding as the tries, so it
        # rides the MeshPlan). Off publishes None and no shed kernel
        # ever traces.
        self._shed_requested = bool(prefilter_shed)
        # (plan generation, placed device table) — recompiled when the
        # policymap mirror or the placement moved
        self._shed_cache: Optional[Tuple[int, object]] = None
        # -- policyd-sparse: O(k) sparse device-table deltas ----------
        # SparseDeltas runtime option: when on, (a) the ident-placed
        # sel_match copy is PATCHED from the engine's delta log (row +
        # column scatters, O(delta) per device) instead of re-placed
        # whole, and (b) ipcache prefix churn patches the device LPM
        # trie tensors in place (ops/lpm.py Patchable* — O(delta) node
        # writes) instead of re-merging whole tries. Off keeps the
        # exact pre-option paths: full device_put on sel_match source
        # change, full trie rebuild on any ipcache version move (the
        # patchable builders are never constructed).
        self._sparse_deltas = bool(sparse_deltas)
        # family → patchable trie builder (4: PatchableFlatTrie or
        # None, 6: PatchableElidedTrie or None), rebuilt alongside
        # self._tries; None until a sparse-enabled full rebuild runs
        self._trie_patch: Optional[Dict[int, object]] = None
        # stuck-dispatch watchdog (dispatch_stall_ms > 0): monitors the
        # actively-completing batch + registered external waits and
        # drives the quarantine/breaker path instead of hanging
        self._watchdog: Optional[Watchdog] = None
        # (inf, t0) while a completion pull is running; the watchdog's
        # only view into "actively stuck" (set/cleared only while the
        # watchdog exists — the off path never writes it per batch)
        self._completing: Optional[Tuple] = None
        if stall_ms > 0:
            self.set_stall_ms(stall_ms)
        # -- policyd-prof: device-time sampling profiler --------------
        # DeviceProfiling runtime option: None (off) keeps the dispatch
        # halves at one `self.profiler is None` read per batch — the
        # exact pre-option programs (observe/profiler.py is not even
        # imported). sample_every is boot config; set_profiling builds
        # the profiler with it.
        self.profile_sample_every = max(1, int(profile_sample_every))
        self.profiler = None
        if profiling:
            self.set_profiling(True)
        # -- policyd-survive: restart/drain continuity ----------------
        # Drain shed: begin_drain() flips this and _submit resolves new
        # batches degraded immediately while drain() FIFO-completes the
        # in-flight queue. The not-draining path pays one GIL-atomic
        # bool read per batch (the hub `active` pattern).
        self._draining = False
        # One-shot CT restore hold: the daemon sets this to the engine
        # revision current when it restored a CT snapshot whose basis
        # it verified against the restored compiled snapshot. The NEXT
        # rebuild's flush triggers consume it — but only if they
        # materialize that SAME revision (this process's first
        # materialization then builds from exactly the restored
        # tables, so the basis that admitted the entries still holds).
        # A policy mutation racing in before the first rebuild bumps
        # the revision, invalidates the hold, and flushes as always.
        self._ct_restore_hold: Optional[int] = None
        # one-shot completion hook (restart_downtime measurement): the
        # daemon points this at its downtime stamp after restore; fired
        # once after the first completed batch, then cleared
        self.on_first_batch = None
        # quarantine CT rescue: set after live device-CT entries were
        # pulled into the host table, so the next fresh device table
        # seeds from the host CT (re-upload on ladder re-promotion)
        # instead of zeros — established flows survive the round trip
        self._device_ct_seed = False
        self.device_ct_rescue_limit = 1 << 16
        _metrics.pipeline_mode.set(0.0)

    def set_endpoints(self, endpoints: Sequence) -> None:
        """Accepts identity ids (endpoint id == identity id) or
        (endpoint_id, identity_id) pairs; order defines the datapath
        endpoint index."""
        with self._lock:
            pairs = [
                e if isinstance(e, tuple) else (int(e), int(e)) for e in endpoints
            ]
            self._endpoint_ids = [p[0] for p in pairs]
            self._endpoints = [p[1] for p in pairs]
            self._mat.clear()  # column layout changes with the endpoint set
            # CT keys embed the endpoint INDEX; a changed endpoint list
            # would let a new occupant of an index inherit the previous
            # endpoint's established-flow bypass entries.
            if self.conntrack is not None:
                self.conntrack.flush()
            self._ct_epoch += 1
            self._device_ct = None
            self._swap_gen += 1  # column layout moved: abandon shadows

    def endpoint_index(self, endpoint_id: int) -> Optional[int]:
        try:
            return self._endpoint_ids.index(endpoint_id)
        except ValueError:
            return None

    def endpoint_id_at(self, idx: int) -> Optional[int]:
        with self._lock:
            if 0 <= idx < len(self._endpoint_ids):
                return self._endpoint_ids[idx]
        return None

    def set_sharding(self, on: bool) -> None:
        """Toggle multi-device flow sharding (the VerdictSharding
        runtime option). Takes effect on the next rebuild; a mesh only
        forms with >1 visible device. Clears placed tables and the
        shape/warm caches — sharded and unsharded dispatches compile
        different programs."""
        with self._lock:
            if bool(on) == self._sharding_requested:
                return
            self._sharding_requested = bool(on)
            self._tables = {}
            self._tries = None
            self._placed_pm.clear()
            self._placed_rt.clear()
            self._placed_sel = (0, None, None)
            self._swap_gen += 1  # placement basis moved: abandon shadows
        # telemetry/warm caches: best-effort sets the lock-free dispatch
        # paths also mutate bare (GIL-atomic; a racing add only costs
        # one redundant compile or a miscounted cache-hit metric)
        self._seen_shapes.clear()
        self._warm_buckets.clear()

    def set_mesh_2d(self, on: bool) -> None:
        """Toggle 2D flows×ident mesh sharding (the MeshSharding2D
        runtime option). Takes effect on the next rebuild through the
        placement plan: the device grid splits into flows×ident axes
        and the identity dimension of the policymaps / rule tables /
        sel_match bitmaps shards over ``ident``. OFF compiles the exact
        pre-option 1D/replicated programs (the ident-gather variant is
        unreachable — pinned spy-style like FlowAttribution). Clears
        placed tables and the shape/warm caches, same discipline as
        set_sharding."""
        with self._lock:
            if bool(on) == self._mesh2d_requested:
                return
            self._mesh2d_requested = bool(on)
            self._tables = {}
            self._tries = None
            self._placed_pm.clear()
            self._placed_rt.clear()
            self._placed_sel = (0, None, None)
            self._swap_gen += 1  # placement basis moved: abandon shadows
        self._seen_shapes.clear()
        self._warm_buckets.clear()

    def set_attribution(self, on: bool) -> None:
        """Toggle per-flow verdict attribution (the FlowAttribution
        runtime option). Takes effect on the next rebuild: the
        materializer sweep re-runs with the attribution kernel variant
        to populate the per-(identity row, column) deciding-rule table,
        and dispatches switch to the attrib program variant (rule
        gather + on-device rule-hit segment-sum; d2h pulls stay in the
        completion half). Off keeps the exact pre-attribution programs
        — the rule table contributes no leaves to the off-path trace.
        The device-CT fused path is NOT attributed; its drops keep the
        generic policy reason. Clears the shape/warm caches —
        attributed and plain dispatches compile different programs."""
        with self._lock:
            if bool(on) == self._attrib_requested:
                return
            self._attrib_requested = bool(on)
            # force re-materialization: the rule table only exists when
            # the sweep ran with attribution (and is dropped when off)
            self._mat.clear()
            self._mat_sig = ()
            self._placed_rt.clear()
            self._swap_gen += 1  # sweep variant moved: abandon shadows
        self.flow_ring.active = bool(on)
        self._seen_shapes.clear()
        self._warm_buckets.clear()

    # -- policyd-autotune: depth controller ----------------------------
    def set_autotune(
        self,
        on: bool,
        *,
        max_depth: Optional[int] = None,
        epoch: Optional[int] = None,
    ) -> None:
        """Toggle the dispatch depth auto-tuner (the DispatchAutoTune
        runtime option). ON installs a fresh DepthTuner stepping
        pipeline_depth in [1, pipeline_max_depth] from per-batch
        enqueue/complete timings; OFF restores the configured static
        depth and drops the tuner (the per-batch cost returns to one
        ``self._tuner is None`` read). ``epoch`` shrinks the decision
        interval for tests' convergence runs."""
        if max_depth is not None:
            self.pipeline_max_depth = max(1, int(max_depth))
        if not on:
            if self._tuner is not None:
                self._tuner = None
                self._apply_depth(self._static_depth)
            return
        kw = {} if epoch is None else {"epoch": int(epoch)}
        self._tuner = DepthTuner(1, self.pipeline_max_depth, **kw)
        _metrics.pipeline_depth_current.set(float(self.pipeline_depth))

    def _apply_depth(self, depth: int) -> None:
        """Move the effective pipeline depth (tuner decisions and
        autotune-off restore). Reads of pipeline_depth on the admission
        path are GIL-atomic, so a step takes effect on the very next
        submit — a deeper queue admits immediately, a shallower one
        drains through the existing over-depth completion loop."""
        depth = max(
            1, min(int(depth), max(self.pipeline_max_depth, self._static_depth))
        )
        cur = self.pipeline_depth
        if depth == cur:
            return
        self.pipeline_depth = depth
        _metrics.pipeline_depth_current.set(float(depth))
        _metrics.autotune_adjustments_total.inc(
            {"direction": "up" if depth > cur else "down"}
        )

    def autotune_state(self) -> Optional[Dict]:
        """Tuner snapshot for GET /traces (None while autotune is
        off)."""
        t = self._tuner
        if t is None:
            return None
        snap = t.snapshot()
        snap["depth"] = self.pipeline_depth
        snap["static_depth"] = self._static_depth
        return snap

    # -- policyd-autotune: pre-pinned staging --------------------------
    # free-list bound per (rung, width): deeper queues keep more tuples
    # in flight, but depth × chunks stays small — beyond this the
    # allocations were a burst, not steady state, so let them collect
    _STAGING_FREE_CAP = 8

    def _staging_acquire(self, rung: int, width: int):
        """One rung-sized staging tuple (peer[rung, width], ep, dp, pr,
        row_override — all int32, matching what prepare() coerces), off
        the free-list or freshly allocated on first use of a rung."""
        key = (rung, width)
        with self._staging_lock:
            free = self._staging.get(key)
            if free:
                return free.pop()
        return (
            np.empty((rung, width), np.int32),
            np.empty(rung, np.int32),
            np.empty(rung, np.int32),
            np.empty(rung, np.int32),
            np.empty(rung, np.int32),
        )

    def _staging_release(self, bufs_list) -> None:
        """Return a completed batch's staging tuples to their
        free-lists (called from the host-pull half only — see the
        aliasing note at _staging)."""
        for bufs in bufs_list:
            key = (bufs[0].shape[0], bufs[0].shape[1])
            with self._staging_lock:
                free = self._staging.setdefault(key, [])
                if len(free) < self._STAGING_FREE_CAP:
                    free.append(bufs)

    def _refresh_mesh_locked(self) -> None:
        """Resolve the placement plan to match the sharding/2D requests
        (held-lock helper for rebuild). Devices in _excluded_devices
        (a degradation-ladder descent) never join the mesh; with an
        empty exclusion set and no PlacementConfig this is exactly the
        pre-placement behavior — one 1D mesh over all visible devices,
        formed once (resolve_plan returns the previous plan unchanged
        when nothing moved, so mesh identity is stable). The legacy
        _mesh/_flow_sharding/_table_sharding attributes are mirrors of
        the plan, kept for tests and older call sites."""
        plan = resolve_plan(
            self._placement,
            sharding=self._sharding_requested,
            mesh_2d=self._mesh2d_requested,
            excluded=frozenset(self._excluded_devices),
            prev=self._plan,
        )
        if plan is not self._plan:
            self._plan = plan
            _metrics.mesh_axis_size.set(
                float(plan.axes.get("flows", 0)), {"axis": "flows"}
            )
            _metrics.mesh_axis_size.set(
                float(plan.axes.get("ident", 0)), {"axis": "ident"}
            )
        self._mesh = plan.mesh
        self._flow_sharding = plan.flow_sharding
        self._table_sharding = plan.table_sharding
        # the engine's device tables live where the plan puts tables:
        # on a 2D plan rule tables replicated, sel_match ident-sharded
        self.engine.set_placement(plan.table_sharding, plan.ident_sharding)

    # -- policyd-failsafe: ladder + classified error handling ----------
    def set_fail_open(self, on: bool) -> None:
        """Toggle the FailOpen runtime option: what a batch that
        exhausted its retries returns. Off (default) is fail-closed —
        DROP_DEGRADED verdicts carrying monitor reason 155; on forwards
        unverdicted traffic (availability over enforcement)."""
        self._fail_open = bool(on)

    @property
    def pipeline_mode(self) -> str:
        return _MODE_NAMES[self._ladder_level]

    def failsafe_state(self) -> Dict:
        """Degraded-state snapshot for GET /healthz, GET /traces, and
        the CLI traces header."""
        return {
            "mode": self.pipeline_mode,
            "level": self._ladder_level,
            "degraded": self._ladder_level > 0,
            "fail_open": self._fail_open,
            "breaker_faults": self._breaker_faults,
            "clean_batches": self._clean_batches,
            "quarantined_batches": self._quarantined,
            "excluded_devices": sorted(self._excluded_devices),
            "fault_injection": _faults.hub.active,
        }

    def placement_state(self) -> Dict:
        """Placement snapshot for GET /traces and the CLI traces
        header: the resolved plan's generation, axes, and device set
        plus the operator's requests. Resolves the plan first so a
        just-patched option reports the mesh it WILL run on, not the
        one the last dispatch used."""
        with self._lock:
            self._refresh_mesh_locked()
        plan = self._plan
        return {
            "generation": plan.generation,
            "axes": dict(plan.axes),
            "devices": list(plan.device_ids),
            "flows_size": plan.flows_size,
            "mesh_2d_requested": self._mesh2d_requested,
            "sharding_requested": self._sharding_requested,
            "ident_sharded": plan.is_2d,
            "excluded_devices": sorted(self._excluded_devices),
        }

    # -- policyd-overload: admission control + watchdog ----------------
    def set_admission(self, on: bool) -> None:
        """Toggle the AdmissionControl runtime option. Off (default)
        keeps the submit path at ONE attribute read per batch
        (``self._admission is None``) — the exact pre-option programs;
        on installs the AIMD gate bounded by pipeline_max_depth and
        keyed on the boot verdict deadline."""
        if on:
            if self._admission is None:
                self._admission = AdmissionController(
                    max_depth=max(
                        self.pipeline_depth, self.pipeline_max_depth
                    ),
                    deadline_ms=self.deadline_ms,
                )
                # a journal armed before the controller existed still
                # sees its shed episodes
                self._admission.on_journal = self.on_journal
        else:
            self._admission = None

    def set_prefilter_shed(self, on: bool) -> None:
        """Toggle the Prefilter runtime option: whether rebuild()
        compiles + publishes the coarse [identity, class] shed table.
        The next rebuild's single _dp_state publish makes the change
        dispatch-visible; off publishes None and the shed kernels never
        trace."""
        self._shed_requested = bool(on)

    def set_stall_ms(self, stall_ms: float) -> None:
        """(Re)arm the stuck-dispatch watchdog; 0 stops it."""
        wd = self._watchdog
        if wd is not None:
            wd.stop()
            self._watchdog = None
        if stall_ms and stall_ms > 0:
            self._watchdog = Watchdog(self, float(stall_ms))
            self._watchdog.start()

    def admission_state(self) -> Dict:
        """Overload snapshot for GET /healthz, GET /traces, and the CLI
        traces header: gate limit + shed accounting, queue depth, and
        the watchdog's last stall."""
        adm = self._admission
        wd = self._watchdog
        out: Dict = {
            "enabled": adm is not None,
            "prefilter": self._shed_requested,
            "queue_depth": len(self._inflight),
            "deadline_ms": self.deadline_ms,
        }
        if adm is not None:
            out.update(adm.snapshot())
        else:
            out["shed_ratio"] = 0.0
        out["watchdog"] = wd.snapshot() if wd is not None else None
        return out

    # -- policyd-prof: device-time sampling profiler -------------------
    def set_profiling(
        self, on: bool, *, sample_every: Optional[int] = None
    ) -> None:
        """Toggle the DeviceProfiling runtime option. Off (default)
        keeps both dispatch halves at ONE attribute read per batch
        (``self.profiler is None``) — the exact pre-option programs;
        on installs a fresh DeviceProfiler whose every
        ``sample_every``-th batch pays the block_until_ready
        sandwiches that decompose dispatch RTT (observe/profiler.py)."""
        if sample_every is not None:
            self.profile_sample_every = max(1, int(sample_every))
        if not on:
            self.profiler = None
            return
        if self.profiler is None:
            from ..observe.profiler import DeviceProfiler

            self.profiler = DeviceProfiler(
                sample_every=self.profile_sample_every
            )
        elif self.profiler.sample_every != self.profile_sample_every:
            # re-enable with a new rate retunes the live instance (the
            # ring and ledgers are kept — only the cadence moves)
            self.profiler.sample_every = self.profile_sample_every

    def profile_state(self) -> Dict:
        """Profiler snapshot for GET /profile and ``cilium-tpu top``
        (enabled flag + samples/aggregates/jit-cost ledger when on)."""
        prof = self.profiler
        if prof is None:
            return {
                "enabled": False,
                "sample_every": self.profile_sample_every,
            }
        return prof.snapshot()

    def _shed_walk(
        self, peer_bytes: np.ndarray, dports, protos, *, family: int
    ) -> Optional[np.ndarray]:
        """[B] bool deny-for-sure mask from the published shed table
        (one device gather + the LPM identity walk), or None when no
        table is live (Prefilter off, pre-first-rebuild, host-mode
        ladder). Reflects the policy as of the LAST rebuild — the same
        one-batch staleness window every in-flight dispatch has. The
        jit keys on the raw batch shape (no bucketing): the gate is
        only reached over budget, where a recompile-per-new-size is
        noise next to the queue it is shedding."""
        state = self._dp_state
        shed_tab = state[7]
        if shed_tab is None:
            return None
        t = state[0].get((TRAFFIC_INGRESS, family))
        if t is None:
            return None
        dp = jnp.asarray(np.asarray(dports, np.int32))
        pr = jnp.asarray(np.asarray(protos, np.int32))
        if family == 4:
            peer_u32 = _pack_v4_u32(np.asarray(peer_bytes, np.int32))
            mask = shed_flows_wide(t, shed_tab, jnp.asarray(peer_u32), dp, pr)
        else:
            mask = shed_flows(
                t, shed_tab, jnp.asarray(np.asarray(peer_bytes, np.int32)),
                dp, pr, levels=16,
            )
        # intended host boundary: the gate partitions the batch on the
        # host, so the [B] bool mask is pulled once per shed decision —
        # the same one-batched-pull contract the verdict path carries
        return np.asarray(mask)  # policyd-lint: disable=TPU001

    def _resolve_at_gate(
        self,
        peer_bytes: np.ndarray,
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        idx: np.ndarray,
        *,
        verdict_code: int,
        ingress: bool,
        family: int,
    ) -> None:
        """Account + emit for flows resolved AT the admission gate
        (shed or deadline-degraded) — the same per-endpoint counters,
        verdicts_total series, drop-reason series, and DropNotify
        events the device path would have produced, so a shed flow is
        observable everywhere a dropped one is (never a silent drop)."""
        if idx.size == 0:
            return
        v = np.full(idx.size, verdict_code, np.int8)
        with self._lock:
            if self.counters.shape[0] == max(1, len(self._endpoints)):
                cls = 0 if verdict_code == FORWARD else 2
                np.add.at(self.counters, (ep_idx[idx], cls), 1)
        if verdict_code == DROP_PREFILTER:
            # reason 144 has two producers; this is the host admission
            # gate, not the device prefilter kernel (observe/README.md)
            _metrics.drop_reasons_total.inc(
                {"reason": "prefilter", "producer": "admission"},
                float(idx.size),
            )
        elif verdict_code == DROP_DEGRADED:
            _metrics.drop_reasons_total.inc(
                {"reason": "pipeline-degraded"}, float(idx.size)
            )
        self._account_batch(v)
        self._emit_flow_events(
            peer_bytes[idx], ep_idx[idx], dports[idx], protos[idx], v,
            ingress=ingress, family=family, producer="admission",
        )

    def _admission_gate(
        self,
        peer_bytes: np.ndarray,
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        sports: Optional[np.ndarray],
        *,
        ingress: bool,
        family: int,
        peer_words,
        want_rev_nat: bool,
        tunnel_identities,
    ) -> Optional[PendingBatch]:
        """The over-budget path of the admission gate. Returns None
        when the batch is admitted UNCHANGED (the caller proceeds down
        the exact ungated submit path), else a fully- or
        partially-resolved PendingBatch:

        1. deny-for-sure flows (shed-table match) resolve NOW with
           DROP_PREFILTER (monitor reason 144) — no queue, no device
           round-trip beyond the one cheap gather;
        2. the remainder DEFERS bounded: this thread drains its own
           in-flight queue until the gate opens or the deadline budget
           is spent (an empty queue always admits — nothing left to
           wait on);
        3. a spent deadline resolves the remainder through the
           failsafe semantics — FORWARD under FailOpen, else
           DROP_DEGRADED (155). Never an unbounded queue, never a
           silent drop."""
        adm = self._admission
        t_gate = time.monotonic()
        forced = False
        if _faults.hub.active:
            try:
                _faults.hub.check(_faults.SITE_QUEUE_FULL)
            except _faults.FaultError:
                # an overload signal, not a device fault: halve the
                # limit and force THIS batch through the shed path (the
                # breaker/ladder stays out of it — shedding load must
                # not also degrade the mesh)
                adm.note_queue_full()
                forced = True
        depth = len(self._inflight)
        _metrics.admission_queue_depth.set(float(depth))
        if not forced and not adm.over_budget(depth):
            adm.note_admitted(peer_bytes.shape[0])
            return None
        b = peer_bytes.shape[0]
        ep_idx = np.asarray(ep_idx, np.int32)
        dports = np.asarray(dports, np.int32)
        protos = np.asarray(protos, np.int32)
        # 1) prefilter shed — ingress only (the table is compiled from
        # the ingress policymaps, like the device pf stage) and never
        # for overlay flows whose tunnel identity overrides the LPM row
        shed_mask = None
        if ingress and tunnel_identities is None and b:
            try:
                shed_mask = self._shed_walk(
                    peer_bytes, dports, protos, family=family
                )
            except BaseException as e:
                kind = _faults.classify(e)
                if kind == _faults.KIND_ERROR:
                    raise
                # the shed walk is an optimization: a faulted gather
                # must never fail the submission itself
                self._note_fault(e, kind)
                shed_mask = None
        if shed_mask is not None and shed_mask.any():
            shed_idx = np.nonzero(shed_mask)[0]
            keep_idx = np.nonzero(~shed_mask)[0]
            self._resolve_at_gate(
                peer_bytes, ep_idx, dports, protos, shed_idx,
                verdict_code=DROP_PREFILTER, ingress=ingress, family=family,
            )
            adm.note_shed(REASON_SHED_PREFILTER, int(shed_idx.size))
        else:
            shed_idx = np.empty(0, np.int64)
            keep_idx = np.arange(b)
        # 2) bounded deferral for the remainder
        admitted = keep_idx.size > 0
        if admitted:
            budget_s = adm.deadline_s or None
            while adm.over_budget(len(self._inflight)):
                if (
                    budget_s is not None
                    and time.monotonic() - t_gate >= budget_s
                ):
                    admitted = False
                    break
                if not self._complete_oldest():
                    break
        _metrics.queue_wait_seconds.observe(time.monotonic() - t_gate)
        if keep_idx.size == 0:
            # whole batch shed: resolved handle, nothing ever queued
            pending = PendingBatch(self)
            v = np.full(b, DROP_PREFILTER, np.int8)
            red = np.zeros(b, bool)
            pending._value = (
                (v, red, np.zeros(b, np.uint16)) if want_rev_nat
                else (v, red)
            )
            pending._event.set()
            return pending
        if not admitted:
            # 3) deadline spent: failsafe resolution for the remainder
            code = FORWARD if self._fail_open else DROP_DEGRADED
            self._resolve_at_gate(
                peer_bytes, ep_idx, dports, protos, keep_idx,
                verdict_code=code, ingress=ingress, family=family,
            )
            adm.note_shed(REASON_SHED_DEADLINE, int(keep_idx.size))
            v = np.empty(b, np.int8)
            v[shed_idx] = DROP_PREFILTER
            v[keep_idx] = code
            red = np.zeros(b, bool)
            pending = PendingBatch(self)
            pending._value = (
                (v, red, np.zeros(b, np.uint16)) if want_rev_nat
                else (v, red)
            )
            pending._event.set()
            return pending
        adm.note_admitted(int(keep_idx.size))
        if keep_idx.size == b:
            # nothing shed and the gate opened: the caller proceeds
            # down the UNCHANGED submit path (bit-identical programs)
            return None
        inner = self._submit(
            peer_bytes[keep_idx], ep_idx[keep_idx], dports[keep_idx],
            protos[keep_idx],
            None if sports is None else np.asarray(sports)[keep_idx],
            ingress=ingress, family=family,
            peer_words=(
                None if peer_words is None
                else (peer_words[0][keep_idx], peer_words[1][keep_idx])
            ),
            want_rev_nat=want_rev_nat,
            tunnel_identities=None,
            gate=False,
        )
        shed_v = np.zeros(b, np.int8)
        shed_v[shed_idx] = DROP_PREFILTER
        return _GatedPending(self, inner, keep_idx, shed_v, b, want_rev_nat)

    def _set_level(self, level: int) -> None:
        """Move the degradation ladder (descent on a tripped breaker,
        re-promotion probe on a clean streak). Clears placed tables and
        the shape/warm caches — the next rebuild re-forms the mesh over
        the healthy device set and re-places tables through the
        identity-cached placement, exactly like a sharding toggle."""
        with self._lock:
            cur = self._ladder_level
            level = max(0, min(len(_MODE_NAMES) - 1, int(level)))
            if level == cur:
                return
            frm, to = _MODE_NAMES[cur], _MODE_NAMES[level]
            self._ladder_level = level
            if level == 0:
                # full re-promotion: all devices eligible again
                self._excluded_devices.clear()
            elif cur == 0:
                # sharded → single-device: keep ONE healthy device.
                # Which chip faulted is not attributable host-side (the
                # pull fails for the whole mesh program), so keep the
                # first and exclude the rest — the recovery probe
                # re-admits them after a clean streak. The excluded set
                # derives from the ACTIVE plan's device ids, not
                # jax.devices(): a placement-restricted daemon must
                # never demote onto a device it was configured not to
                # use (the plan's first device stays; everything else
                # the plan was using leaves the mesh).
                plan_ids = self._plan.device_ids or tuple(
                    d.id for d in jax.devices()
                )
                self._excluded_devices.update(plan_ids[1:])
            self._tables = {}
            self._tries = None
            self._placed_pm.clear()
            self._placed_rt.clear()
            self._placed_sel = (0, None, None)
            self._breaker_faults = 0
            self._clean_batches = 0
            # a ladder move re-forms the mesh: a shadow generation
            # built for the old device set must never install
            self._swap_gen += 1
        self._seen_shapes.clear()
        self._warm_buckets.clear()
        _metrics.degradations_total.inc({"from": frm, "to": to})
        _metrics.pipeline_mode.set(float(level))
        oj = self.on_journal
        if oj is not None:
            oj(
                kind="ladder_move",
                severity="warning" if level > cur else "info",
                attrs={"from": frm, "to": to, "level": level},
            )

    def _note_fault(self, exc: BaseException, kind: str) -> None:
        """Account one classified fault and trip the breaker when due.
        Injected FaultErrors were already counted at the injection site
        (faults.hub.check) — only real errors add a metric here."""
        if not isinstance(exc, _faults.FaultError):
            _metrics.pipeline_faults_total.inc(
                {"site": getattr(exc, "site", "pipeline"), "kind": kind}
            )
        with self._lock:
            self._clean_batches = 0
            self._breaker_faults += 1
            trip = self._breaker_faults >= self.breaker_threshold
            lvl = self._ladder_level
        if trip and lvl < len(_MODE_NAMES) - 1:
            self._set_level(lvl + 1)

    def _note_clean_batch(self) -> None:
        """One healthy completion: clear the breaker after a short
        streak; at a degraded level a long-enough streak is the
        recovery probe — re-promote ONE level and keep watching."""
        if self._ladder_level == 0 and self._breaker_faults == 0:
            return  # steady state: one int read, no lock
        with self._lock:
            self._clean_batches += 1
            if self._clean_batches >= self.breaker_threshold:
                self._breaker_faults = 0
            lvl = self._ladder_level
            promote = lvl > 0 and self._clean_batches >= self.recover_after_clean
        if promote:
            self._set_level(lvl - 1)

    def _degraded_result(self, inf: "_InFlight"):
        """Shape-correct result for an unresolvable batch. NEVER an
        exception: every submitted flow gets a verdict (verdicts_lost
        stays 0) — FORWARD under FailOpen, DROP_DEGRADED (monitor
        reason 155) fail-closed. Flow tuples are no longer reachable
        (they live in the abandoned closure), so per-endpoint counters
        and DropNotify events are skipped; the batch still lands in
        verdicts_total{dropped_degraded} and drop_reasons_total."""
        b = max(0, inf.b)
        if self._fail_open:
            v = np.full(b, FORWARD, np.int8)
        else:
            v = np.full(b, DROP_DEGRADED, np.int8)
            if b:
                _metrics.drop_reasons_total.inc(
                    {"reason": "pipeline-degraded"}, float(b)
                )
        self._account_batch(v)
        red = np.zeros(b, bool)
        if inf.rev:
            return v, red, np.zeros(b, np.uint16)
        return v, red

    def _quarantine(self, inf: "_InFlight"):
        """Give up on a poisoned batch: advance the CT epoch under the
        lock so any sibling completing after us cannot create CT
        entries verdicted under the possibly-poisoned basis, drop the
        device-CT state, and resolve the handle with a degraded RESULT.
        The batch's pinned staging buffers are abandoned (NOT returned
        to the free-list — the wedged device program may still read
        them; the pool only ever re-issues buffers it owns, so the
        free-lists stay consistent and the GC reclaims the orphans once
        the program dies)."""
        with self._lock:
            dct = self._device_ct
            self._ct_epoch += 1
            ct_epoch = self._ct_epoch
            self._device_ct = None
            self._quarantined += 1
            quarantined = self._quarantined
            # the epoch the shadow bound to may be the poisoned one —
            # a swap mid-quarantine must not resurrect it
            self._swap_gen += 1
        # policyd-survive: before the zeroed device-CT is forgotten,
        # best-effort pull its established entries into the host table
        # (outside the lock — the pull can be slow or fail outright on
        # a quarantined device)
        rescue = None
        if dct is not None and self.conntrack is not None:
            rescue = self._rescue_device_ct(dct)
        oj = self.on_journal
        if oj is not None:
            oj(kind="quarantine", severity="error", attrs={
                "ct_epoch": ct_epoch,
                "quarantined": quarantined,
                "ct_rescue": "skipped" if rescue is None else rescue,
            })
        return self._degraded_result(inf)

    def _rescue_device_ct(self, state) -> Optional[Dict]:
        """Quarantine CT rescue (policyd-survive): pull the live
        device-CT entries into the host FlowConntrack so degraded/
        host-mode keeps serving established flows, and mark the next
        fresh device table to seed from the host CT (the re-upload half
        — re-promotion must not forget the flows a second time).

        Bounded (device_ct_rescue_limit) and classified: the device is
        the very thing being quarantined, so ANY failure — including an
        injected fault at the completion-pull site — means "rescue
        skipped, cold" (returns None), never a second escalation.
        Programmer errors still surface raw. Returns the
        {kept, expired} outcome for the quarantine journal event."""
        from .device_ct import pull_live_entries

        try:
            if _faults.hub.active:
                _faults.hub.check(_faults.SITE_COMPLETE)
            pulled = pull_live_entries(
                state, int(time.monotonic()),
                limit=self.device_ct_rescue_limit,
            )
            kept, expired = self.conntrack.restore_arrays(
                pulled["ka"], pulled["kb"], pulled["kc"], pulled["ttl"]
            )
        except BaseException as e:
            if _faults.classify(e) == _faults.KIND_ERROR:
                raise
            return None  # rescue skipped — quarantine proceeds cold
        if kept:
            _metrics.ct_restored_entries_total.inc(
                {"result": "kept"}, float(kept)
            )
            with self._lock:  # published to _process_device_ct readers
                self._device_ct_seed = True
        if expired:
            _metrics.ct_restored_entries_total.inc(
                {"result": "expired"}, float(expired)
            )
        return {"kept": int(kept), "expired": int(expired)}

    def _seed_device_ct(self):
        """Fresh device-CT state pre-populated from the host table (the
        re-upload half of the quarantine rescue; caller holds
        self._lock). Falls back to a zeros table on any classified
        failure — seeding is an optimization, never a correctness
        dependency."""
        from .device_ct import make_state, seed_state_from_host

        try:
            snap = self.conntrack.snapshot_arrays()
            return seed_state_from_host(
                snap["ka"], snap["kb"], snap["kc"], snap["ttl"],
                self._device_ct_bits, int(time.monotonic()),
                limit=self.device_ct_rescue_limit,
            )
        except BaseException as e:
            if _faults.classify(e) == _faults.KIND_ERROR:
                raise
            return make_state(self._device_ct_bits)

    def _finish_guarded(self, inf: "_InFlight"):
        """Run a batch's finish closure with classified error handling:

        - transient → bounded retry (retry_limit attempts, fresh
          Backoff sleeps). Sound because the closure's externally
          visible mutations (counters, CT create, events) all happen
          AFTER the host pull — the only device interaction that can
          fail transiently — so re-running from the top cannot
          double-account.
        - poisoned (or retries exhausted) → quarantine: degraded
          result, CT-epoch rollback, FIFO order preserved.
        - error (programmer/control) → returned as the exception for
          the caller to surface raw through PendingBatch.result(),
          exactly the pre-failsafe contract.

        Returns (value, exc) — exactly one is non-None."""
        attempt = 0
        bo: Optional[Backoff] = None
        while True:
            try:
                out = inf.finish()
            except BaseException as e:
                kind = _faults.classify(e)
                if kind == _faults.KIND_ERROR:
                    return None, e
                self._note_fault(e, kind)
                if (
                    kind == _faults.KIND_TRANSIENT
                    and attempt < self.retry_limit
                ):
                    attempt += 1
                    if bo is None:
                        bo = Backoff(
                            min_s=self.retry_min_s, max_s=self.retry_max_s,
                            jitter=False,
                        )
                    time.sleep(bo.duration())
                    continue
                return self._quarantine(inf), None
            self._note_clean_batch()
            return out, None

    # ------------------------------------------------------------------
    def rebuild(self, force: bool = False) -> Dict[Tuple[int, int], DatapathTables]:
        """Bring device state up to date. Incremental where possible:

        - identity churn ("rows" engine deltas) → policymap row patches
          in BOTH directions (n_seg × k verdicts instead of full sweeps)
        - rule appends / full recompiles → warm re-materialization
        - ipcache/prefilter moves → trie rebuild only (policymap kept)

        Returns {(direction, family): DatapathTables}.
        """
        oj = self.on_journal
        prev_basis = self._mat_basis if oj is not None else None
        tables = self._rebuild_locked(force)
        # served-basis move → one journal event, AFTER the lock is
        # released (the journal must never extend the rebuild critical
        # section the dispatch path competes with)
        if oj is not None and self._mat_basis != prev_basis:
            basis = self._mat_basis
            oj(kind="rebuild", attrs={
                "prev_basis": None if prev_basis is None else list(prev_basis),
                "basis": None if basis is None else list(basis),
                "policy_epoch": self._policy_epoch,
                "generation": self._plan.generation,
            })
        return tables

    def _rebuild_locked(
        self, force: bool = False
    ) -> Dict[Tuple[int, int], DatapathTables]:
        with self._lock:
            self._refresh_mesh_locked()
            # Capture versions BEFORE reading the sources: a concurrent
            # mutation mid-build then triggers one extra rebuild rather
            # than being silently marked materialized.
            trie_versions = (self.ipcache.version, self.prefilter.revision)
            delta_target = self.engine.delta_seq
            compiled, device = self.engine.snapshot()
            # one delta fetch per rebuild, shared by the placed-copy
            # patcher and the materialized-state router — both replay
            # FINAL-state values, so re-application across rebuilds
            # (e.g. a non-advancing cursor under a pending epoch swap)
            # is idempotent
            pending_deltas = self.engine.deltas_since(self._last_delta_seq)
            # 2D plan: the materializer sweeps/patches read an ident-
            # sharded sel_match (generation-cached; the engine's own
            # copy is untouched)
            device = self._ident_placed_device(device, pending_deltas)
            delta_target = max(delta_target, self.engine.delta_seq)
            ep_sig = tuple(self._endpoints)
            # captured before the trie block updates _trie_versions;
            # feeds the conntrack invalidation below
            basis_moved = trie_versions != self._trie_versions

            mat_fresh = False
            saw_row_event = False
            saw_rule_delta = False
            swap_pending = False
            if force or not self._mat or self._mat_sig != ep_sig:
                self._materialize_both(compiled, device)
                mat_fresh = True
            else:
                routed = self._route_deltas(compiled, device, pending_deltas)
                if routed is None:
                    # full rebuild needed (log truncation, a "full"
                    # recompile event, or a rule delta the column patch
                    # cannot express). With EpochSwap on, build it on
                    # the shadow thread and KEEP SERVING the current
                    # generation — the install advances the delta
                    # cursor itself, so nothing here commits.
                    if self._epoch_swap and self._kick_shadow_build(
                        compiled, device, ep_sig, delta_target
                    ):
                        swap_pending = True
                    else:
                        # warm jit, shape-bucketed — the fast full path
                        self._materialize_both(compiled, device)
                        mat_fresh = True
                else:
                    saw_row_event, saw_rule_delta = routed
            if not swap_pending:
                self._mat_sig = ep_sig
                self._last_delta_seq = delta_target

            # policyd-sparse: when the ONLY trie trigger is ipcache
            # churn (prefilter untouched, row basis stable), patch the
            # placed trie tensors in place from the ipcache delta ring
            # instead of rebuilding — O(delta) node rows / dense spans
            # uploaded. Success commits _trie_versions, so the full
            # rebuild below sees a clean basis and skips; any failure
            # (ring truncation, pool exhaustion, live deny trie,
            # elision violation) leaves the versions stale and falls
            # through to the classic rebuild.
            if (
                self._sparse_deltas
                and self._trie_patch is not None
                and not force
                and not mat_fresh
                and not saw_row_event
                and self._tries is not None
                and self._tables
                and len(self._trie_versions) == 2
                and trie_versions != self._trie_versions
                and trie_versions[1] == self._trie_versions[1]
            ):
                self._patch_tries_locked(compiled, trie_versions)

            # Tries: rebuilt when their sources move, when the row basis
            # was re-established, or when any row event could have
            # changed an ipcache row mapping.
            if (
                force
                or self._tries is None
                or trie_versions != self._trie_versions
                or mat_fresh
                or saw_row_event  # any row move can re-point trie targets
                or not self._tables
            ):
                _, pf_cidrs = self.prefilter.dump()
                # empty-set flags first: both families' fusion gates
                # read them (an empty deny set skips the walk entirely)
                self._pf_empty = (
                    not any(":" not in c for c in pf_cidrs),
                    not any(":" in c for c in pf_cidrs),
                )
                # IPv6: stride-8 tries with the shared prefix elided
                # (pod allocations live under one /48-/64 — compare
                # those bytes once instead of walking them)
                pf6_list = [(c, 0) for c in pf_cidrs if ":" in c]
                ip6_list = [
                    (cidr, row)
                    for cidr, e in self.ipcache.items()
                    if ":" in cidr
                    and (row := compiled.id_to_row.get(e.identity))
                    is not None
                ]
                # policyd-sparse: with no live v6 deny trie, build the
                # identity trie through a patchable host mirror (pow2
                # node-pool headroom) so ipcache churn can patch it in
                # place. The OFF path — and any fused build — compiles
                # the exact classic layout.
                p6_patch = (
                    PatchableElidedTrie(ip6_list, ipv6=True)
                    if self._sparse_deltas and self._pf_empty[1]
                    else None
                )
                ip6 = (
                    p6_patch.arrays()
                    if p6_patch is not None
                    else build_trie_elided(ip6_list, ipv6=True)
                )
                # fused deny+identity v6 walk (one elided pass, both
                # answers) — built only while the deny stage is live
                merged6_list = (
                    merge_trie_entries(ip6_list, pf6_list, ipv6=True)
                    if not self._pf_empty[1]
                    else None
                )
                placeholder6 = (
                    np.zeros((1, 256), np.int32),
                    np.zeros((1, 256), np.int32),
                    np.zeros(0, np.int32),
                )
                if merged6_list is not None:
                    merged6 = build_trie_elided(merged6_list, ipv6=True)
                    self._v6_fused = True
                    # the fused trie fully covers the deny stage (same
                    # reasoning as the v4 pf_wide elision below): don't
                    # build/upload the standalone deny trie
                    pf6 = placeholder6
                else:
                    pf6 = build_trie_elided(pf6_list, ipv6=True)
                    merged6 = placeholder6
                    self._v6_fused = False
                # IPv4 rides the wide (dense-16-bit-first) tries
                pf_wide = build_wide_trie(
                    (c, 0) for c in pf_cidrs if ":" not in c
                )
                ip4_list = [
                    (cidr, row)
                    for cidr, e in self.ipcache.items()
                    if ":" not in cidr
                    and (row := compiled.id_to_row.get(e.identity)) is not None
                ]
                # v4 mirror only when the flat 16+16 layout holds (the
                # 16-8-8 pointer layout is not patched → None)
                p4_patch = (
                    make_patchable_wide(ip4_list)
                    if self._sparse_deltas and self._pf_empty[0]
                    else None
                )
                ip_wide = (
                    p4_patch.arrays()
                    if p4_patch is not None
                    else build_wide_trie(ip4_list)
                )
                # fused deny+identity walk: only worth building when
                # the deny stage is live and both layouts are flat
                merged = (
                    merge_flat_tries(ip_wide, pf_wide)
                    if not self._pf_empty[0]
                    else None
                )
                if merged is None:
                    merged = (
                        np.zeros(1, np.int32),
                        np.zeros(1, np.int32),
                        np.zeros((1, 1), np.int32),
                        np.zeros((1, 1), np.int32),
                    )
                else:
                    # the fused table fully covers the deny stage, so
                    # the standalone deny trie would never be read —
                    # don't upload it (placeholders keep the pytree
                    # shape-stable for the jit cache)
                    pf_wide = (
                        np.zeros(1, np.int32),
                        np.zeros(1, np.int32),
                        np.zeros((1, 1), np.int32),
                        np.zeros((1, 1), np.int32),
                    )
                world_row = compiled.id_to_row.get(ID_WORLD)
                if world_row is None:
                    raise RuntimeError("reserved:world identity has no device row")
                # sharding-aware upload (ops/lpm.py place_table):
                # tries are replicated across the verdict mesh — every
                # flow shard walks the whole trie. The device_put runs
                # under _lock BY DESIGN: rebuild() is the control
                # plane's table swap, and publishing a trie ref before
                # its device buffers exist would hand the verdict path
                # a half-placed table (EpochSwap is the stall-free
                # alternative; this is the non-shadow path).
                tsh = self._table_sharding
                self._tries = (
                    tuple(
                        place_table(a, tsh)  # policyd-lint: disable=LOCK002
                        for a in (*pf_wide, *ip_wide, *merged)
                    ),
                    tuple(place_table(a, tsh) for a in (*pf6, *ip6, *merged6)),  # policyd-lint: disable=LOCK002
                    place_table(np.int32(world_row), tsh),  # policyd-lint: disable=LOCK002
                )
                self._trie_versions = trie_versions
                self._trie_patch = (
                    {4: p4_patch, 6: p6_patch} if self._sparse_deltas else None
                )

            # Conntrack invalidation: established-flow bypass is only
            # sound while the verdict basis that admitted the flow still
            # holds. ANY basis move — policy re-materialization (rule
            # changes, endpoint set), identity row churn, ipcache remap,
            # prefilter revision — flushes the table, so revoked rules,
            # remapped peer IPs, and new deny prefixes apply to
            # established flows on their next packet (the reference
            # scrubs CT after regeneration / ipcache changes; we take
            # the conservative whole-table flush — one re-verdict per
            # flow is a single batched dispatch). Uses the versions
            # captured BEFORE the reads so a mutation landing mid-build
            # flushes again on the next rebuild rather than slipping by.
            # saw_rule_delta: a column patch is still a rule change —
            # a revoked rule must not keep admitting its established
            # flows just because the policymap was patched in place
            # rather than re-materialized.
            if mat_fresh or saw_row_event or saw_rule_delta or basis_moved:
                # policyd-survive restore hold (one-shot): on the first
                # rebuild after a verified CT restore, the fresh
                # materialization builds from the restored tables — the
                # basis that admitted the restored entries still holds,
                # so greeting it with the usual flush would cold-flush
                # exactly what restore just placed. Revision-pinned: a
                # policy mutation racing in before this rebuild bumps
                # the compiled revision and voids the hold. Consumed
                # below; every later trigger flushes as always.
                if self._ct_restore_hold != compiled.revision:
                    self._ct_flush_pending = True
            if self._ct_flush_pending:
                if _faults.hub.active:
                    # before the flush: a retried rebuild re-runs this
                    # whole block (pending stays set), so nothing is
                    # half-advanced
                    _faults.hub.check(_faults.SITE_CT_EPOCH)
                if self.conntrack is not None:
                    self.conntrack.flush()
                # a basis move while batches are in flight: their
                # completion halves must not create CT entries
                # verdicted under the old basis
                self._ct_epoch += 1
                self._device_ct = None  # zeroed on next use
                self._ct_flush_pending = False

            # LB tables: deterministic per-flow backend selection means
            # backend churn changes the translated CT key (natural
            # miss), but entries created while a flow was NOT
            # translated (pre-service, or post-delete) would bypass the
            # new service table — so any LB move also flushes CT.
            if self.lb is not None and self.lb.version != self._lb_version:
                lb_ver = self.lb.version
                self._lb_tables = self.lb.build_device()
                self._lb_version = lb_ver
                # restore hold covers this trigger too: restored
                # services come from the SAME state.json snapshot the
                # CT entries were saved with, so the restored entries
                # were translated under exactly these service tables
                if self._ct_restore_hold != compiled.revision:
                    if self.conntrack is not None:
                        self.conntrack.flush()
                    self._ct_epoch += 1
                    self._device_ct = None
            # the one-shot hold is spent once both flush triggers above
            # have seen it
            self._ct_restore_hold = None
            # Served-basis commit (policyd-survive): AFTER the flush
            # blocks above, so a concurrent CT-snapshot writer can
            # never pair surviving old-basis entries with the new
            # stamp. A pending shadow swap keeps serving the old
            # generation — its basis stays until the install's flush
            # publishes through here.
            if not swap_pending:
                self._mat_basis = (
                    compiled.revision, compiled.identity_version,
                    compiled.vocab_version,
                )

            assert self._tries is not None and self._mat
            v4, v6, world = self._tries
            # Build complete, then assign once: _dispatch reads
            # self._tables without the lock and must never observe a
            # partially-populated dict.
            tables: Dict[Tuple[int, int], object] = {}
            for direction, mat in self._mat.items():
                pm = self._replicated_policymap(direction, mat.tables)
                tables[(direction, 4)] = WideDatapathTables(
                    pf_root_info=v4[0],
                    pf_root_child=v4[1],
                    pf_sub_child=v4[2],
                    pf_sub_info=v4[3],
                    ip_root_info=v4[4],
                    ip_root_child=v4[5],
                    ip_sub_child=v4[6],
                    ip_sub_info=v4[7],
                    merged_root_info=v4[8],
                    merged_root_child=v4[9],
                    merged_sub_child=v4[10],
                    merged_sub_info=v4[11],
                    world_row=world,
                    policymap=pm,
                )
                tables[(direction, 6)] = DatapathTables(
                    pf_child=v6[0],
                    pf_info=v6[1],
                    pf_common=v6[2],
                    ip_child=v6[3],
                    ip_info=v6[4],
                    ip_common=v6[5],
                    merged_child=v6[6],
                    merged_info=v6[7],
                    merged_common=v6[8],
                    world_row=world,
                    policymap=pm,
                )
            self._tables = tables
            # flows-axis size, NOT total device count: bucket-ladder
            # rung rounding and chunk spans split over "flows" only
            # (1D: the two are equal; 2D: ndev = devices / ident)
            ndev = self._plan.flows_size
            # attribution element: present only when EVERY direction's
            # state carries a rule table (a race with a rule mutation
            # can leave one direction plain for a cycle — the racing
            # delta re-materializes on the next rebuild)
            attrib_el = None
            if self._attrib_requested:
                rtabs = {}
                for direction, mat in self._mat.items():
                    if mat.rule_tab is None:
                        rtabs = None
                        break
                    rtabs[direction] = self._replicated_rule_tab(
                        direction, mat.rule_tab
                    )
                if rtabs:
                    attrib_el = (rtabs, self._attrib_n_rules)
            # prefilter shed element (policyd-overload): the coarse
            # [identity, proto/port-class] deny-for-sure table, compiled
            # from the ingress policymap host mirror and placed with the
            # same table sharding as the tries so it rides the MeshPlan.
            # Cached across rebuilds that change neither the policymap
            # basis nor the placement; Prefilter off publishes None and
            # the shed kernels never trace.
            shed_el = None
            if self._shed_requested:
                mat_in = self._mat.get(TRAFFIC_INGRESS)
                if mat_in is not None:
                    gen = self._plan.generation
                    if (
                        self._shed_cache is None
                        or self._shed_cache[0] != gen
                        or mat_fresh
                        or saw_row_event
                        or saw_rule_delta
                    ):
                        shed_tab = compile_shed_table(
                            mat_in.allow_nc, mat_in.ep_slots
                        )
                        # placed under _lock by design: same publish-
                        # whole-tables invariant as the trie upload
                        self._shed_cache = (
                            gen,
                            place_table(shed_tab, self._table_sharding),  # policyd-lint: disable=LOCK002
                        )
                    shed_el = self._shed_cache[1]
            else:
                self._shed_cache = None
            self._dp_state = (
                tables, self._pf_empty, self._v6_fused,
                self._flow_sharding, ndev, attrib_el, self._plan.is_2d,
                shed_el,
            )
            # per-device table-bytes telemetry: under a 2D plan the
            # identity tables split by the ident factor (within the
            # last shard's padding); replicated/1D reports full bytes
            ident = self._plan.ident_size if self._plan.is_2d else 1
            pm_bytes = sum(
                int(np.prod(m.tables.id_bits.shape)) * 4
                for m in self._mat.values()
            )
            rt_bytes = sum(
                int(np.prod(m.rule_tab.shape)) * 4
                for m in self._mat.values()
                if m.rule_tab is not None
            )
            _metrics.sharded_table_bytes.set(
                float(pm_bytes // ident), {"family": "policymap"}
            )
            _metrics.sharded_table_bytes.set(
                float(rt_bytes // ident), {"family": "rule_tab"}
            )
            # policyd-prof memory ledger: every device-resident table
            # family under its placement (same per-device convention as
            # sharded_table_bytes; the tries are always replicated —
            # every flow shard walks the whole trie)
            ident_placement = (
                "ident-sharded" if self._plan.is_2d else "replicated"
            )
            _metrics.device_table_bytes.set(
                float(pm_bytes // ident),
                {"family": "policymap", "placement": ident_placement},
            )
            _metrics.device_table_bytes.set(
                float(rt_bytes // ident),
                {"family": "rule_tab", "placement": ident_placement},
            )
            sel = getattr(device, "sel_match", None)
            if sel is not None:
                _metrics.device_table_bytes.set(
                    float(int(getattr(sel, "nbytes", 0)) // ident),
                    {"family": "sel_match", "placement": ident_placement},
                )
            if self._tries is not None:
                trie_bytes = sum(
                    int(getattr(a, "nbytes", 0))
                    for leaves in self._tries[:2]
                    for a in leaves
                )
                _metrics.device_table_bytes.set(
                    float(trie_bytes),
                    {"family": "lpm_trie", "placement": "replicated"},
                )
            if self.counters.shape[0] != len(self._endpoints):
                self.counters = np.zeros((len(self._endpoints), 3), np.int64)
            return self._tables

    def _route_deltas(
        self, compiled, device, deltas
    ) -> Optional[Tuple[bool, bool]]:
        """Apply the engine delta log to the materialized state IN
        PLACE (the O(delta) refresh path). Held-lock helper for
        rebuild. Returns ``(saw_row_event, saw_rule_delta)`` on
        success, or None when the
        log demands a full re-materialization: a truncated ring, a
        "full" recompile event, or a rule delta the column patch cannot
        express (slot growth, row-bucket crossing, attribution deletes
        — every later rule's index shifts, so the per-cell rule table
        cannot be patched).

        Ordering note: row patches rewrite whole identity ROWS and
        column patches whole endpoint COLUMNS, and both sweep against
        the FINAL (compiled, device) snapshot — so replaying rows
        first and the coalesced rule-column union second lands every
        touched cell on its final value regardless of how the log
        interleaved them."""
        if deltas is None:
            return None
        if any(k == "full" for _, k, _ in deltas):
            return None
        if self._attrib_requested and any(
            k == "rules" and p and p[0] == "del" for _, k, p in deltas
        ):
            if any(m.rule_nc is not None for m in self._mat.values()):
                return None
        t0 = time.perf_counter()
        ao, nr = self._attrib_origins(compiled)
        saw_row_event = False
        touched_sids: set = set()
        row_events: list = []
        for _seq, kind, payload in deltas:
            if kind == "rows":
                # Coalesce across log entries, one patch per direction
                # below — the engine-side _set_rows2 discipline applied
                # at the pipeline layer. The stale-snapshot scan and
                # the verdict re-sweep are per-CALL costs, so a churny
                # tick (many row deltas between rebuilds) must replay
                # as one patch, not one per log entry; last event per
                # row wins inside patch_identity_rows, which preserves
                # log order.
                row_events.extend(payload)
                # Any row event (add OR release) can change what an
                # ipcache entry resolves to — e.g. a released id being
                # re-allocated onto a tombstoned row, or an add
                # resolving a previously-unmapped entry — so the tries
                # must follow every row move.
                saw_row_event |= bool(payload)
            elif kind == "cols":
                # (sel_lo, sel_hi, touched rows): sel_match column
                # scatter already applied by the engine (and replayed
                # onto the ident-placed copy by _ident_placed_device).
                # The materialized policymap consumes the selector
                # change through the PAIRED "rules" event's column
                # re-sweep, so there is nothing to route here.
                pass
            else:  # "rules": ("add"|"del", (subject_sid, ...))
                touched_sids.update(payload[1])
        if row_events:
            for direction, mat in self._mat.items():
                # patch the mesh-placed copies through the SAME scatter
                # (PlacedTables holder) so 2D/replicated placement
                # survives the O(delta) path without a re-place
                placed = self._placed_holder(direction, mat)
                patch_identity_rows(
                    mat, compiled, device, row_events,
                    attrib_origin=ao[direction == TRAFFIC_INGRESS],
                    n_rules=nr, placed=placed,
                )
                self._rekey_placed(direction, mat, placed)
        if touched_sids:
            for direction, mat in self._mat.items():
                placed = self._placed_holder(direction, mat)
                if not patch_endpoints_state(
                    mat, compiled, device, sorted(touched_sids),
                    attrib_origin=ao[direction == TRAFFIC_INGRESS],
                    n_rules=nr, placed=placed,
                ):
                    # partial patches are harmless: every cell they
                    # wrote already holds its final value, and the
                    # full rebuild replaces the state wholesale
                    return None
                self._rekey_placed(direction, mat, placed)
            # appends grow the rule set: keep the completion half's
            # rule-index → origin map in step with the patched tables
            if nr:
                self._attrib_n_rules = nr
                self._attrib_names = self.engine.repo.origin_names()
        if saw_row_event or touched_sids:
            _metrics.engine_refresh_seconds.observe(
                time.perf_counter() - t0, {"kind": "delta"}
            )
        return saw_row_event, bool(touched_sids)

    def _replicated_policymap(self, direction: int, pm: PolicymapTables):
        """Mesh-placed copy of one direction's policymap, cached on the
        source object AND the plan generation: row patches (which swap
        the arrays) and placement changes (ladder demotion/re-promotion,
        runtime 2D toggles) re-place, while steady-state rebuilds reuse
        the committed copy. Under a 2D plan the identity axis shards
        (shard_tables_ident); 1D replicates, exactly as before."""
        plan = self._plan
        if plan.table_sharding is None:
            return pm
        gen, src, placed = self._placed_pm.get(direction, (-1, None, None))
        if src is pm and gen == plan.generation:
            return placed
        # identity-cached: the callee's device_put fires only when a
        # rebuild swapped the policymap (same publish-whole-tables
        # invariant and same _lock as the trie upload in rebuild)
        if plan.is_2d:
            placed = shard_tables_ident(  # policyd-lint: disable=LOCK002
                pm, plan.ident_sharding, plan.table_sharding
            )
        else:
            placed = replicate_tables(pm, plan.table_sharding)  # policyd-lint: disable=LOCK002
        self._placed_pm[direction] = (plan.generation, pm, placed)
        return placed

    def _replicated_rule_tab(self, direction: int, rt):
        """Mesh-placed copy of one direction's attribution rule table —
        the _replicated_policymap pattern (generation-keyed). 1D keeps
        it whole on every device the flow shards land on; the 2D plan
        row-shards it like id_bits (the rule gather becomes the same
        ident-axis one-hot contraction)."""
        plan = self._plan
        if plan.table_sharding is None:
            return rt
        gen, src, placed = self._placed_rt.get(direction, (-1, None, None))
        if src is rt and gen == plan.generation:
            return placed
        # identity-cached: the transfer fires only when a rebuild
        # swapped the rule table (same cadence + same _lock as the
        # sibling _replicated_policymap's replicate_tables placement)
        sh = plan.ident_sharding if plan.is_2d else plan.table_sharding
        placed = jax.device_put(rt, sh)  # policyd-lint: disable=LOCK002
        self._placed_rt[direction] = (plan.generation, rt, placed)
        return placed

    def _ident_placed_device(self, device, deltas=None):
        """DevicePolicy view with sel_match re-placed under the 2D
        plan's ident sharding (generation-cached on the source array).
        Non-2D plans return the snapshot untouched. The engine's own
        device object is never mutated — the pipeline's sweeps just
        read through a sharded copy so the [N, S/32] selector-match
        matrix also stops replicating at scale.

        With SparseDeltas on, a source change whose gap is covered by
        the engine delta log (``deltas``) PATCHES the cached placed
        copy — O(delta) row/column scatters that preserve the ident
        sharding (GSPMD propagates the operand's sharding through
        ``.at[].set``) — instead of re-placing the full matrix; the
        placed jit caches survive because the placement never moves."""
        plan = self._plan
        if not plan.is_2d:
            return device
        gen, src, placed = self._placed_sel
        if src is not device.sel_match or gen != plan.generation:
            patched = (
                self._patch_placed_sel(device, deltas)
                if self._sparse_deltas
                else None
            )
            if patched is None:
                placed = jax.device_put(  # policyd-lint: disable=LOCK002
                    device.sel_match, plan.ident_sharding
                )
            else:
                placed = patched
            self._placed_sel = (plan.generation, device.sel_match, placed)
        return device.replace(sel_match=placed)

    def _patch_placed_sel(self, device, deltas):
        """Replay the delta window onto the cached ident-placed
        sel_match copy (policyd-sparse). Returns the patched placed
        array, or None when the gap is not patchable — no cached copy,
        plan generation moved, truncated/absent log, a "full" recompile
        in the window, a shape move (row bucket or selector word
        growth), or a mirror-bounds miss — and the caller re-places
        wholesale. Values are FINAL-state reads from the engine's host
        mirror (sel_match_rows), so replay is idempotent and ordering
        against concurrent engine mutation self-heals on the next
        rebuild, exactly like the in-place compiled snapshot."""
        plan = self._plan
        gen, _src, placed = self._placed_sel
        if placed is None or gen != plan.generation:
            return None
        if not deltas:  # None (truncated) or an un-logged source move
            return None
        if getattr(placed, "shape", None) != device.sel_match.shape:
            return None
        row_set: set = set()
        col_events: list = []
        for _seq, kind, payload in deltas:
            if kind == "rows":
                row_set.update(int(r) for r, _ident, _live in payload)
            elif kind == "cols":
                col_events.append(payload)
            elif kind != "rules":  # "full" (or unknown): re-place
                return None
        if not row_set and not col_events:
            # source object moved with no sel_match event in the
            # window — the gap is not explained by the log; re-place
            return None
        nbytes = 0
        nscat = 0
        if row_set:
            rows = sorted(row_set)
            vals = self.engine.sel_match_rows(rows)
            if vals is None or vals.shape[1] != placed.shape[1]:
                return None
            placed = patch_selector_rows(placed, rows, vals)
            nbytes += len(rows) * 4 + int(vals.nbytes)
            nscat += 1
        for sel_lo, sel_hi, touched in col_events:
            # rows already rewritten whole by the row patch above carry
            # their final column bits — skip them here
            rows = [int(r) for r in touched if int(r) not in row_set]
            if not rows:
                continue
            words = selector_word_window(int(sel_lo), int(sel_hi))
            if words.size == 0 or int(words.max()) >= placed.shape[1]:
                return None
            vals = self.engine.sel_match_rows(rows, words)
            if vals is None:
                return None
            placed = patch_selector_cols(placed, rows, words, vals)
            nbytes += len(rows) * 4 + int(vals.nbytes) + int(words.nbytes)
            nscat += 1
        if nscat:
            # transfer-ledger attribution for the column/row patches:
            # O(k) logical bytes where the dense re-place moved the
            # full [N, S/32] matrix (control-plane cadence, counted
            # unconditionally — rebuilds are rare and the delta is
            # the O(k) transfer evidence)
            _metrics.device_transfer_bytes_total.inc(
                {"direction": "h2d"}, float(nbytes)
            )
            _metrics.device_transfers_total.inc(
                {"direction": "h2d"}, float(nscat)
            )
        return placed

    def _patch_tries_locked(self, compiled, trie_versions) -> bool:
        """Apply the ipcache delta window to the placed identity-trie
        tensors in place (policyd-sparse). On success commits
        ``_trie_versions`` (the full-rebuild trigger then sees a clean
        basis) and returns True; any non-patchable condition — ring
        truncation, a live deny trie for a touched family, an
        unsupported layout, pool exhaustion, an elision violation, a
        device/mirror shape mismatch — returns False with the versions
        left stale, and the classic full rebuild runs. Host mirrors
        mutated before a mid-window failure are discarded by that
        rebuild, so partial application never leaks."""
        deltas = self.ipcache.deltas_since(self._trie_versions[0])
        if not deltas:  # None (truncated) or un-logged version move
            return False
        patch = self._trie_patch or {}
        ops = []  # staged (mirror, family, cidr, row|None)
        for _ver, cidr, _old_ident, new_ident in deltas:
            fam = 6 if ":" in cidr else 4
            if not self._pf_empty[0 if fam == 4 else 1]:
                # the fused deny+identity trie is live for this family;
                # it is never patched — rebuild keeps it coherent
                return False
            mirror = patch.get(fam)
            if mirror is None:
                return False  # unsupported layout (16-8-8 wide v4)
            row = (
                compiled.id_to_row.get(new_ident)
                if new_ident is not None
                else None
            )
            # identity without a device row == absent from the trie
            ops.append((mirror, cidr, row))
        napplied = {4: 0, 6: 0}
        for mirror, cidr, row in ops:
            ok = (
                mirror.insert(cidr, row)
                if row is not None
                else mirror.delete(cidr)
            )
            if not ok:
                return False
            napplied[6 if ":" in cidr else 4] += 1
        v4, v6, world = self._tries
        nbytes = 0
        p4 = patch.get(4)
        if p4 is not None and p4.dirty:
            out = p4.flush(v4[4], v4[5], v4[6], v4[7])
            if out is None:
                return False
            (ri, rc, sc, si), nb = out
            v4 = (*v4[:4], ri, rc, sc, si, *v4[8:])
            nbytes += nb
        p6 = patch.get(6)
        if p6 is not None and p6.dirty:
            out = p6.flush(v6[3], v6[4])
            if out is None:
                return False
            (child, info), nb = out
            v6 = (*v6[:3], child, info, v6[5], *v6[6:])
            nbytes += nb
        self._tries = (v4, v6, world)
        self._trie_versions = trie_versions
        for fam in (4, 6):
            if napplied[fam]:
                _metrics.lpm_trie_patches_total.inc(
                    {"family": str(fam)}, float(napplied[fam])
                )
        if nbytes:
            _metrics.device_transfer_bytes_total.inc(
                {"direction": "h2d"}, float(nbytes)
            )
            _metrics.device_transfers_total.inc({"direction": "h2d"}, 1.0)
        return True

    def _placed_holder(self, direction: int, mat) -> Optional[PlacedTables]:
        """PlacedTables view of the direction's CURRENT placed-table
        cache entries, for the O(delta) patch paths to scatter into.
        None when nothing valid is cached (unplaced pipeline, source
        swap, or plan-generation move) — the next rebuild re-places
        wholesale instead."""
        plan = self._plan
        if plan.table_sharding is None:
            return None
        gen, src, ppm = self._placed_pm.get(direction, (-1, None, None))
        if src is not mat.tables or gen != plan.generation:
            return None
        holder = PlacedTables(tables=ppm)
        rgen, rsrc, prt = self._placed_rt.get(direction, (-1, None, None))
        if (
            mat.rule_tab is not None
            and rsrc is mat.rule_tab
            and rgen == plan.generation
        ):
            holder.rule_tab = prt
        return holder

    def _rekey_placed(self, direction: int, mat, holder) -> None:
        """Re-key the placed caches after an in-place patch: the patch
        swapped both the host-materialized arrays AND the placed copies
        (same scatter), so the cache entries move to the new source
        objects without any re-place transfer."""
        if holder is None:
            return
        plan = self._plan
        self._placed_pm[direction] = (
            plan.generation, mat.tables, holder.tables
        )
        if holder.rule_tab is not None and mat.rule_tab is not None:
            self._placed_rt[direction] = (
                plan.generation, mat.rule_tab, holder.rule_tab
            )

    def _attrib_origins(self, compiled):
        """({ingress_bool: AttribTables|None}, n_rules) for the current
        rebuild — all-None when attribution is off, the engine carries
        no compile state (snapshot-restored), or a rule mutation raced
        the (compiled, device) snapshot (the racing delta forces
        re-materialization on the next rebuild, which self-heals)."""
        off = {True: None, False: None}
        if not self._attrib_requested:
            return off, 0
        ai = self.engine.attribution(True, expect_revision=compiled.revision)
        ae = self.engine.attribution(False, expect_revision=compiled.revision)
        if ai is None or ae is None:
            return off, 0
        return {True: ai[0], False: ae[0]}, ai[1]

    def _materialize_both(self, compiled, device) -> None:
        ao, nr = self._attrib_origins(compiled)
        self._attrib_n_rules = nr
        self._attrib_names = (
            self.engine.repo.origin_names() if nr else []
        )
        # a full sweep is the slowest thing rebuild() can do — with the
        # watchdog armed, register it so a wedged device compile shows
        # up as a classified stall instead of a silent hang
        wd = self._watchdog
        if wd is not None:
            with wd.watching("compile"):
                self._mat = self._build_mats(
                    compiled, device, self._endpoints, ao, nr
                )
        else:
            self._mat = self._build_mats(
                compiled, device, self._endpoints, ao, nr
            )

    @staticmethod
    def _build_mats(compiled, device, endpoints, ao, nr):
        """Both directions' full sweeps from one frozen (compiled,
        device) snapshot. Static and self-free on purpose: the
        epoch-swap shadow thread runs this OFF the pipeline lock, so
        it must not read mutable pipeline state."""
        return {
            TRAFFIC_INGRESS: materialize_endpoints_state(
                compiled, device, endpoints, ingress=True,
                attrib_origin=ao[True], n_rules=nr,
            ),
            TRAFFIC_EGRESS: materialize_endpoints_state(
                compiled, device, endpoints, ingress=False,
                attrib_origin=ao[False], n_rules=nr,
            ),
        }

    # -- policyd-delta: epoch-swapped shadow rebuilds ------------------
    def set_epoch_swap(self, on: bool) -> None:
        """Toggle epoch-swapped full rebuilds (the EpochSwap runtime
        option). Turning it off also abandons any in-flight shadow
        build — the next rebuild that needs a full sweep runs it
        synchronously again."""
        with self._lock:
            on = bool(on)
            if on == self._epoch_swap:
                return
            self._epoch_swap = on
            if not on:
                self._swap_gen += 1

    def set_sparse_deltas(self, on: bool) -> None:
        """Toggle O(k) sparse device-table deltas (the SparseDeltas
        runtime option). ON takes effect on the next rebuild: the
        patchable trie builders are constructed alongside the full trie
        compile, and subsequent ipcache / selector deltas patch device
        tensors in place. OFF drops the patch state and the placed
        sel_match cache so the next rebuild re-places and re-merges
        from scratch — the exact pre-option arrays and programs (the
        patch kernels are never traced)."""
        with self._lock:
            on = bool(on)
            if on == self._sparse_deltas:
                return
            self._sparse_deltas = on
            self._trie_patch = None
            self._placed_sel = (0, None, None)
            # drop the trie tensors on BOTH transitions: ON must
            # construct the patchable mirrors alongside a fresh full
            # compile (they mirror the device arrays row for row), OFF
            # must shed the ON path's pow2 node-pool headroom and
            # rebuild exact-sized pre-option tries
            self._tries = None

    def wait_epoch_swap(self, timeout: float = 60.0) -> bool:
        """Block until no shadow build is in flight (tests'
        convergence helper; the daemon never calls this). Returns False
        on timeout. The installed generation becomes dispatch-visible
        on the NEXT rebuild() — call it after this returns."""
        t = self._shadow_thread
        if t is not None and t.is_alive():
            t.join(timeout)
            return not t.is_alive()
        return True

    @property
    def policy_epoch(self) -> int:
        """Shadow-built generations swapped in since start (telemetry:
        rides /healthz next to the failsafe state)."""
        return self._policy_epoch

    def _kick_shadow_build(
        self, compiled, device, ep_sig, delta_target
    ) -> bool:
        """Start (or keep watching) a shadow materialization bound to
        the current basis generation. Held-lock helper for rebuild.
        Returns True while a shadow is (now) running — the caller keeps
        serving the old generation — or False when it must fall back to
        the synchronous full path (a previous shadow died on a
        transient/poisoned fault; programmer errors re-raise here)."""
        exc = self._shadow_exc
        if exc is not None:
            self._shadow_exc = None
            if _faults.classify(exc) == _faults.KIND_ERROR:
                raise exc
            return False
        t = self._shadow_thread
        if t is not None and t.is_alive():
            return True  # one shadow at a time; converge via the log
        gen = self._swap_gen
        ao, nr = self._attrib_origins(compiled)
        names = self.engine.repo.origin_names() if nr else []
        t = threading.Thread(
            target=self._shadow_build,
            args=(
                compiled, device, list(self._endpoints), ep_sig,
                delta_target, gen, ao, nr, names,
            ),
            name="policyd-shadow-mat",
            daemon=True,
        )
        self._shadow_thread = t
        t.start()
        return True

    def _shadow_build(
        self, compiled, device, endpoints, ep_sig, delta_target, gen,
        ao, nr, names,
    ) -> None:
        """Shadow-thread body: the expensive sweeps run OFF the
        pipeline lock (dispatches and O(delta) rebuilds keep going
        against the old generation), then the finished generation
        installs under it. Deltas that landed while the sweep ran are
        NOT lost: the install rewinds the cursor to the kick-time
        target, so the next rebuild replays them against the new
        generation (row/column patches compute from the then-current
        snapshot — eventually consistent, same contract as any
        in-flight window)."""
        try:
            mats = self._build_mats(compiled, device, endpoints, ao, nr)
        # The broad catch is the point: ANY shadow failure must park in
        # _shadow_exc so the next kick can route it through
        # faults.classify (KIND_ERROR re-raises there, transients fall
        # back to a synchronous build) — a raise on this daemon thread
        # would vanish.  # policyd-lint: disable=ROBUST001
        except BaseException as e:
            with self._lock:
                if self._swap_gen == gen:
                    self._shadow_exc = e
            return
        with self._lock:
            if self._swap_gen != gen:
                return  # basis moved under us: abandon this epoch
            self._mat = mats
            self._mat_sig = ep_sig
            self._last_delta_seq = delta_target
            self._attrib_n_rules = nr
            self._attrib_names = names
            # rows may have moved with the rebuild: tries must follow
            self._tries = None
            # The generation becomes dispatch-visible ONLY through the
            # next rebuild's single _dp_state publish (the atomic
            # batch-boundary swap). Its CT flush rides the
            # transactional pending block there — fault-injectable at
            # SITE_CT_EPOCH like every other basis move.
            self._ct_flush_pending = True
            self._policy_epoch += 1
            epoch = self._policy_epoch
        _metrics.engine_epoch_swaps_total.inc()
        oj = self.on_journal
        if oj is not None:
            oj(kind="epoch_swap", attrs={
                "policy_epoch": epoch,
                "basis": [
                    compiled.revision, compiled.identity_version,
                    compiled.vocab_version,
                ],
            })

    def snapshots(self, ingress: bool = True) -> List[EndpointPolicySnapshot]:
        self.rebuild()
        return self._mat[TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS].snapshots

    def fastpath(self, ingress: bool = True):
        """Per-flow verdict cache over the current realized policymaps
        (datapath/fastpath.py). Row patches from identity churn are
        visible through the shared snapshot dicts; re-fetch after rule
        changes (re-materialization swaps the snapshot objects)."""
        from .fastpath import VerdictFastpath

        self.rebuild()
        direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS
        return VerdictFastpath(
            self._mat[direction].snapshots, direction=direction
        )

    # ------------------------------------------------------------------
    def _emit_flow_events(
        self,
        peer_bytes: np.ndarray,
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        verdict: np.ndarray,
        *,
        ingress: bool,
        family: int,
        redirect: Optional[np.ndarray] = None,
        rule: Optional[np.ndarray] = None,
        l4_covered: Optional[np.ndarray] = None,
        producer: str = "prefilter",
    ) -> None:
        """DropNotify per dropped flow (+ TraceNotify per forwarded
        flow when trace_enabled). Cold path: runs only while a monitor
        listener is attached (hub.active), and drops are normally the
        small tail of a batch. Peer identity is resolved host-side via
        the ipcache (the event consumer wants labels/identity, the
        datapath only knows rows).

        ``producer`` disambiguates reason-144's two emitters on the
        DropNotify record: the device path defaults to "prefilter" (the
        shed kernel), the host admission gate passes "admission". Only
        REASON_PREFILTER drops carry it — other reasons have one
        producer.

        With attribution arrays (``rule``/``l4_covered``, FlowAttribution
        on) policy drops carry the REAL reason from the policyd-flows
        taxonomy — deny-rule vs no-L3-match vs no-L4-match — instead of
        the generic REASON_POLICY."""
        hub = self.monitor
        if hub is None or not hub.active:
            return
        from ..monitor.events import (
            REASON_NO_SERVICE,
            REASON_PIPELINE_DEGRADED,
            REASON_POLICY,
            REASON_POLICY_DENY,
            REASON_POLICY_NO_L3,
            REASON_POLICY_NO_L4,
            REASON_PREFILTER,
            REASON_PROXY_REDIRECT,
            REASON_UNKNOWN,
            TRACE_TO_ENDPOINT,
            TRACE_TO_PROXY,
            DropNotify,
            PolicyVerdictNotify,
            TraceNotify,
        )
        import ipaddress as _ipa

        reason_of = {
            DROP_POLICY: REASON_POLICY,
            DROP_PREFILTER: REASON_PREFILTER,
            DROP_NO_SERVICE: REASON_NO_SERVICE,
            DROP_DEGRADED: REASON_PIPELINE_DEGRADED,
        }

        def _reason(i: int) -> int:
            code = int(verdict[i])
            if code == DROP_POLICY and rule is not None:
                if int(rule[i]) >= 0:
                    return REASON_POLICY_DENY
                if l4_covered is not None and bool(l4_covered[i]):
                    return REASON_POLICY_NO_L4
                return REASON_POLICY_NO_L3
            return reason_of.get(code, 0)

        events = []

        def _identity(addr: bytes) -> int:
            e = self.ipcache.lookup_by_ip(str(_ipa.ip_address(addr)))
            return 0 if e is None else e.identity

        def _ep(i: int) -> int:
            idx = int(ep_idx[i])
            return (
                self._endpoint_ids[idx]
                if 0 <= idx < len(self._endpoint_ids)
                else idx
            )

        def _opt(ep_id: int, name: str, default: bool) -> bool:
            if self.endpoint_options is None:
                return default
            try:
                return bool(self.endpoint_options(ep_id, name, default))
            except Exception as e:
                # classified (policyd-failsafe): a transient/poisoned
                # resolver fault degrades to the default — but a
                # programmer error in the resolver is a bug and must
                # surface, not silently un-gate event emission
                if _faults.classify(e) == _faults.KIND_ERROR:
                    raise
                return default

        for i in np.nonzero(verdict >= DROP_POLICY)[0]:
            if not _opt(_ep(i), "DropNotification", self.drop_notifications):
                continue
            addr = bytes(int(b) & 0xFF for b in peer_bytes[i])
            r = _reason(i)
            events.append(
                DropNotify(
                    reason=r,
                    endpoint=_ep(i),
                    src_identity=_identity(addr),
                    family=family,
                    peer_addr=addr,
                    dport=int(dports[i]),
                    proto=int(protos[i]),
                    ingress=ingress,
                    producer=producer if r == REASON_PREFILTER else "",
                )
            )
        # forwarded flows are the bulk of a batch — skip the per-flow
        # walk entirely unless traces can possibly be on
        trace_possible = self.trace_enabled or self.endpoint_options is not None
        for i in np.nonzero(verdict == FORWARD)[0] if trace_possible else ():
            if _opt(_ep(i), "TraceNotification", self.trace_enabled):
                addr = bytes(int(b) & 0xFF for b in peer_bytes[i])
                to_proxy = redirect is not None and bool(redirect[i])
                events.append(
                    TraceNotify(
                        obs_point=TRACE_TO_PROXY if to_proxy else TRACE_TO_ENDPOINT,
                        endpoint=_ep(i),
                        src_identity=_identity(addr),
                        family=family,
                        peer_addr=addr,
                        dport=int(dports[i]),
                        proto=int(protos[i]),
                        ingress=ingress,
                    )
                )
        # PolicyVerdictNotify reports EVERY flow's decision, allowed
        # flows included — same skip-unless-possibly-on contract as the
        # trace walk (this whole function is listener-gated cold path)
        vn_possible = (
            self.verdict_notifications or self.endpoint_options is not None
        )
        for i in range(len(verdict)) if vn_possible else ():
            if not _opt(
                _ep(i), "PolicyVerdictNotification",
                self.verdict_notifications,
            ):
                continue
            code = int(verdict[i])
            if code == FORWARD:
                if redirect is not None and bool(redirect[i]):
                    action, reason = 2, REASON_PROXY_REDIRECT
                else:
                    action, reason = 1, REASON_UNKNOWN
            else:
                action, reason = 0, _reason(i)
            addr = bytes(int(b) & 0xFF for b in peer_bytes[i])
            events.append(
                PolicyVerdictNotify(
                    action=action,
                    reason=reason,
                    endpoint=_ep(i),
                    src_identity=_identity(addr),
                    family=family,
                    peer_addr=addr,
                    dport=int(dports[i]),
                    proto=int(protos[i]),
                    ingress=ingress,
                    rule_index=int(rule[i]) if rule is not None else -1,
                )
            )
        if events:
            hub.publish_many(events)

    def _account_batch(
        self, verdict: np.ndarray, shard_of: Optional[np.ndarray] = None
    ) -> None:
        """Registry accounting for one completed batch (the metricsmap →
        pkg/metrics bridge). Post-host-sync by construction: callers
        pass the already-pulled numpy verdict array, so no new device
        syncs happen here. ``shard_of`` ([B] device index per flow,
        sharded dispatches only) switches verdicts_total to per-device
        series so hot shards are visible."""
        _metrics.verdict_batches.inc({"path": "pipeline"})
        if shard_of is None:
            counts = np.bincount(verdict.astype(np.int64), minlength=6)
            for code, outcome in _OUTCOME_NAMES:
                n = int(counts[code])
                if n:
                    _metrics.verdicts_total.inc({"outcome": outcome}, float(n))
            return
        for d in np.unique(shard_of):
            counts = np.bincount(
                verdict[shard_of == d].astype(np.int64), minlength=6
            )
            for code, outcome in _OUTCOME_NAMES:
                n = int(counts[code])
                if n:
                    _metrics.verdicts_total.inc(
                        {"outcome": outcome, "device": str(int(d))}, float(n)
                    )

    def _account_attribution(
        self,
        verdict: np.ndarray,
        rule: np.ndarray,
        l4x: np.ndarray,
        hits: Optional[np.ndarray],
        *,
        ingress: bool,
    ) -> None:
        """rule_hits_total / drop_reasons_total accounting for one
        attributed batch. Post-host-sync by construction (pulled numpy
        arrays in, no device syncs). ``hits=None`` means padded lanes
        polluted the device segment-sum — fall back to a host bincount
        over the (already trimmed) rule array."""
        names = self._attrib_names
        if hits is None:
            matched = rule[rule >= 0]
            hits = np.bincount(matched, minlength=len(names))
        direction = "ingress" if ingress else "egress"
        for r in np.nonzero(hits)[0]:
            origin = names[r] if r < len(names) else f"rule-{r}"
            _metrics.rule_hits_total.inc(
                {"origin": origin, "direction": direction}, float(hits[r])
            )
        pol = verdict == DROP_POLICY
        deny = pol & (rule >= 0)
        for reason, mask in (
            ("deny-rule", deny),
            ("no-l4-match", pol & ~deny & l4x),
            ("no-l3-match", pol & ~deny & ~l4x),
            ("prefilter", verdict == DROP_PREFILTER),
            ("no-service", verdict == DROP_NO_SERVICE),
            ("pipeline-degraded", verdict == DROP_DEGRADED),
        ):
            n = int(np.count_nonzero(mask))
            if n:
                labels = {"reason": reason}
                if reason == "prefilter":
                    # reason 144's device-kernel producer (the host
                    # admission gate labels its own rows "admission")
                    labels["producer"] = "prefilter"
                _metrics.drop_reasons_total.inc(labels, float(n))

    def _record_flows(
        self,
        peer_bytes: np.ndarray,
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        verdict: np.ndarray,
        rule: np.ndarray,
        l4x: np.ndarray,
        redirect: Optional[np.ndarray],
        *,
        ingress: bool,
    ) -> None:
        """Sampled FlowRecord feed for the flow-log ring: at most
        SAMPLE_CAP records per batch, drops first (they are the rare,
        interesting tail), then forwarded flows for the remainder —
        per-record host cost is bounded regardless of batch size."""
        ring = self.flow_ring
        if not ring.active:
            return
        import ipaddress as _ipa

        from ..monitor.events import (
            REASON_NO_SERVICE,
            REASON_PIPELINE_DEGRADED,
            REASON_POLICY_DENY,
            REASON_POLICY_NO_L3,
            REASON_POLICY_NO_L4,
            REASON_PREFILTER,
            REASON_PROXY_REDIRECT,
            reason_name,
        )
        from ..observe.flows import now as _flow_now

        take = list(np.nonzero(verdict >= DROP_POLICY)[0][:_FLOW_SAMPLE_CAP])
        if len(take) < _FLOW_SAMPLE_CAP:
            take.extend(
                np.nonzero(verdict == FORWARD)[0][
                    : _FLOW_SAMPLE_CAP - len(take)
                ]
            )
        if not take:
            return
        origins = self.engine.repo.rule_origins()
        outcome = dict(_OUTCOME_NAMES)
        labels_of = self.identity_labels
        ts = _flow_now()
        recs = []
        for i in take:
            code = int(verdict[i])
            ri = int(rule[i])
            if code == DROP_PREFILTER:
                reason = REASON_PREFILTER
            elif code == DROP_NO_SERVICE:
                reason = REASON_NO_SERVICE
            elif code == DROP_DEGRADED:
                reason = REASON_PIPELINE_DEGRADED
            elif code == DROP_POLICY:
                if ri >= 0:
                    reason = REASON_POLICY_DENY
                elif bool(l4x[i]):
                    reason = REASON_POLICY_NO_L4
                else:
                    reason = REASON_POLICY_NO_L3
            elif redirect is not None and bool(redirect[i]):
                reason = REASON_PROXY_REDIRECT
            else:
                reason = 0
            addr = bytes(int(b) & 0xFF for b in peer_bytes[i])
            peer_ip = str(_ipa.ip_address(addr))
            e = self.ipcache.lookup_by_ip(peer_ip)
            peer_ident = 0 if e is None else e.identity
            idx = int(ep_idx[i])
            ep_ident = (
                self._endpoints[idx]
                if 0 <= idx < len(self._endpoints)
                else 0
            )

            def _labels(ident: int) -> Tuple[str, ...]:
                if labels_of is None:
                    return ()
                try:
                    return tuple(labels_of(ident))
                except Exception as e:
                    # classified (policyd-failsafe): degrade to
                    # unlabeled records on environmental faults only —
                    # a buggy resolver surfaces instead of silently
                    # stripping every flow record's labels
                    if _faults.classify(e) == _faults.KIND_ERROR:
                        raise
                    return ()

            # flow orientation: ingress = peer → endpoint, egress =
            # endpoint → peer (the endpoint's own address is not known
            # to the datapath — only the peer side carries an IP)
            src_id, dst_id = (
                (peer_ident, ep_ident) if ingress else (ep_ident, peer_ident)
            )
            recs.append(
                FlowRecord(
                    ts=ts,
                    direction="ingress" if ingress else "egress",
                    src_identity=src_id,
                    dst_identity=dst_id,
                    src_labels=_labels(src_id),
                    dst_labels=_labels(dst_id),
                    src_ip=peer_ip if ingress else "",
                    dst_ip="" if ingress else peer_ip,
                    dport=int(dports[i]),
                    proto=int(protos[i]),
                    verdict=code,
                    verdict_name=outcome.get(code, str(code)),
                    reason=reason,
                    reason_name=(
                        "allowed" if reason == 0 else reason_name(reason)
                    ),
                    rule_index=ri,
                    rule_origin=(
                        origins[ri] if 0 <= ri < len(origins) else None
                    ),
                )
            )
        ring.push_many(recs)

    @staticmethod
    def _shard_map(spans, ndev: int, b: int) -> np.ndarray:
        """[B] device index per flow: P("flows") splits each padded
        chunk's dim 0 into ndev contiguous shards in mesh device
        order."""
        out = np.zeros(b, np.int32)
        for lo, hi, padded in spans:
            w = max(1, padded // ndev)
            out[lo:hi] = np.minimum(np.arange(hi - lo) // w, ndev - 1)
        return out

    def _chunk_spans(self, n: int, *, bucketed: bool, ndev: int):
        """Dispatch spans [(lo, hi, padded)] for an n-flow batch.

        Unbucketed (the no-CT full-batch path) keeps the exact shape —
        padded lanes would pollute the device-side counters — except
        under sharding, where the batch must split evenly across the
        mesh. Bucketed spans (the CT-miss tail) come off the fixed
        BUCKET_LADDER (ndev-rounded): full top-rung chunks first (zero
        pad, each its own overlapped enqueue), then the exact
        minimum-padded-lane rung cover of what remains (_tail_cover) —
        so the padded shape set stays ≤ len(BUCKET_LADDER) per
        static-arg combination while tail pad drops versus both the
        old largest-warm-bucket reuse (a 3000-flow tail dispatched as
        3×1024, now 2048+1024) and a single-bucket pad (1100 flows pad
        to 2048, not 4096)."""
        if not bucketed:
            return [(0, n, n + ((-n) % ndev) if ndev > 1 else n)]
        rungs = _ladder_rungs(ndev)
        top = rungs[-1]
        spans = []
        lo = 0
        while n - lo > top:
            spans.append((lo, lo + top, top))
            lo += top
        _lanes, _chunks, plan = _tail_cover(n - lo, rungs)
        for r in plan:  # largest-first: only the final chunk has pad
            live = min(r, n - lo)
            spans.append((lo, lo + live, r))
            lo += live
        return spans

    def _enqueue_one(
        self, t, peer_bytes, ep_idx, dports, protos, row_override,
        lo, hi, padded, *, family, pf_stage, ep_count, v6_fused,
        flow_sharding, rule_tab=None, n_rules=0, staging=None,
        ident_gather=False, psample=None,
    ):
        """Pad + upload + enqueue ONE chunk; returns the UN-PULLED
        device (verdict, redirect, counters) triple. Under sharding
        the flow arrays are committed split over the mesh's "flows"
        axis (the tests/test_multichip.py pattern) before the call.
        ``staging`` (bucketed dispatches only) collects the pre-pinned
        rung buffers the pad half wrote into, for release at the host
        pull; padded rungs then cost four memcpys instead of four
        np.pad allocations. ``psample`` (policyd-prof, the 1-in-N
        sampled batch only) makes the upload an explicit synchronous
        device_put so its wall time separates from the async program
        enqueue — identical avals, so the compiled program is the same
        one the unsampled path runs."""
        if _faults.hub.active:
            _faults.hub.check(_faults.SITE_H2D)
        pb = peer_bytes[lo:hi]
        ei = ep_idx[lo:hi]
        dp = dports[lo:hi]
        pr = protos[lo:hi]
        ro = None if row_override is None else row_override[lo:hi]
        pad = padded - (hi - lo)
        if pad and staging is not None:
            bufs = self._staging_acquire(padded, peer_bytes.shape[1])
            spb, sei, sdp, spr, sro = bufs
            m = hi - lo
            spb[:m] = pb
            spb[m:] = 0
            sei[:m] = ei
            sei[m:] = 0
            sdp[:m] = dp
            sdp[m:] = 0
            spr[:m] = pr
            spr[m:] = 0
            pb, ei, dp, pr = spb, sei, sdp, spr
            if ro is not None:
                # padded lanes must derive-by-LPM, never trust (-1)
                sro[:m] = ro
                sro[m:] = -1
                ro = sro
            staging.append(bufs)
        elif pad:
            pb, ei, dp, pr, ro = _pad_flows(pad, pb, ei, dp, pr,
                                            row_override=ro)
        peer = _pack_v4_u32(pb) if family == 4 else pb
        if psample is not None:
            # sampled h2d edge: upload explicitly and wait — the time
            # between here and the post-enqueue ready wait is then pure
            # device compute. device_put with sharding=None commits to
            # the default device; either way the avals (and therefore
            # the jit cache key / compiled program) are unchanged.
            _t0 = time.perf_counter()
            peer, ei, dp, pr = jax.block_until_ready(
                jax.device_put((peer, ei, dp, pr), flow_sharding)
            )
            if ro is not None:
                ro = jax.block_until_ready(
                    jax.device_put(ro, flow_sharding)
                )
            psample.add_h2d(time.perf_counter() - _t0)
        elif flow_sharding is not None:
            peer, ei, dp, pr = jax.device_put(
                (peer, ei, dp, pr), flow_sharding
            )
            if ro is not None:
                ro = jax.device_put(ro, flow_sharding)
        elif ro is not None:
            ro = jnp.asarray(ro)
        attrib = rule_tab is not None
        if family == 4:
            fn = process_flows_wide
            fargs = (t, peer, ei, dp, pr)
            fkw = dict(
                ep_count=ep_count, prefilter=pf_stage, row_override=ro,
                attrib=attrib, rule_tab=rule_tab, n_rules=n_rules,
                ident_gather=ident_gather,
            )
        else:
            fn = process_flows
            fargs = (t, peer, ei, dp, pr)
            fkw = dict(
                ep_count=ep_count, levels=16, prefilter=pf_stage,
                fused=v6_fused, row_override=ro, attrib=attrib,
                rule_tab=rule_tab, n_rules=n_rules,
                ident_gather=ident_gather,
            )
        if psample is not None:
            prof = self.profiler
            if prof is not None:
                # compile-time cost ledger: flops / bytes-accessed for
                # this (site, stable ladder shape), recorded once
                prof.note_jit_cost(
                    "dispatch",
                    (family, padded, pf_stage, ep_count, ro is not None,
                     v6_fused, attrib, ident_gather),
                    fn, fargs, fkw,
                )
        return fn(*fargs, **fkw)

    # -- policyd-failsafe: ladder level 2 (host fallback) ---------------
    def _host_tables(self, direction: int) -> Optional[Tuple]:
        """Host numpy copy of one direction's policymap columns/bitmaps,
        cached on the source object (the _replicated_policymap pattern).
        The pull itself touches the device — on a dead backend it fails
        classified, and the caller falls through to policy synthesis."""
        mat = self._mat.get(direction)
        if mat is None:
            return None
        pm = mat.tables
        src, ht = self._host_pm.get(direction, (None, None))
        if src is pm:
            return ht
        try:
            ht = (
                np.asarray(pm.col_ep),
                np.asarray(pm.col_port),
                np.asarray(pm.col_proto),
                np.asarray(pm.col_is_l3).astype(bool),
                np.asarray(pm.id_bits),
            )
        except BaseException as e:
            if _faults.classify(e) == _faults.KIND_ERROR:
                raise
            return None
        self._host_pm[direction] = (pm, ht)
        return ht

    def _host_verdicts(
        self, peer_bytes, ep_idx, dports, protos, *, ingress, family,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Numpy mirror of the device verdict path (the ladder's last
        rung). Identity resolution goes through the HOST ipcache — the
        authoritative source the device tries are built FROM — instead
        of mirroring the LPM walk bit-for-bit; the policymap decision
        mirrors ops/lookup.lookup_batch exactly (colsel → allow/red).
        O(B · C) numpy plus an O(B) python ipcache walk: an emergency
        path that keeps verdicts flowing, not a fast path. When even
        the host tables are unreachable, falls back to pure policy
        synthesis (FailOpen → forward, fail-closed → DROP_DEGRADED)."""
        import ipaddress as _ipa

        b = peer_bytes.shape[0]
        direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS
        ht = self._host_tables(direction)
        if ht is None:
            v = np.full(
                b, FORWARD if self._fail_open else DROP_DEGRADED, np.int8
            )
            if not self._fail_open and b:
                _metrics.drop_reasons_total.inc(
                    {"reason": "pipeline-degraded"}, float(b)
                )
            return v, np.zeros(b, bool)
        col_ep, col_port, col_proto, col_is_l3, id_bits = ht
        addrs = [
            _ipa.ip_address(bytes(int(x) & 0xFF for x in peer_bytes[i]))
            for i in range(b)
        ]
        idents = np.empty(b, np.int64)
        for i, a in enumerate(addrs):
            e = self.ipcache.lookup_by_ip(str(a))
            idents[i] = ID_WORLD if e is None else e.identity
        rows = np.asarray(self.engine.rows_or_negative(idents))
        world = np.asarray(
            self.engine.rows_or_negative(np.array([ID_WORLD], np.int64))
        )[0]
        rows = np.where(rows < 0, world, rows).astype(np.int64)
        # prefilter deny (ingress only, like the device pf stage)
        pf_drop = np.zeros(b, bool)
        if ingress:
            _, pf_cidrs = self.prefilter.dump()
            nets = [
                _ipa.ip_network(c)
                for c in pf_cidrs
                if (":" in c) == (family == 6)
            ]
            if nets:
                for i, a in enumerate(addrs):
                    pf_drop[i] = any(a in n for n in nets)
        w = id_bits.shape[1] // 2
        gathered = id_bits[np.clip(rows, 0, id_bits.shape[0] - 1)]
        shifts = np.arange(32, dtype=np.uint32)
        both = (
            ((gathered[:, :, None] >> shifts) & np.uint32(1))
            .astype(bool)
            .reshape(b, -1)
        )
        c = col_ep.shape[0]
        allow_bits = both[:, : w * 32][:, :c]
        red_bits = both[:, w * 32:][:, :c]
        ep = np.asarray(ep_idx, np.int64)
        colsel = (ep[:, None] == col_ep[None, :]) & (
            col_is_l3[None, :]
            | (
                (np.asarray(dports)[:, None] == col_port[None, :])
                & (np.asarray(protos)[:, None] == col_proto[None, :])
            )
        )
        hit = colsel & allow_bits
        allow = hit.any(axis=1)
        red = (hit & red_bits).any(axis=1)
        v = np.where(allow, np.int8(FORWARD), np.int8(DROP_POLICY))
        v = np.where(pf_drop, np.int8(DROP_PREFILTER), v).astype(np.int8)
        return v, (red & (v == FORWARD))

    def _host_enqueue(
        self, peer_bytes, ep_idx, dports, protos, *, ingress, family, bt,
    ) -> _Enqueued:
        """_dispatch_enqueue stand-in at ladder level 2: the "dispatch"
        phase computes on host numpy and the _Enqueued carries finished
        results — _dispatch_complete returns them without touching the
        device. Shapes/ordering of the completion half are unchanged so
        the FIFO queue, CT create, counters, and events all run as
        usual over host-produced verdicts."""
        with bt.phase("dispatch"):
            v, red = self._host_verdicts(
                peer_bytes, ep_idx, dports, protos,
                ingress=ingress, family=family,
            )
        attrib = self._dp_state[5] is not None
        return _Enqueued(
            (), [], peer_bytes.shape[0], False, 1,
            attrib=attrib, host=(v, red),
        )

    def _dispatch_enqueue(
        self,
        peer_bytes: np.ndarray,
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        *,
        ingress: bool,
        family: int,
        bucketed: bool = False,
        row_override: Optional[np.ndarray] = None,
        bt=_NOOP_BATCH,
    ) -> _Enqueued:
        """Non-blocking half of a dispatch: pad/chunk, upload, enqueue
        the fused device program(s), return un-pulled device arrays.
        The host pull lives in _dispatch_complete — with depth>1 it
        runs after successor batches were enqueued, so device execution
        hides behind their host prep."""
        direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS
        if self._ladder_level >= 2:
            # host fallback (ladder level 2): verdict on host numpy,
            # synchronously — there is no device work to overlap
            return self._host_enqueue(
                peer_bytes, ep_idx, dports, protos,
                ingress=ingress, family=family, bt=bt,
            )
        if _faults.hub.active:
            _faults.hub.check(_faults.SITE_DISPATCH)
        # ONE atomic snapshot read: tables + flags + sharding +
        # attribution swap together in rebuild(), so fused-ness,
        # placement, and the rule table always match the tables they
        # describe
        (
            tables_map, pf_empty, v6_fused, flow_sharding, ndev, attrib_el,
            ident2d, _shed,
        ) = self._dp_state
        t = tables_map[(direction, family)]
        rule_tab = None
        n_rules = 0
        if attrib_el is not None:
            rule_tab = attrib_el[0][direction]
            n_rules = attrib_el[1]
        b = peer_bytes.shape[0]
        # XDP prefilter guards traffic entering the node only, and an
        # empty deny set skips the walk entirely (it's one of the two
        # LPM walks that dominate the pipeline)
        pf_stage = ingress and not pf_empty[0 if family == 4 else 1]
        ep_count = max(1, len(self._endpoints))
        spans = self._chunk_spans(b, bucketed=bucketed, ndev=ndev)
        # pad-lane accounting on EVERY dispatch path (bucketed rung pad
        # and the unbucketed sharded ndev-rounding alike) — the
        # benchmark's verdict_kernel_roofline counts live lanes as
        # lanes less pad
        pad_lanes = sum(p for _, _, p in spans) - b
        if pad_lanes:
            _metrics.dispatch_pad_lanes_total.inc(
                {"family": f"v{family}"}, float(pad_lanes)
            )
        # policyd-prof: one attribute read while off (None); while on,
        # every sample_every-th dispatch gets a live sample and pays
        # the synchronizing sandwiches (h2d inside _enqueue_one, the
        # ready wait below, d2h in _dispatch_complete)
        prof = self.profiler
        psample = (
            prof.begin_dispatch("dispatch", b) if prof is not None else None
        )
        tr = self.tracer
        if tr.active:
            # shape-bucket telemetry: the jit cache keys on padded
            # chunk shape + the static args below — a fresh key on
            # this pipeline ≈ one XLA recompile on dispatch
            for _lo, _hi, padded in spans:
                key = (
                    direction, family, padded, pf_stage, ep_count,
                    row_override is not None, v6_fused, ndev > 1,
                    rule_tab is not None, ident2d,
                )
                if key in self._seen_shapes:
                    _metrics.jit_shape_buckets_total.inc(
                        {"site": "dispatch", "result": "hit"}
                    )
                else:
                    self._seen_shapes.add(key)
                    _metrics.jit_shape_buckets_total.inc(
                        {"site": "dispatch", "result": "miss"}
                    )
            # each logical upload is one per-device slice transfer per
            # mesh device under sharding (P("flows") splits dim 0)
            _metrics.device_transfers_total.inc(
                {"direction": "h2d"},
                (4.0 + (row_override is not None)) * len(spans) * ndev,
            )
            # byte-ledger sibling (policyd-prof): logical upload bytes
            # — v4 packs to one u32 lane, v6 ships the raw int32
            # bytes; shard slices sum to the full array, so no ×ndev
            peer_w = 4 if family == 4 else peer_bytes.shape[1] * 4
            _metrics.device_transfer_bytes_total.inc(
                {"direction": "h2d"},
                float(sum(
                    p * (peer_w + 12 + (4 if row_override is not None else 0))
                    for _, _, p in spans
                )),
            )
            bt.mark(
                padded=int(sum(p for _, _, p in spans)), chunks=len(spans)
            )
        # "dispatch" covers the h2d uploads + the async XLA enqueue of
        # the FUSED device program (LPM walks + policymap lookup +
        # counter matmul trace as one jit — splitting them into
        # separate spans would de-fuse the program); the actual device
        # execution time aggregates into "host_sync" at completion.
        staging = [] if bucketed else None
        _pl_t0 = time.perf_counter() if psample is not None else 0.0
        with bt.phase("dispatch"):
            chunks = [
                self._enqueue_one(
                    t, peer_bytes, ep_idx, dports, protos, row_override,
                    lo, hi, padded, family=family, pf_stage=pf_stage,
                    ep_count=ep_count, v6_fused=v6_fused,
                    flow_sharding=flow_sharding, rule_tab=rule_tab,
                    n_rules=n_rules, staging=staging, ident_gather=ident2d,
                    psample=psample,
                )
                for lo, hi, padded in spans
            ]
            if psample is not None:
                # sampled compute edge: h2d already completed
                # synchronously inside _enqueue_one, so what remains of
                # the chunk loop — per-chunk program dispatch (slicing,
                # padding, jit call) plus the residual device wait here
                # — is charged to device_compute (on hardware the
                # dispatch overhead runs concurrently with execution;
                # splitting it would need a per-chunk sync that changes
                # what's being measured). Done INSIDE the dispatch span
                # so a sampled batch's trace and its decomposition
                # cover the same wall clock. This serializes THIS batch
                # against the pipeline overlap — the cost sampling
                # exists to amortize.
                jax.block_until_ready(chunks)
                psample.add_compute(
                    time.perf_counter() - _pl_t0 - psample.h2d_s
                )
                # rung occupancy: what the tuner/chunker chose vs what
                # was live — makes pad waste visible per sample
                psample.mark(
                    rungs=[int(p) for _, _, p in spans],
                    lanes=int(b),
                    pad_lanes=int(sum(p for _, _, p in spans) - b),
                    chunks=len(spans),
                    ndev=int(ndev),
                    depth=int(self.pipeline_depth),
                    family=int(family),
                    bucketed=bool(bucketed),
                )
        if bucketed:
            for _lo, _hi, padded in spans:
                self._warm_buckets.add(padded)
        exact = all(hi - lo == padded for lo, hi, padded in spans)
        return _Enqueued(chunks, spans, b, exact, ndev,
                         attrib=rule_tab is not None,
                         staging=staging or (), psample=psample)

    def _dispatch_complete(
        self, enq: _Enqueued, bt=_NOOP_BATCH
    ) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
        """Blocking half: pull chunk results to host. With depth>1 the
        device worked through this batch while the host prepared its
        successors, so "host_sync" here measures the RESIDUAL wait.
        Counters come back None when padded lanes polluted the device
        accumulation (callers fall back to host-side np.add.at); the
        attribution rule-hit sums follow the same exact/fallback rule
        (None → host bincount over the pulled rule array). Attributed
        dispatches return (verdict, redirect, counters, rule,
        l4_covered, hits) — the attribution d2h pulls live HERE, in
        the completion half, so PR 3's enqueue/complete overlap is
        preserved."""
        if enq.host is not None:
            # ladder level 2: verdicts were computed on host at enqueue
            v, red = enq.host
            if not enq.attrib:
                return v, red, None
            # host fallback carries no per-rule attribution — report
            # "no rule decided" (-1) so rule_hits_total only ever
            # counts real device attributions
            b = enq.b
            return (
                v, red, None,
                np.full(b, -1, np.int32), np.zeros(b, bool), None,
            )
        if _faults.hub.active:
            # the injected "complete" fault fires BEFORE the pull — the
            # retry soundness argument in _finish_guarded relies on the
            # transient window preceding any host-state mutation
            _faults.hub.check(_faults.SITE_COMPLETE)
        if self.tracer.active:
            _metrics.device_transfers_total.inc(
                {"direction": "d2h"},
                (6.0 if enq.attrib else 3.0) * len(enq.chunks) * enq.ndev,
            )
            # byte-ledger sibling (policyd-prof): logical bytes the
            # pull below actually moves (counters/hits only when exact
            # — the inexact path never reads them). .nbytes on an
            # un-pulled device array is metadata, no sync.
            nb = 0
            for ch in enq.chunks:
                nb += int(ch[0].nbytes) + int(ch[1].nbytes)
                if enq.attrib:
                    nb += int(ch[3].nbytes) + int(ch[4].nbytes)
                if enq.exact:
                    nb += int(ch[2].nbytes)
                    if enq.attrib:
                        nb += int(ch[5].nbytes)
            _metrics.device_transfer_bytes_total.inc(
                {"direction": "d2h"}, float(nb)
            )
        ps = enq.psample
        _pt0 = time.perf_counter() if ps is not None else 0.0
        with bt.phase("host_sync"):
            b = enq.b
            rule = l4x = hits = None
            if len(enq.chunks) == 1:
                ch = enq.chunks[0]
                verdict = np.asarray(ch[0])[:b]
                redirect = np.asarray(ch[1])[:b]
                if enq.attrib:
                    rule = np.asarray(ch[3])[:b]
                    l4x = np.asarray(ch[4])[:b]
            else:
                verdict = np.empty(b, np.int8)
                redirect = np.empty(b, bool)
                if enq.attrib:
                    rule = np.empty(b, np.int32)
                    l4x = np.empty(b, bool)
                for (lo, hi, _padded), ch in zip(enq.spans, enq.chunks):
                    verdict[lo:hi] = np.asarray(ch[0])[: hi - lo]
                    redirect[lo:hi] = np.asarray(ch[1])[: hi - lo]
                    if enq.attrib:
                        rule[lo:hi] = np.asarray(ch[3])[: hi - lo]
                        l4x[lo:hi] = np.asarray(ch[4])[: hi - lo]
            if enq.exact:
                counters = np.asarray(enq.chunks[0][2])
                for ch in enq.chunks[1:]:
                    counters = counters + np.asarray(ch[2])
                if enq.attrib:
                    hits = np.asarray(enq.chunks[0][5])
                    for ch in enq.chunks[1:]:
                        hits = hits + np.asarray(ch[5])
            else:
                counters = None
        if ps is not None:
            # sampled d2h edge: the residual pull wait (compute already
            # completed at the enqueue half's ready sandwich)
            ps.add_d2h(time.perf_counter() - _pt0)
            prof = self.profiler
            if prof is not None:
                prof.complete(ps)
            enq.psample = None  # retry-idempotent: never retire twice
        if enq.staging:
            # the host pull above proves the device program finished —
            # only now are the pinned buffers safe to hand to the next
            # batch (JAX CPU zero-copy aliasing)
            self._staging_release(enq.staging)
            enq.staging = ()
        if not enq.attrib:
            return verdict, redirect, counters
        return verdict, redirect, counters, rule, l4x, hits

    def _dispatch(
        self,
        peer_bytes: np.ndarray,
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        *,
        ingress: bool,
        family: int,
        pad_to: Optional[int] = None,
        row_override: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
        """Synchronous dispatch (enqueue + immediate pull) — kept for
        direct callers/tests; the pipelined path drives the two halves
        separately. ``pad_to`` is honored as "bucket this batch"."""
        tr = self.tracer
        bt = tr.current() if tr.active else _NOOP_BATCH
        enq = self._dispatch_enqueue(
            peer_bytes, ep_idx, dports, protos, ingress=ingress,
            family=family, bucketed=pad_to is not None,
            row_override=row_override, bt=bt,
        )
        return self._dispatch_complete(enq, bt)

    # -- bounded in-flight queue ---------------------------------------
    def _complete_oldest(self) -> bool:
        """Pull + finish the oldest in-flight batch. Returns False when
        nothing was queued. The finish closure runs OUTSIDE the queue
        lock (it publishes events and fires callbacks)."""
        with self._queue_lock:
            if not self._inflight:
                return False
            inf = self._inflight.popleft()
            _metrics.pipeline_inflight_depth.set(float(len(self._inflight)))
        # completion-half timing (p99 verdict-latency proxy): observed
        # only for batches admitted while the tuner was on
        tuner = self._tuner
        t0 = (
            time.perf_counter_ns()
            if tuner is not None and inf.enq_ns
            else 0
        )
        adm = self._admission
        wd = self._watchdog
        try:
            with inf.bt.half("complete"):
                # the watchdog's stall clock starts when a thread
                # ACTIVELY pulls this batch — un-pulled in-flight
                # batches are the pipeline's normal lazy shape, not
                # stalls
                if wd is not None:
                    self._completing = (inf, time.monotonic())
                # classified completion (policyd-failsafe): transient
                # faults retry bounded, poisoned batches quarantine into
                # a degraded RESULT, and only programmer errors come
                # back as an exception for result() to surface raw
                value, exc = self._finish_guarded(inf)
                # publish under the queue lock, where the watchdog
                # decides abandonment: a batch it already resolved
                # degraded must not have its (late, possibly-poisoned)
                # result overwrite the published one
                with self._queue_lock:
                    if not inf.abandoned:
                        inf.pending._value = value
                        inf.pending._exc = exc
        finally:
            if wd is not None:
                self._completing = None
            inf.pending._event.set()
            if inf.bt is not _NOOP_BATCH:
                inf.bt.end(self.monitor)
        if adm is not None and inf.t0:
            adm.observe_completion(time.monotonic() - inf.t0)
        if t0:
            new_depth = tuner.observe(
                self.pipeline_depth, inf.b, inf.enq_ns,
                time.perf_counter_ns() - t0, inf.occ,
            )
            # tuner armistice (policyd-overload): while the admission
            # gate shed recently, the depth controller must not probe
            # the queue UP — two controllers pushing the same knob in
            # opposite directions oscillate
            if new_depth is not None and not (
                new_depth > self.pipeline_depth
                and adm is not None
                and adm.shedding()
            ):
                self._apply_depth(new_depth)
        # policyd-survive: one-shot first-completion hook (the daemon's
        # restart_downtime stamp). One attribute read when unset.
        cb = self.on_first_batch
        if cb is not None:
            self.on_first_batch = None
            # a measurement hook must never fail the batch it measures
            try:
                cb()
            except Exception:  # policyd-lint: disable=ROBUST001
                pass
        return True

    def _complete_until(self, pending: PendingBatch) -> None:
        """Complete in-flight batches FIFO until ``pending`` is done.
        An empty queue with ``pending`` still unset means another
        thread popped it and is mid-finish — the caller's event wait
        covers that."""
        while not pending.done:
            if not self._complete_oldest():
                return

    def begin_drain(self) -> None:
        """Stop admitting new batches (graceful drain, policyd-survive):
        subsequent submits resolve immediately with the degraded shape
        while drain() FIFO-completes the in-flight queue."""
        self._draining = True

    def end_drain(self) -> None:
        """Re-open admission (a drain that was probed but not followed
        by process exit — tests, aborted shutdowns)."""
        self._draining = False

    def drain(self, deadline_s: Optional[float] = None) -> dict:
        """Complete every in-flight batch FIFO (barrier; daemon
        shutdown). With a deadline, batches still queued when it
        expires resolve DEGRADED instead of blocking exit — a drain
        never loses a verdict, it only downgrades late ones
        (verdicts_lost stays 0). → {completed, abandoned}."""
        completed = 0
        limit = (
            time.monotonic() + deadline_s if deadline_s is not None else None
        )
        while limit is None or time.monotonic() < limit:
            if not self._complete_oldest():
                break
            completed += 1
        abandoned = 0
        while True:
            with self._queue_lock:
                if not self._inflight:
                    break
                inf = self._inflight.popleft()
                inf.abandoned = True
                _metrics.pipeline_inflight_depth.set(
                    float(len(self._inflight))
                )
            inf.pending._value = self._degraded_result(inf)
            inf.pending._event.set()
            if inf.bt is not _NOOP_BATCH:
                inf.bt.end(self.monitor)
            abandoned += 1
        return {"completed": completed, "abandoned": abandoned}

    @property
    def inflight_depth(self) -> int:
        return len(self._inflight)

    def _submit(
        self,
        peer_bytes: np.ndarray,  # [B, 4|16] int32 peer address bytes
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        sports: Optional[np.ndarray],
        *,
        ingress: bool,
        family: int,
        peer_words: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        want_rev_nat: bool = False,
        tunnel_identities: Optional[np.ndarray] = None,
        gate: bool = True,
    ) -> PendingBatch:
        """Trace shell + queue admission around _submit_inner: the
        disabled cost is ONE ``tracer.active`` attribute read per batch
        (the hub's `active` pattern, observe/tracer.py). The trace is
        DETACHED from the thread-local stack once the enqueue half
        returns — it stays open and ends when the batch completes, so
        spans attach to the batch that completes, not the one being
        prepared — and admission beyond pipeline_depth completes the
        oldest batch first (the bounded in-flight queue).

        ``gate=False`` is the admission gate's internal re-entry for
        the kept remainder of a partially-shed batch — it must not be
        gated twice."""
        # policyd-overload admission gate: one attribute read when the
        # AdmissionControl option is off — the exact pre-option path
        if gate and self._admission is not None:
            gated = self._admission_gate(
                peer_bytes, ep_idx, dports, protos, sports,
                ingress=ingress, family=family, peer_words=peer_words,
                want_rev_nat=want_rev_nat,
                tunnel_identities=tunnel_identities,
            )
            if gated is not None:
                return gated
        # policyd-survive drain shed: a draining pipeline admits no new
        # work — resolve immediately with the degraded shape (FORWARD
        # under FailOpen, DROP_DEGRADED fail-closed; a shed flow still
        # gets a verdict, so verdicts_lost stays 0). The not-draining
        # path pays one GIL-atomic bool read.
        if self._draining:
            pending = PendingBatch(self)
            shell = _InFlight(
                pending, None, _NOOP_BATCH,
                b=peer_bytes.shape[0], rev=want_rev_nat,
            )
            pending._value = self._degraded_result(shell)
            pending._event.set()
            return pending
        tr = self.tracer
        # tuner timing: the enqueue half is everything up to queue
        # admission (prepare + CT pre-pass + h2d + async enqueue) —
        # captured only while DispatchAutoTune is on
        tuner = self._tuner
        t0 = time.perf_counter_ns() if tuner is not None else 0
        if tr.active:
            bt = tr.begin(
                f"v{family}-{'ingress' if ingress else 'egress'}",
                peer_bytes.shape[0],
            )
        else:
            bt = _NOOP_BATCH
        # the enqueue half ends at queue admission: completing older
        # batches past the depth bound belongs to THEIR complete halves
        with bt.half("enqueue"):
            # classified enqueue (policyd-failsafe): a fault in the enqueue
            # half (rebuild / h2d / async dispatch) retries bounded on
            # transient, then resolves DEGRADED — the caller always gets a
            # PendingBatch whose result() carries a verdict per flow.
            # Programmer errors still raise raw (pre-failsafe contract).
            attempt = 0
            bo: Optional[Backoff] = None
            while True:
                try:
                    inf = self._submit_inner(
                        peer_bytes, ep_idx, dports, protos, sports,
                        ingress=ingress, family=family, peer_words=peer_words,
                        want_rev_nat=want_rev_nat,
                        tunnel_identities=tunnel_identities, bt=bt,
                    )
                    break
                except BaseException as e:
                    kind = _faults.classify(e)
                    if kind == _faults.KIND_ERROR:
                        if bt is not _NOOP_BATCH:
                            bt.end(self.monitor)
                        raise
                    self._note_fault(e, kind)
                    if (
                        kind == _faults.KIND_TRANSIENT
                        and attempt < self.retry_limit
                    ):
                        attempt += 1
                        if bo is None:
                            bo = Backoff(
                                min_s=self.retry_min_s, max_s=self.retry_max_s,
                                jitter=False,
                            )
                        time.sleep(bo.duration())
                        continue
                    if bt is not _NOOP_BATCH:
                        bt.end(self.monitor)
                    pending = PendingBatch(self)
                    shell = _InFlight(
                        pending, None, bt,
                        b=peer_bytes.shape[0], rev=want_rev_nat,
                    )
                    pending._value = self._quarantine(shell)
                    pending._event.set()
                    return pending
            if bt is not _NOOP_BATCH:
                tr.detach(bt)
            if self._admission is not None or self._watchdog is not None:
                inf.t0 = time.monotonic()
            if inf.finish is None:
                # ran synchronously (device-CT donated-state path)
                if bt is not _NOOP_BATCH:
                    bt.end(self.monitor)
                return inf.pending
            with self._queue_lock:
                self._inflight.append(inf)
                if tuner is not None:
                    inf.enq_ns = time.perf_counter_ns() - t0
                    inf.occ = len(self._inflight)
                    inf.b = peer_bytes.shape[0]
                _metrics.pipeline_inflight_depth.set(float(len(self._inflight)))
                over = len(self._inflight) > self.pipeline_depth
        while over:
            self._complete_oldest()
            with self._queue_lock:
                over = len(self._inflight) > self.pipeline_depth
        return inf.pending

    def _submit_inner(
        self,
        peer_bytes: np.ndarray,  # [B, 4|16] int32 peer address bytes
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        sports: Optional[np.ndarray],
        *,
        ingress: bool,
        family: int,
        peer_words: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        want_rev_nat: bool = False,
        tunnel_identities: Optional[np.ndarray] = None,
        bt=_NOOP_BATCH,
    ):
        with bt.phase("rebuild"):
            self.rebuild()
        with bt.phase("prepare"):
            ep_idx = np.asarray(ep_idx, np.int32)
            dports = np.asarray(dports, np.int32)
            protos = np.asarray(protos, np.int32)
            b = peer_bytes.shape[0]

            # Overlay path (bpf_overlay.c): decapped flows carry the
            # peer's security identity in the tunnel key — trust it over
            # the ipcache LPM when it resolves to a known device row;
            # unknown or zero identities fall back to the LPM walk.
            row_override: Optional[np.ndarray] = None
            if tunnel_identities is not None:
                row_override = self.engine.rows_or_negative(
                    np.asarray(tunnel_identities, np.int64)
                )

        # --- LB stage (egress only): VIP→backend translate -------------
        # bpf_lxc.c:444-455 — the service lookup precedes conntrack and
        # the policy check, so CT tracks the backend tuple and policy
        # sees the backend's identity, exactly like the kernel path.
        svc_drop: Optional[np.ndarray] = None
        revnat_vals: Optional[np.ndarray] = None
        if not ingress and self.lb is not None:
            with bt.phase("lb_translate"):
                lbt = self._lb_tables.get(family)
                if lbt is not None:
                    # hash over STABLE endpoint ids so unrelated
                    # endpoint churn cannot re-select backends for
                    # established flows
                    if self._endpoint_ids:
                        ep_ids = np.asarray(self._endpoint_ids, np.int64)[
                            np.clip(ep_idx, 0, len(self._endpoint_ids) - 1)
                        ]
                    else:
                        ep_ids = ep_idx
                    fh = flow_hash32(
                        peer_bytes, sports, dports, protos, ep_ids
                    )
                    nb, npo, rv, ok, nobk = lb_translate(
                        lbt,
                        jnp.asarray(peer_bytes),
                        jnp.asarray(dports),
                        jnp.asarray(protos),
                        jnp.asarray(fh),
                    )
                    ok = np.asarray(ok)
                    nobk = np.asarray(nobk)
                    if ok.any() or nobk.any():
                        peer_bytes = np.asarray(nb)
                        dports = np.asarray(npo, np.int32)
                        revnat_vals = np.asarray(rv).astype(np.uint16)
                        svc_drop = nobk
                        peer_words = None  # address changed — repack for CT

        # ── device-resident conntrack: ONE fused program per batch ──
        # Host fallbacks: any family with an active LB table (BOTH
        # directions — the CT is one bidirectional structure; an
        # egress VIP flow's entry must be visible to its ingress
        # reply, so the two directions must share a CT domain) and
        # overlay tunnel identities.
        if (
            self._device_ct_bits is not None
            and self._ladder_level < 2
            and sports is not None
            and svc_drop is None
            and row_override is None
            and (self.lb is None or self._lb_tables.get(family) is None)
        ):
            # the donated CT state is threaded batch-to-batch, so this
            # path stays synchronous: run now, return already-done
            result = self._process_device_ct(
                peer_bytes, ep_idx, dports, protos,
                np.asarray(sports, np.int32),
                ingress=ingress, family=family, want_rev_nat=want_rev_nat,
            )
            pending = PendingBatch(self)
            pending._value = result
            pending._event.set()
            return _InFlight(pending, None, bt)

        ct = self.conntrack
        if ct is None or sports is None:
            # No CT: full batch takes the device path (counters on MXU
            # when no padded lanes polluted them).
            enq = self._dispatch_enqueue(
                peer_bytes, ep_idx, dports, protos, ingress=ingress,
                family=family, row_override=row_override, bt=bt,
            )
            pending = PendingBatch(self)

            def finish():
                out = self._dispatch_complete(enq, bt)
                v, red, counters = out[:3]
                rule = l4x = hits = None
                if enq.attrib:
                    rule, l4x, hits = out[3:]
                with bt.phase("counters"):
                    if svc_drop is not None and svc_drop.any():
                        v = v.copy()
                        red = red.copy()
                        v[svc_drop] = DROP_NO_SERVICE
                        red[svc_drop] = False
                        # device counters classified these flows
                        # pre-override — accumulate host-side instead
                        # for this batch
                        counters = None
                        if rule is not None:
                            # no-backend flows never reached a rule —
                            # drop their attribution and re-derive the
                            # hit sums host-side
                            rule = rule.copy()
                            rule[svc_drop] = -1
                            hits = None
                    if counters is None:
                        with self._lock:
                            if self.counters.shape[0] == max(
                                1, len(self._endpoints)
                            ):
                                cls = np.select(
                                    [v == FORWARD, v == DROP_POLICY],
                                    [0, 1], default=2,
                                )
                                np.add.at(self.counters, (ep_idx, cls), 1)
                    else:
                        with self._lock:
                            if self.counters.shape == counters.shape:
                                self.counters += counters
                    self._account_batch(
                        v,
                        shard_of=(
                            self._shard_map(enq.spans, enq.ndev, b)
                            if enq.ndev > 1
                            else None
                        ),
                    )
                    if rule is not None:
                        self._account_attribution(
                            v, rule, l4x, hits, ingress=ingress
                        )
                with bt.phase("emit_events"):
                    self._emit_flow_events(
                        peer_bytes, ep_idx, dports, protos, v,
                        ingress=ingress, family=family, redirect=red,
                        rule=rule, l4_covered=l4x,
                    )
                    if rule is not None:
                        self._record_flows(
                            peer_bytes, ep_idx, dports, protos, v,
                            rule, l4x, red, ingress=ingress,
                        )
                if want_rev_nat:
                    # no CT → replies can't be recognized → no restore
                    return v, red, np.zeros(b, np.uint16)
                return v, red

            return _InFlight(pending, finish, bt, b=b, rev=want_rev_nat)

        # --- conntrack pre-pass (vectorized host) ----------------------
        with bt.phase("ct_prepass"):
            sports = np.asarray(sports, np.int64)
            if peer_words is not None:
                # caller already holds packed address words (IPv4 u32 path)
                peer_hi, peer_lo = peer_words
            else:
                bytes64 = peer_bytes.astype(np.uint64)
                if family == 4:
                    peer_lo = (
                        (bytes64[:, 0] << 24) | (bytes64[:, 1] << 16)
                        | (bytes64[:, 2] << 8) | bytes64[:, 3]
                    )
                    peer_hi = np.zeros(b, np.uint64)
                else:
                    shift = np.arange(7, -1, -1, dtype=np.uint64) * np.uint64(8)
                    peer_hi = (bytes64[:, :8] << shift).sum(axis=1, dtype=np.uint64)
                    peer_lo = (bytes64[:, 8:] << shift).sum(axis=1, dtype=np.uint64)
            direction = np.full(b, 0 if ingress else 1, np.uint64)
            ka, kb, kc = pack_keys(
                peer_hi, peer_lo, ep_idx.astype(np.uint64), sports,
                dports.astype(np.uint64), protos.astype(np.uint64), direction,
            )
            if want_rev_nat:
                from .conntrack import CT_REPLY

                # revNAT ids read under the SAME lock hold as the find:
                # a timer gc()/compact between the lookup and a post-hoc
                # revnat read could hand back another flow's id
                state, slot, ct_rev = ct.lookup_batch(ka, kb, kc, want_revnat=True)
                ct_rev[state != CT_REPLY] = 0
            else:
                state, slot = ct.lookup_batch(ka, kb, kc)
            miss = state == CT_NEW

        verdict = np.full(b, FORWARD, np.int8)
        redirect = np.zeros(b, bool)
        enq = None
        midx = None
        if miss.any():
            midx = np.nonzero(miss)[0]
            enq = self._dispatch_enqueue(
                peer_bytes[midx],
                ep_idx[midx],
                dports[midx],
                protos[midx],
                ingress=ingress,
                family=family,
                bucketed=True,
                row_override=(
                    None if row_override is None else row_override[midx]
                ),
                bt=bt,
            )
        # completion must not create CT entries verdicted under a basis
        # that moved while the batch was in flight
        ct_epoch = self._ct_epoch
        pending = PendingBatch(self)

        def finish():
            rule_full = l4x_full = None
            if enq is not None:
                out = self._dispatch_complete(enq, bt)
                v, red = out[0], out[1]
                at_rule = at_l4x = at_hits = None
                if enq.attrib:
                    at_rule, at_l4x, at_hits = out[3:]
                if svc_drop is not None:
                    sd = svc_drop[midx]
                    v = np.where(sd, np.int8(DROP_NO_SERVICE), v)
                    red = red & ~sd
                    if at_rule is not None and sd.any():
                        # no-backend flows never reached a rule
                        at_rule = np.where(sd, np.int32(-1), at_rule)
                        at_hits = None
                verdict[midx] = v
                redirect[midx] = red
                if at_rule is not None:
                    # CT-bypassed established flows took no policy
                    # decision this batch: rule -1, reason "allowed"
                    # (rule_hits_total counts decisions, not packets)
                    rule_full = np.full(b, -1, np.int32)
                    l4x_full = np.zeros(b, bool)
                    rule_full[midx] = at_rule
                    l4x_full[midx] = at_l4x
                    self._account_attribution(
                        v, at_rule, at_l4x, at_hits, ingress=ingress
                    )
                # CT entries for newly-allowed flows (ct_create4,
                # bpf_lxc.c:~560: only successful verdicts create
                # state). L7-redirect flows are EXCLUDED: a CT bypass
                # would return redirect=False on later packets and
                # route them around the proxy — proxied connections
                # stay on the policy path (the reference tracks them in
                # the proxymap instead).
                ok = (v == FORWARD) & ~red
                if (
                    ok.any()
                    and self.conntrack is ct
                    and self._ct_epoch == ct_epoch
                ):
                    with bt.phase("ct_create"):
                        oidx = midx[ok]
                        ct.create_batch(
                            ka[oidx],
                            kb[oidx],
                            kc[oidx],
                            revnat=(
                                None if revnat_vals is None
                                else revnat_vals[oidx]
                            ),
                        )

            # proxymap handoff: redirected flows carry their full
            # 5-tuple here (sports present) — record for the L7
            # front-end
            self._hand_off_redirects(
                redirect, peer_bytes, ep_idx, sports, dports, protos,
                ingress=ingress, family=family,
            )

            # host counter accumulation (CT hits included)
            with bt.phase("counters"):
                with self._lock:
                    if self.counters.shape[0] == max(1, len(self._endpoints)):
                        cls = np.select(
                            [verdict == FORWARD, verdict == DROP_POLICY],
                            [0, 1],
                            default=2,
                        )
                        np.add.at(self.counters, (ep_idx, cls), 1)
                self._account_batch(verdict)
            with bt.phase("emit_events"):
                self._emit_flow_events(
                    peer_bytes, ep_idx, dports, protos, verdict,
                    ingress=ingress, family=family, redirect=redirect,
                    rule=rule_full, l4_covered=l4x_full,
                )
                if rule_full is not None:
                    self._record_flows(
                        peer_bytes, ep_idx, dports, protos, verdict,
                        rule_full, l4x_full, redirect, ingress=ingress,
                    )
            if want_rev_nat:
                # revNAT restore (bpf/lib/lb.h lb4_rev_nat via the CT
                # entry's rev_nat_index): flows whose CT hit is in the
                # REPLY direction carry the id of the service that
                # translated the original request — the caller rewrites
                # the reply source back to that VIP (rev_nat_frontend()).
                return verdict, redirect, ct_rev
            return verdict, redirect

        return _InFlight(pending, finish, bt, b=b, rev=want_rev_nat)

    def _hand_off_redirects(
        self, redirect, peer_bytes, ep_idx, sports, dports, protos, *,
        ingress: bool, family: int,
    ) -> None:
        """Hand the batch's redirected rows to ``on_redirect_batch`` in
        one call; a batch that redirects nothing calls nothing. Rows
        past ``len(redirect)`` (bucket padding) are never redirected."""
        hook = self.on_redirect_batch
        if hook is None or not redirect.any():
            return
        idx = np.nonzero(redirect)[0]
        hook(
            peer_bytes[idx], ep_idx[idx], sports[idx], dports[idx],
            protos[idx], ingress, family,
        )

    def _process_device_ct(
        self,
        peer_bytes: np.ndarray,
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        sports: np.ndarray,
        *,
        ingress: bool,
        family: int,
        want_rev_nat: bool,
    ):
        """Dispatch through the fused device-CT program and thread the
        donated CT state forward."""
        import time as _time

        from .device_ct import make_state

        tr = self.tracer
        bt = tr.current() if tr.active else _NOOP_BATCH
        direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS
        # same atomic snapshot rule as _dispatch (fused flag must match
        # the tables it was computed with); the fused CT program is not
        # attributed — its drops keep the generic policy reason
        # the fused CT path keeps the plain jnp.take gather even under
        # a 2D plan (GSPMD all-gathers the sharded table — correct,
        # just unoptimized; the CT program is not ident-aware yet)
        tables_map, pf_empty, v6_fused, _fs, _ndev, _at, _i2d, _sh = (
            self._dp_state
        )
        t = tables_map[(direction, family)]
        b = peer_bytes.shape[0]
        pad = _bucket(b) - b
        valid = np.zeros(b + pad, bool)
        valid[:b] = True
        peer_bytes, ep_idx, dports, protos, sports, _ = _pad_flows(
            pad, peer_bytes, ep_idx, dports, protos, sports
        )
        peer = _pack_v4_u32(peer_bytes) if family == 4 else peer_bytes
        now = jnp.asarray(np.int32(_time.monotonic()))
        with self._lock:
            if self._device_ct is None:
                # policyd-survive re-upload: after a quarantine rescue
                # pulled device entries into the host table, the next
                # fresh device table seeds from the host CT so
                # re-promotion onto the fused path does not forget the
                # rescued flows a second time. Without a rescue (the
                # steady-state OFF path) this is one bool read and the
                # exact pre-PR zeros table.
                if self._device_ct_seed and self.conntrack is not None:
                    self._device_ct_seed = False
                    self._device_ct = self._seed_device_ct()
                else:
                    self._device_ct = make_state(self._device_ct_bits)
            state = self._device_ct
            with bt.phase("dispatch"):
                v, red, counters, new_state = process_flows_ct(
                    t,
                    state,
                    jnp.asarray(peer),
                    jnp.asarray(ep_idx),
                    jnp.asarray(dports),
                    jnp.asarray(protos),
                    jnp.asarray(sports),
                    jnp.asarray(np.int32(0 if ingress else 1)),
                    now,
                    jnp.asarray(valid),
                    ep_count=max(1, len(self._endpoints)),
                    prefilter=(
                        ingress
                        and not pf_empty[0 if family == 4 else 1]
                    ),
                    levels=16,
                    family=family,
                    fused=v6_fused if family == 6 else False,
                )
            self._device_ct = new_state
            with bt.phase("host_sync"):
                counters = np.asarray(counters)
            if self.counters.shape == counters.shape:
                self.counters += counters
        with bt.phase("host_sync"):
            verdict = np.asarray(v)[:b]
            redirect = np.asarray(red)[:b]
        with bt.phase("counters"):
            self._account_batch(verdict)
        self._hand_off_redirects(
            redirect, peer_bytes, ep_idx, sports, dports, protos,
            ingress=ingress, family=family,
        )
        self._emit_flow_events(
            peer_bytes[:b], ep_idx[:b], dports[:b], protos[:b], verdict,
            ingress=ingress, family=family, redirect=redirect,
        )
        if want_rev_nat:
            # no LB table was active on this path (fallback condition)
            return verdict, redirect, np.zeros(b, np.uint16)
        return verdict, redirect

    # ------------------------------------------------------------------
    def submit(
        self,
        src_ips: np.ndarray,  # [B] uint32 IPv4 host-order (peer address)
        ep_idx: np.ndarray,  # [B] int32 local endpoint index
        dports: np.ndarray,
        protos: np.ndarray,
        *,
        ingress: bool = True,
        sports: Optional[np.ndarray] = None,
        return_rev_nat: bool = False,
        tunnel_identities: Optional[np.ndarray] = None,
    ) -> PendingBatch:
        """Enqueue an IPv4 batch WITHOUT pulling its results: returns a
        PendingBatch whose .result() blocks on the device round-trip.
        Submitting the next batch before resolving the previous one
        overlaps host prep with device execution (bounded by
        VerdictPipelineDepth — admission past the bound completes the
        oldest in-flight batch first)."""
        src = np.asarray(src_ips)
        peer_bytes = ipv4_to_bytes(src)
        return self._submit(
            peer_bytes, ep_idx, dports, protos, sports,
            ingress=ingress, family=4,
            peer_words=(
                np.zeros(src.shape[0], np.uint64),
                src.astype(np.uint64),
            ),
            want_rev_nat=return_rev_nat,
            tunnel_identities=tunnel_identities,
        )

    def submit_v6(
        self,
        peer_bytes: np.ndarray,  # [B, 16] int32 address bytes
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        *,
        ingress: bool = True,
        sports: Optional[np.ndarray] = None,
        return_rev_nat: bool = False,
        tunnel_identities: Optional[np.ndarray] = None,
    ) -> PendingBatch:
        """IPv6 counterpart of submit()."""
        return self._submit(
            np.asarray(peer_bytes, np.int32), ep_idx, dports, protos, sports,
            ingress=ingress, family=6, want_rev_nat=return_rev_nat,
            tunnel_identities=tunnel_identities,
        )

    def process(
        self,
        src_ips: np.ndarray,  # [B] uint32 IPv4 host-order (peer address)
        ep_idx: np.ndarray,  # [B] int32 local endpoint index
        dports: np.ndarray,
        protos: np.ndarray,
        *,
        ingress: bool = True,
        sports: Optional[np.ndarray] = None,
        return_rev_nat: bool = False,
        tunnel_identities: Optional[np.ndarray] = None,
    ):
        """IPv4 batch → (verdicts [B] int8, redirect [B] bool);
        accumulates the per-endpoint counters. ``src_ips`` is the peer
        address (source for ingress, destination for egress). Passing
        ``sports`` with a conntrack-enabled pipeline activates the CT
        pre-pass (established/reply bypass + creation on allow).
        ``return_rev_nat`` appends a [B] uint16 array of revNAT ids for
        reply-direction CT hits (0 otherwise) — resolve with
        rev_nat_frontend() to restore the VIP on reply sources.
        ``tunnel_identities`` ([B] int, 0 = none) marks overlay-decapped
        flows whose encap key carried the peer identity — trusted over
        the ipcache LPM when known (bpf_overlay.c)."""
        return self.submit(
            src_ips, ep_idx, dports, protos,
            ingress=ingress, sports=sports, return_rev_nat=return_rev_nat,
            tunnel_identities=tunnel_identities,
        ).result()

    def process_v6(
        self,
        peer_bytes: np.ndarray,  # [B, 16] int32 address bytes
        ep_idx: np.ndarray,
        dports: np.ndarray,
        protos: np.ndarray,
        *,
        ingress: bool = True,
        sports: Optional[np.ndarray] = None,
        return_rev_nat: bool = False,
        tunnel_identities: Optional[np.ndarray] = None,
    ):
        """IPv6 batch (16-level LPM walk, bpf_lxc.c:848 tail_ipv6_*)."""
        return self.submit_v6(
            peer_bytes, ep_idx, dports, protos,
            ingress=ingress, sports=sports, return_rev_nat=return_rev_nat,
            tunnel_identities=tunnel_identities,
        ).result()

    def rev_nat_frontend(self, revnat_id: int):
        """revNAT id (from a return_rev_nat=True process call) → the
        original frontend L3n4Addr, or None."""
        if self.lb is None or not revnat_id:
            return None
        return self.lb.rev_nat(int(revnat_id))
