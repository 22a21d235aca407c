"""Batched policy-verdict kernel (matmul formulation).

Evaluates, entirely on device, the verdict semantics of
pkg/policy/repository.go AllowsIngressRLocked/AllowsEgressRLocked for a
batch of flows (subject identity row, peer identity row, dport, proto):

    deny      = any(subj ∧ ((1-peer) @ deny_matᵀ > 0))
    l3_allow  = any(subj ∧ (peer @ allow_matᵀ > 0))
    req_ok    = ¬deny                        # folded-requirements term
    combo     = (subj @ s1) ∧ (port_onehot @ p1)
    l4_allow  = any(combo ∧ peer@enᵀ) | req_ok ∧ any(combo ∧ peer@eeᵀ)
    l7_present= any((subj @ s7) ∧ (port @ p7) ∧ (group_ok @ g7))
    verdict   = ALLOW  if l3_allow ∧ ¬deny
              | ALLOW  if flow has L4 context ∧ l4_allow
              | DENY   otherwise

Per flow the only data-dependent access is ONE packed row-gather from
``sel_match`` (an embedding lookup); everything else is int8 matmuls on
the MXU plus elementwise logic on the VPU. This is deliberate: TPU
executes per-element dynamic gathers essentially serially, so the
earlier gather-per-(flow, rule-pair) formulation ran ~1000× slower than
this one.
"""

from __future__ import annotations

import functools

import chex
import jax
import jax.numpy as jnp

from ..compiler.program import DirectionProgram
from ..policy.search import Decision
from .bitmap import unpack_bits_u32

ALLOW = int(Decision.ALLOWED)
DENY = int(Decision.DENIED)

# -- verdict attribution (policyd-flows) ---------------------------------
# Per-flow attribution reason codes emitted by the attrib=True kernel
# variant. These classify WHICH term decided the flow; the pipeline maps
# them onto the monitor's DropNotify reason taxonomy
# (monitor/events.py REASON_POLICY_*).
ATTR_ALLOW = 0  # allowed (rule = the first-match allowing rule)
ATTR_DENY_RULE = 1  # an explicit deny (FromRequires) rule matched
ATTR_NO_L3 = 2  # dropped: no L3 allow covered the peer
ATTR_NO_L4 = 3  # dropped: L4 coverage existed, peer not allowed
ATTR_L7 = 4  # allowed via a parser-bearing filter (proxy redirect)

ATTR_NAMES = {
    ATTR_ALLOW: "allowed",
    ATTR_DENY_RULE: "deny-rule",
    ATTR_NO_L3: "no-l3-match",
    ATTR_NO_L4: "no-l4-match",
    ATTR_L7: "l7-redirect",
}

# Sentinel for "no rule contributes to this term" in the origin arrays
# (min-reduction identity; converted to -1 in the per-flow output).
NO_RULE = 2**31 - 1


@chex.dataclass(frozen=True)
class AttribTables:
    """Term→rule origin arrays for the attribution kernel variant:
    the FIRST (lowest-index) repository rule contributing each deny
    subject-selector, pure-L3-allow subject-selector, and L4 combo —
    first-contributing-rule-wins mirrors the reference's in-order rule
    walk. Entries with no contributing rule hold ``NO_RULE``. Built by
    ``compiler.program.build_attrib_tables``."""

    deny_rule: jnp.ndarray  # [S] int32
    allow_rule: jnp.ndarray  # [S] int32
    combo_rule: jnp.ndarray  # [K1] int32


@chex.dataclass(frozen=True)
class Attribution:
    """Per-flow attribution (attrib=True only). ``rule``: repository
    rule index that decided the flow (-1 = no rule — a no-match drop).
    ``reason``: ATTR_* code."""

    rule: jnp.ndarray  # [B] int32
    reason: jnp.ndarray  # [B] int8


@chex.dataclass(frozen=True)
class Verdict:
    """Per-flow results. ``decision``: 1 allow / 2 deny. ``l3`` is the
    pure-L3 stage decision (0 undecided / 1 allowed / 2 denied) used by
    the policymap materializer; ``l7_redirect`` flags flows whose L4
    allow passes through a parser-bearing filter (proxy redirect)."""

    decision: jnp.ndarray
    l3: jnp.ndarray
    l7_redirect: jnp.ndarray


@chex.dataclass(frozen=True)
class DeviceTables:
    """DirectionProgram matrices as device arrays. Transposed copies of
    the peer-side relations are stored so the kernel's contractions all
    run with the contracted axis leading (no per-call transpose)."""

    deny_t: jnp.ndarray  # [S, S]  deny_matᵀ
    allow_t: jnp.ndarray  # [S, S]  allow_matᵀ
    ports: jnp.ndarray  # [P4]
    protos: jnp.ndarray  # [P4]
    s1_mat: jnp.ndarray  # [S, K1]
    p1_mat: jnp.ndarray  # [P4, K1]
    en_t: jnp.ndarray  # [S, K1]  en_matᵀ
    ee_t: jnp.ndarray  # [S, K1]  ee_matᵀ
    gpn_mat: jnp.ndarray  # [S, G]
    gpe_mat: jnp.ndarray  # [S, G]
    group_no_peers: jnp.ndarray  # [G]
    s7_mat: jnp.ndarray  # [S, K7]
    p7_mat: jnp.ndarray  # [P4, K7]
    g7_mat: jnp.ndarray  # [G, K7]

    @classmethod
    def from_host(cls, d: DirectionProgram, sharding=None) -> "DeviceTables":
        """Upload each matrix: an uncommitted default-device array, or
        with ``sharding`` placed straight from the host copy. The
        transposed copies are made on the device: a host transpose of
        an [S, S] int8 matrix costs seconds at 100k rules, and an
        upload of a transposed view makes one for every device. Each
        transpose finishes before the next upload starts, so the device
        holds at most one upright copy beside the tables (1.4 GB at
        100k rules; all of them at once reached 95% of a v5e's memory)."""
        def put(a):
            return jnp.asarray(a) if sharding is None else jax.device_put(a, sharding)

        def put_t(a):
            return put(a).T.block_until_ready()

        return cls(
            deny_t=put_t(d.deny_mat),
            allow_t=put_t(d.allow_mat),
            ports=put(d.ports),
            protos=put(d.protos),
            s1_mat=put(d.s1_mat),
            p1_mat=put(d.p1_mat),
            en_t=put_t(d.en_mat),
            ee_t=put_t(d.ee_mat),
            gpn_mat=put(d.gpn_mat),
            gpe_mat=put(d.gpe_mat),
            group_no_peers=put(d.group_no_peers),
            s7_mat=put(d.s7_mat),
            p7_mat=put(d.p7_mat),
            g7_mat=put(d.g7_mat),
        )


@chex.dataclass(frozen=True)
class DevicePolicy:
    """Fully device-resident compiled policy."""

    id_bits: jnp.ndarray  # [N, W] uint32
    sel_match: jnp.ndarray  # [N, S/32] uint32 (bit-packed selector matches)
    ingress: DeviceTables
    egress: DeviceTables


def _mm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """int8 [B, A] @ int8 [A, C] → bool [B, C] (int32 accumulate)."""
    return (
        jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
        > 0
    )


def _verdict_block(
    sel_match: jnp.ndarray,
    t: DeviceTables,
    subj_rows: jnp.ndarray,
    peer_rows: jnp.ndarray,
    dport: jnp.ndarray,
    proto: jnp.ndarray,
    has_l4: jnp.ndarray,
    origin: "AttribTables" = None,
):
    subj8 = unpack_bits_u32(jnp.take(sel_match, subj_rows, axis=0))  # [b, S]
    peer8 = unpack_bits_u32(jnp.take(sel_match, peer_rows, axis=0))
    subj_b = subj8.astype(bool)

    deny_vec = subj_b & _mm(jnp.int8(1) - peer8, t.deny_t)  # [b, S]
    allow_vec = subj_b & _mm(peer8, t.allow_t)  # [b, S]
    deny = deny_vec.any(axis=1)
    l3_allow = allow_vec.any(axis=1)
    req_ok = ~deny

    pp = (
        (dport[:, None] == t.ports[None, :])
        & (proto[:, None] == t.protos[None, :])
        & has_l4[:, None]
    ).astype(jnp.int8)

    combo = _mm(subj8, t.s1_mat) & _mm(pp, t.p1_mat)  # [b, K1]
    en_hit = combo & _mm(peer8, t.en_t)  # [b, K1]
    ee_hit = combo & _mm(peer8, t.ee_t)  # [b, K1]
    l4_allow = en_hit.any(axis=1) | (req_ok & ee_hit.any(axis=1))

    group_ok = (
        _mm(peer8, t.gpn_mat)
        | (_mm(peer8, t.gpe_mat) & req_ok[:, None])
        | t.group_no_peers[None, :]
    )  # [b, G]
    l7_present = (
        _mm(subj8, t.s7_mat)
        & _mm(pp, t.p7_mat)
        & _mm(group_ok.astype(jnp.int8), t.g7_mat)
    ).any(axis=1)

    l3 = jnp.where(deny, jnp.int8(2), jnp.where(l3_allow, jnp.int8(1), jnp.int8(0)))
    decision = jnp.where(
        l3_allow & ~deny,
        jnp.int8(ALLOW),
        jnp.where(has_l4 & l4_allow, jnp.int8(ALLOW), jnp.int8(DENY)),
    )
    # Datapath redirect semantics (bpf/lib/policy.h lookup order: the
    # exact {id,port,proto} entry wins over the L3-only entry): a flow
    # allowed at L4 through a parser-bearing filter redirects even when
    # L3 also allows it.
    l7_redirect = has_l4 & l4_allow & l7_present
    verdict = Verdict(decision=decision, l3=l3, l7_redirect=l7_redirect)
    if origin is None:
        return verdict

    # -- attribution (policyd-flows): first-match rule + reason ----------
    # Masked min over the pre-reduction term vectors picks the LOWEST
    # repository rule index whose cell fired — the reference's in-order
    # rule walk stops at the first decider. All [b, S]/[b, K1] operands
    # already exist above; this adds three where+min reductions and a
    # select chain, no extra matmuls or gathers.
    def _first(mask, rule_of):
        return jnp.min(
            jnp.where(mask, rule_of[None, :], jnp.int32(NO_RULE)), axis=1
        )

    deny_rule = _first(deny_vec, origin.deny_rule)
    allow_rule = _first(allow_vec, origin.allow_rule)
    combo_fired = en_hit | (req_ok[:, None] & ee_hit)  # [b, K1]
    l4_rule = _first(combo_fired, origin.combo_rule)

    # Attribute by what actually DECIDED: pure-L3 allow wins over the
    # L4 path (repository walk order); a deny only decides when the
    # flow really dropped (an en-side L4 entry can allow past a deny).
    allowed = decision == jnp.int8(ALLOW)
    l3_decides = l3_allow & ~deny
    rule = jnp.where(
        allowed,
        jnp.where(l3_decides, allow_rule, l4_rule),
        jnp.where(deny, deny_rule, jnp.int32(NO_RULE)),
    )
    rule = jnp.where(rule == NO_RULE, jnp.int32(-1), rule)

    # Drop refinement: with L4 context and any combo covering the
    # subject at this port, the peer was the missing half (no-L4);
    # otherwise nothing covered the flow at all (no-L3).
    l4_covered = has_l4 & combo.any(axis=1)
    dropped = decision == jnp.int8(DENY)
    reason = jnp.where(
        dropped,
        jnp.where(
            deny,
            jnp.int8(ATTR_DENY_RULE),
            jnp.where(l4_covered, jnp.int8(ATTR_NO_L4), jnp.int8(ATTR_NO_L3)),
        ),
        jnp.where(l7_redirect, jnp.int8(ATTR_L7), jnp.int8(ATTR_ALLOW)),
    )
    return verdict, Attribution(rule=rule, reason=reason)


@functools.partial(
    jax.jit, static_argnames=("ingress", "block", "attrib", "n_rules")
)
def verdict_batch(
    policy: DevicePolicy,
    subj_rows: jnp.ndarray,  # [B] int32 identity rows
    peer_rows: jnp.ndarray,  # [B] int32
    dport: jnp.ndarray,  # [B] int32 (with has_l4)
    proto: jnp.ndarray,  # [B] int32 IANA proto (u8proto)
    has_l4: jnp.ndarray,  # [B] bool — False = pure-L3 query
    ingress: bool = True,
    block: int = 8192,
    attrib: bool = False,
    origin: AttribTables = None,
    n_rules: int = 0,
):
    """Batch verdicts; blocks the batch with lax.map to bound the
    [block, S] activation footprint.

    With ``attrib=False`` (default) this traces exactly the program it
    always has — ``origin=None`` contributes no leaves to the jaxpr and
    the attribution tail is never staged. With ``attrib=True`` (static,
    so the off path keeps its own executable) returns
    ``(Verdict, Attribution, hits)`` where ``hits`` is the [n_rules]
    int32 per-rule hit counter, segment-summed on device so the host
    pulls R scalars instead of B."""
    t = policy.ingress if ingress else policy.egress
    b = subj_rows.shape[0]
    pad = (-b) % block

    def pad1(x, fill=0):
        return jnp.pad(x, (0, pad), constant_values=fill).reshape(-1, block)

    args = (pad1(subj_rows), pad1(peer_rows), pad1(dport), pad1(proto), pad1(has_l4))
    out = jax.lax.map(
        lambda xs: _verdict_block(
            policy.sel_match, t, *xs, origin=origin if attrib else None
        ),
        args,
    )
    out = jax.tree_util.tree_map(lambda x: x.reshape(-1)[:b], out)
    if not attrib:
        return out
    verdict, attribution = out
    valid = attribution.rule >= 0
    idx = jnp.clip(attribution.rule, 0, max(n_rules - 1, 0))
    hits = jnp.zeros((max(n_rules, 1),), jnp.int32).at[idx].add(
        valid.astype(jnp.int32)
    )[:n_rules]
    return verdict, attribution, hits
