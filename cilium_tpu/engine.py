"""PolicyEngine: the device-backed policy resolver.

The TPU-native counterpart of the reference's per-endpoint regeneration
entry points (pkg/endpoint/policy.go regeneratePolicy →
repository.AllowsIngress*): owns a Repository + IdentityRegistry,
compiles them into device tensors, refreshes when revisions move, and
answers batched verdict queries.

The refresh is the "datapath compile" of this framework — instead of
clang→llc per endpoint (pkg/datapath/loader/compile.go), it re-packs
numpy tables and lets jit shape-bucketing reuse compiled XLA programs.

Refresh is **incremental** where the reference's is per-endpoint
(pkg/endpoint/policy.go:506-552 revision gate): identity churn becomes
device row updates (id_bits + sel_match rows), and rule imports that
fit the existing shape buckets append matrix cells in place
(compiler.DirectionPacker) with only the new selector columns
recomputed. Full recompiles happen only on bucket overflow, rule
deletion, or vocab word growth.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import metrics as _metrics
from . import u8proto
from .compiler import (
    CompiledPolicy,
    compile_policy_state,
    host_selector_matches,
    try_append_rules,
)
from .compiler.program import rule_origin_arrays, subject_sids, unpack_conjuncts
from .identity import IdentityRegistry
from .identity.model import MAX_USER_IDENTITY
from .ops.bitmap import compute_selector_matches
from .ops.verdict import (
    ALLOW,
    ATTR_NAMES,
    AttribTables,
    DevicePolicy,
    DeviceTables,
    Verdict,
    verdict_batch,
)
from .policy.repository import Repository

PROTO_TCP = u8proto.TCP
PROTO_UDP = u8proto.UDP


@jax.jit
def _set_rows(buf: jnp.ndarray, idx: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    # No donation: concurrent readers may still hold the old buffer.
    return buf.at[idx].set(rows)


@jax.jit
def _set_rows2(
    a: jnp.ndarray,
    b: jnp.ndarray,
    idx: jnp.ndarray,
    rows_a: jnp.ndarray,
    rows_b: jnp.ndarray,
):
    """Row-update two buffers in ONE dispatch (device round trips
    dominate small updates)."""
    return a.at[idx].set(rows_a), b.at[idx].set(rows_b)


@jax.jit
def _set_rows_cols(
    buf: jnp.ndarray,
    rows: jnp.ndarray,  # [k] int32
    cols: jnp.ndarray,  # [w] int32
    vals: jnp.ndarray,  # [k, w]
) -> jnp.ndarray:
    """Sparse rows × word-window scatter for sel_match: a new selector
    matching k identities uploads O(k · window) words, not [N, S/32].
    Duplicate row indices (power-of-two padding repeats the last row)
    carry identical values, so the scatter stays deterministic."""
    return buf.at[rows[:, None], cols[None, :]].set(vals)


@jax.jit
def _set_col_window(
    buf: jnp.ndarray,
    start_word: jnp.ndarray,  # scalar int32
    window: jnp.ndarray,  # [N, w]
) -> jnp.ndarray:
    """Dense fallback when most identities match the appended
    selectors: upload the whole touched word window (still O(N · w),
    never the full matrix). Traced start keeps one program per width."""
    return jax.lax.dynamic_update_slice(buf, window, (jnp.int32(0), start_word))


def _pow2_rows(rows: np.ndarray) -> np.ndarray:
    """Pad a row-index list to a power-of-two bucket (min 8) by
    repeating the last row, bounding _set_rows_cols recompiles."""
    k = rows.shape[0]
    bucket = 8
    while bucket < k:
        bucket <<= 1
    if bucket == k:
        return rows
    return np.concatenate([rows, np.repeat(rows[-1:], bucket - k)])


def _pack_match_words(m: np.ndarray) -> np.ndarray:
    """[k, S] bool → [k, S/32] uint32 in sel_match bit order (S is a
    multiple of 128, so the byte view folds cleanly into words)."""
    packed = np.packbits(m, axis=1, bitorder="little")  # [k, S/8] uint8
    return packed.view(np.uint32).reshape(m.shape[0], m.shape[1] // 32)


def _policy_from_host(compiled, sel_match, placement) -> DevicePolicy:
    """The compiled tables uploaded as ``placement`` says (see
    ``PolicyEngine.set_placement``); ``sel_match`` may already be on a
    device."""
    if placement is None:
        return DevicePolicy(
            id_bits=jnp.asarray(compiled.id_bits),
            sel_match=jnp.asarray(sel_match),
            ingress=DeviceTables.from_host(compiled.ingress),
            egress=DeviceTables.from_host(compiled.egress),
        )
    rep, ident = placement
    return DevicePolicy(
        id_bits=jax.device_put(compiled.id_bits, rep),
        sel_match=jax.device_put(sel_match, ident),
        ingress=DeviceTables.from_host(compiled.ingress, rep),
        egress=DeviceTables.from_host(compiled.egress, rep),
    )


def _place_policy(device: DevicePolicy, placement) -> DevicePolicy:
    """``device`` moved to ``placement`` (None: uncommitted arrays on
    the default device, by way of the host)."""
    if placement is None:
        def put_rep(a):
            return jnp.asarray(np.asarray(a))
        put_ident = put_rep
    else:
        rep, ident = placement

        def put_rep(a):
            return jax.device_put(a, rep)

        def put_ident(a):
            return jax.device_put(a, ident)
    return DevicePolicy(
        id_bits=put_rep(device.id_bits),
        sel_match=put_ident(device.sel_match),
        ingress=jax.tree_util.tree_map(put_rep, device.ingress),
        egress=jax.tree_util.tree_map(put_rep, device.egress),
    )


class PolicyEngine:
    # Delta-log ring consumed by DatapathPipeline for incremental
    # policymap materialization.
    DELTA_LOG_CAP = 512

    def __init__(self, repo: Repository, registry: IdentityRegistry) -> None:
        self.repo = repo
        self.registry = registry
        self._lock = threading.Lock()
        self._compiled: Optional[CompiledPolicy] = None
        self._state = None  # compiler.CompileState
        self._device: Optional[DevicePolicy] = None
        self._sel_match_host: Optional[np.ndarray] = None
        # Dense row table for the compact ranges (reserved + user,
        # < 65536) and a dict for sparse local/CIDR identities
        # (≥ LOCAL_IDENTITY_BASE = 1<<24) — a dense table over the full
        # numeric space would be ~64MB per refresh.
        self._low_rows: Optional[np.ndarray] = None
        self._high_rows: dict = {}
        self._conj_unpacked = None  # cached unpack_conjuncts result
        # Identity change feed (registry observer) + outward delta log.
        self._pending_idents: List[Tuple[object, bool]] = []
        registry.observe(
            lambda ident, added: self._pending_idents.append((ident, added))
        )
        self.delta_seq = 0
        self._delta_log: List[Tuple[int, str, tuple]] = []
        self._bg_refresh: Optional[threading.Thread] = None
        self._install_gen = 0  # bumps on every _install_compiled
        # (key, {ingress: AttribTables}, n_rules) — rule-origin tables
        # for verdict attribution, rebuilt when the compile moves
        self._attrib_cache: Optional[tuple] = None
        # (replicated, ident) shardings of a 2D placement plan, or None:
        # where the device tables live (set_placement)
        self._placement: Optional[tuple] = None

    # ------------------------------------------------------------------
    def set_placement(self, replicated=None, ident=None) -> None:
        """Place the device tables as the datapath's placement plan
        says. On a 2D plan (``ident`` given) ``sel_match`` is row-sharded
        with ``ident`` and every other table is ``replicated`` on each
        device of the plan, built there from the host copy: no full
        table is staged on the default device first, and none stays
        there beside the placed copies. Without ``ident`` the tables
        are uncommitted arrays on the default device, as the 1D and
        single-device plans use them. Re-places what is already
        uploaded when the placement moves."""
        new = (replicated, ident) if ident is not None else None
        with self._lock:
            if new == self._placement:
                return
            self._placement = new
            if self._device is not None:
                self._device = _place_policy(self._device, new)

    def _log_delta(self, kind: str, payload: tuple) -> None:
        self.delta_seq += 1
        self._delta_log.append((self.delta_seq, kind, payload))
        if len(self._delta_log) > self.DELTA_LOG_CAP:
            del self._delta_log[: len(self._delta_log) - self.DELTA_LOG_CAP]

    def deltas_since(self, seq: int):
        """Refresh deltas with seq > ``seq`` (oldest first), or None when
        the log has been truncated past that point (consumer must do a
        full rebuild)."""
        with self._lock:
            if seq >= self.delta_seq:
                return []
            if self._delta_log and self._delta_log[0][0] > seq + 1:
                return None
            if not self._delta_log and self.delta_seq > seq:
                return None
            return list(e for e in self._delta_log if e[0] > seq)

    # ------------------------------------------------------------------
    def _stale(self) -> bool:
        c = self._compiled
        return (
            c is None
            or c.revision != self.repo.revision
            or c.identity_version != self.registry.version
        )

    # policyd: refresh-path
    def refresh(self, force: bool = False) -> CompiledPolicy:
        """Recompile (or incrementally patch) if repository or identity
        state moved (the revision gate of pkg/endpoint/policy.go:506).

        A snapshot-RESTORED engine (untrusted counters, revision < 0)
        refreshes in the BACKGROUND instead: the restored tables keep
        serving verdicts while the O(identities × rules) recompile runs
        — the pinned-map continuity the reference gets from maps that
        outlive the agent (daemon/state.go:53,135). Every other path is
        synchronous as before."""
        with self._lock:
            if not force and not self._stale():
                return self._compiled  # type: ignore[return-value]
            if (
                not force
                and self._compiled is not None
                and self._compiled.revision < 0
            ):
                self._kick_background_refresh()
                return self._compiled
            if force or self._compiled is None:
                return self._full_refresh()

            t0 = time.perf_counter()
            c = self._compiled
            rule_ops = []
            if c.revision != self.repo.revision:
                rule_ops = self.repo.changes_since(c.revision)
                if rule_ops is None or any(
                    op not in ("add", "delete") for _, op, _ in rule_ops
                ):
                    return self._full_refresh()
            if rule_ops and self._state is None:
                # snapshot-restored engines carry no incremental
                # CompileState: any rule movement means a full rebuild
                return self._full_refresh()

            if not self._apply_identity_delta():
                return self._full_refresh()
            # Each applied op advances c.revision to ITS revision (never a
            # re-read of repo.revision): a concurrent AddList landing
            # between changes_since() and here must stay stale so the
            # next refresh picks it up (otherwise its rules — including
            # deny rules → fail-open — would never compile).
            for rev, op, payload in rule_ops:
                if op == "add":
                    # payload is the tuple of rules added at that rev
                    if not self._apply_rule_append(list(payload), rev):
                        return self._full_refresh()
                else:  # "delete": payload = (labels, deleted_rules)
                    if len(payload) < 2 or not self._apply_rule_delete(
                        list(payload[1]), rev
                    ):
                        return self._full_refresh()
            _metrics.engine_refresh_seconds.observe(
                time.perf_counter() - t0, {"kind": "incremental"}
            )
            _metrics.engine_refreshes_total.inc({"kind": "incremental"})
            return c

    @staticmethod
    def _compute_full(repo, registry, placement=None):
        """The expensive half of a full refresh (host compile + device
        upload, placed as ``placement`` says: see set_placement),
        lock-free so the background-continuity path can run it while
        restored tables keep serving."""
        compiled, state = compile_policy_state(repo, registry)
        sel_match = compute_selector_matches(
            jnp.asarray(compiled.id_bits),
            jnp.asarray(compiled.conj_req),
            jnp.asarray(compiled.conj_forbid),
            jnp.asarray(compiled.conj_valid),
            jnp.asarray(compiled.req_count),
        )
        # the upload runs where the refresh runs: under the engine lock
        # on the synchronous path, as the default-device upload always
        # did (the refresh is the control plane's table swap; readers
        # keep the previous tables until the install)
        device = _policy_from_host(  # policyd-lint: disable=LOCK002
            compiled, sel_match, placement
        )
        return compiled, state, sel_match, device, placement

    def _install_compiled(self, compiled, state, sel_match, device,
                          placement=None) -> None:
        """Swap a computed full-refresh result in (lock held)."""
        self._install_gen += 1
        if placement != self._placement:
            # the plan moved while a background refresh computed
            device = _place_policy(device, self._placement)
        self._device = device
        # np.array (copy): asarray on a device buffer is read-only and
        # the incremental paths mutate this in place.
        self._sel_match_host = np.array(sel_match)
        low = np.full(MAX_USER_IDENTITY + 1, -1, np.int32)
        high: dict = {}
        for ident, row in compiled.id_to_row.items():
            if ident < low.size:
                low[ident] = row
            else:
                high[ident] = row
        self._low_rows = low
        self._high_rows = high
        self._compiled = compiled
        self._state = state
        self._conj_unpacked = None
        self._pending_idents.clear()
        self._log_delta("full", ())

    def _full_refresh(self) -> CompiledPolicy:
        t0 = time.perf_counter()
        self._install_compiled(
            *self._compute_full(self.repo, self.registry, self._placement)
        )
        _metrics.engine_refresh_seconds.observe(
            time.perf_counter() - t0, {"kind": "full"}
        )
        _metrics.engine_refreshes_total.inc({"kind": "full"})
        return self._compiled

    # -- incremental paths ---------------------------------------------
    # policyd: refresh-path
    def _apply_identity_delta(self) -> bool:
        """Apply pending identity adds/releases as device row updates.
        False → caller must full-rebuild."""
        c = self._compiled
        assert c is not None
        target_version = self.registry.version
        if c.identity_version == target_version:
            return True
        pend = list(self._pending_idents)
        # The observer feed must cover exactly the version gap; if the
        # engine attached late or events were lost, rebuild.
        if len(pend) != target_version - c.identity_version:
            return False
        if self.registry.padded_rows() != c.id_bits.shape[0]:
            # row-capacity bucket crossed → the device tables reshape
            # and every jitted program over them recompiles
            _metrics.jit_shape_buckets_total.inc(
                {"site": "engine_rows", "result": "miss"}
            )
            return False
        _metrics.jit_shape_buckets_total.inc(
            {"site": "engine_rows", "result": "hit"}
        )

        vocab = self.registry.vocab
        touched: List[int] = []
        plans: List[Tuple[int, bool, object]] = []
        for ident, added in pend:
            row = self.registry.row(ident.id)
            if row is None:
                return False
            if added:
                bits = vocab.identity_bits(ident.labels)  # may grow vocab
                plans.append((row, True, (ident, bits)))
            else:
                plans.append((row, False, ident))
        if vocab.num_words > c.num_words:
            return False  # new label words → conjunct arrays reshape

        events: List[Tuple[int, int, bool]] = []
        for row, added, info in plans:
            if added:
                ident, bits = info
                c.id_bits[row] = vocab.pack(bits, c.num_words)
                c.row_ids[row] = ident.id
                c.row_live[row] = True
                c.id_to_row[ident.id] = row
                self._set_row_index(ident.id, row)
                events.append((row, ident.id, True))
            else:
                ident = info
                c.id_bits[row] = 0
                c.row_live[row] = False
                c.id_to_row.pop(ident.id, None)
                self._set_row_index(ident.id, -1)
                events.append((row, ident.id, False))
            touched.append(row)

        rows = sorted(set(touched))
        idx = np.asarray(rows, np.int32)
        # Recompute sel_match rows host-side (small [k, S] matmul);
        # unpacked conjunct operands are cached across identity churn.
        sub_bits = c.id_bits[idx]
        if self._conj_unpacked is None:
            self._conj_unpacked = unpack_conjuncts(c.conj_req, c.conj_forbid)
        m = host_selector_matches(
            sub_bits,
            c.conj_req,
            c.conj_forbid,
            c.conj_valid,
            c.req_count,
            unpacked=self._conj_unpacked,
        )  # [k, S]
        words = _pack_match_words(m)
        assert self._sel_match_host is not None
        self._sel_match_host[idx] = words

        device = self._device
        assert device is not None
        new_bits, new_match = _set_rows2(
            device.id_bits,
            device.sel_match,
            jnp.asarray(idx),
            jnp.asarray(sub_bits),
            jnp.asarray(words),
        )
        self._device = DevicePolicy(
            id_bits=new_bits,
            sel_match=new_match,
            ingress=device.ingress,
            egress=device.egress,
        )
        # Only the processed prefix is consumed — events racing in during
        # this delta stay queued and are covered by the next refresh.
        c.identity_version = target_version
        del self._pending_idents[: len(pend)]
        _metrics.engine_delta_rows_total.inc(value=len(events))
        # payload: (row, identity_id, live) events in apply order
        self._log_delta("rows", tuple(events))
        return True

    # policyd: refresh-path
    @staticmethod
    def _patch_tables(tables: DeviceTables, writes) -> DeviceTables:
        """Apply a DirectionPacker write log as per-matrix scatters —
        only the touched cells travel to the device, not the matrices.
        Transposed fields (deny_t/allow_t/en_t/ee_t) swap indices."""
        if not writes:
            return tables
        by_name: dict = {}
        for name, i, j, v in writes:
            by_name.setdefault(name, []).append((i, j, v))
        transposed = {"deny": "deny_t", "allow": "allow_t", "en": "en_t", "ee": "ee_t"}
        direct = {
            "s1": "s1_mat", "p1": "p1_mat", "gpn": "gpn_mat", "gpe": "gpe_mat",
            "s7": "s7_mat", "p7": "p7_mat", "g7": "g7_mat",
        }
        reps: dict = {}
        for name, items in by_name.items():
            ii = np.asarray([x[0] for x in items])
            jj = np.asarray([x[1] for x in items])
            # value carried per write: 1 for appends, 0 for deletion
            # retractions (DirectionPacker.remove_rule)
            # control-plane scatter prep: one upload per touched table
            # (≤9 names), not a per-flow loop — the serving path never
            # runs this
            vv8 = jnp.asarray(  # policyd-lint: disable=TPU002
                np.asarray([x[2] for x in items], np.int8)
            )
            if name in transposed:
                field = transposed[name]
                mat = getattr(tables, field)
                reps[field] = mat.at[jj, ii].set(vv8)
            elif name in direct:
                field = direct[name]
                mat = getattr(tables, field)
                reps[field] = mat.at[ii, jj].set(vv8)
            elif name == "group_no_peers":
                reps["group_no_peers"] = tables.group_no_peers.at[ii].set(
                    jnp.asarray(np.asarray([x[2] for x in items], bool))
                )
            elif name == "port_vocab":
                # (pid, port, proto): jj = port, third = proto
                vv = np.asarray([x[2] for x in items])
                reps["ports"] = tables.ports.at[ii].set(jnp.asarray(jj, jnp.int32))
                reps["protos"] = tables.protos.at[ii].set(jnp.asarray(vv, jnp.int32))
            else:  # pragma: no cover - unknown write kind
                raise KeyError(name)
        return tables.replace(**reps)

    # policyd: refresh-path
    def _apply_rule_append(self, rules, revision: int) -> bool:
        """Append a rule batch in place, advancing the compiled revision
        to the op's own revision. False → full rebuild needed."""
        c = self._compiled
        assert c is not None and self._state is not None
        res = try_append_rules(c, self._state, self.registry, rules, revision)
        if res is None:
            return False
        self._conj_unpacked = None  # conjunct rows changed
        old_s, new_s = res
        new_match = None
        if new_s > old_s:
            # New selector columns: match against ALL identities, then
            # OR the bits into the packed words (columns were zero).
            m = host_selector_matches(
                c.id_bits,
                c.conj_req[old_s:new_s],
                c.conj_forbid[old_s:new_s],
                c.conj_valid[old_s:new_s],
                c.req_count[old_s:new_s],
            )  # [N, k]
            sm = self._sel_match_host
            assert sm is not None
            for j, sid in enumerate(range(old_s, new_s)):
                col = m[:, j]
                if col.any():
                    sm[:, sid >> 5] |= col.astype(np.uint32) << np.uint32(sid & 31)
            # CSR-style device update: only the word WINDOW the new
            # selector bits land in moves, and only for the rows that
            # matched — k identities cost O(k · window) words, not the
            # full [N, S/32] re-upload this used to be.
            w0, w1 = old_s >> 5, (new_s - 1) >> 5
            cols = np.arange(w0, w1 + 1, dtype=np.int32)
            touched = np.nonzero(m.any(axis=1))[0]
            new_match = self._scatter_sel_window(sm, touched, cols)
            if touched.size:
                # payload: (sel_lo, sel_hi, touched identity rows) — the
                # CSR column-delta consumers (pipeline placed-copy
                # patching) replay against the host mirror's FINAL
                # state, so re-application is idempotent and ordering
                # against "rows" events is irrelevant
                self._log_delta(
                    "cols", (old_s, new_s, tuple(int(r) for r in touched))
                )
                # host counter: ``touched`` is the np row index set
                # from the host mirror diff, never a device array
                _metrics.engine_delta_cols_total.inc(value=int(touched.size))  # policyd-lint: disable=TPU005
        device = self._device
        assert device is not None
        self._device = DevicePolicy(
            id_bits=device.id_bits,
            sel_match=(
                new_match if new_match is not None else device.sel_match
            ),
            ingress=self._patch_tables(
                device.ingress, self._state.ingress.take_writes()
            ),
            egress=self._patch_tables(
                device.egress, self._state.egress.take_writes()
            ),
        )
        # payload: op + the subject selector ids the batch touches —
        # every verdict term is subject-gated, so these columns bound
        # the policymap cells the delta can change (the pipeline's
        # patch_endpoints_state contract)
        self._log_delta(
            "rules", ("add", subject_sids(rules, self._state.table))
        )
        return True

    # policyd: refresh-path
    def _scatter_sel_window(
        self, sm: np.ndarray, touched: np.ndarray, cols: np.ndarray
    ):
        """Upload the changed sel_match word window: row-sparse scatter
        when few identities matched, dense column window otherwise."""
        device = self._device
        assert device is not None
        if touched.size == 0:
            # no identity matches the new selectors — their device bits
            # were zero and stay zero
            return device.sel_match
        if touched.size <= max(8, sm.shape[0] // 4):
            rows = _pow2_rows(touched.astype(np.int32))
            return _set_rows_cols(
                device.sel_match,
                jnp.asarray(rows),
                jnp.asarray(cols),
                jnp.asarray(sm[np.ix_(rows, cols)]),
            )
        return _set_col_window(
            device.sel_match,
            jnp.int32(cols[0]),
            jnp.asarray(np.ascontiguousarray(sm[:, cols])),
        )

    # policyd: refresh-path
    def _apply_rule_delete(self, rules, revision: int) -> bool:
        """Retract a deleted rule batch in place (the incremental
        counterpart of repository.go DeleteByLabels:286): refcounted
        matrix cells drop to zero and are scattered to the device as
        value-0 writes — no recompile, no re-upload. False → full
        rebuild needed (a rule this compile never attributed)."""
        c = self._compiled
        state = self._state
        assert c is not None and state is not None
        ing, eg = state.ingress, state.egress
        keys = [id(r) for r in rules]
        # check attribution FIRST: a partial removal (ingress done,
        # egress unknown) would leave the two directions inconsistent
        if any(k not in ing.rule_cells or k not in eg.rule_cells for k in keys):
            return False
        for k in keys:
            ing.remove_rule(k)
            eg.remove_rule(k)
        ing.refresh_entry_views()
        eg.refresh_entry_views()
        device = self._device
        assert device is not None
        self._device = DevicePolicy(
            id_bits=device.id_bits,
            sel_match=device.sel_match,
            ingress=self._patch_tables(device.ingress, ing.take_writes()),
            egress=self._patch_tables(device.egress, eg.take_writes()),
        )
        c.revision = revision
        # deletes only retract cells under the removed rules' subject
        # selectors (refcounted 0-writes) — same column-bounding
        # contract as appends; the selectors stay interned, so this
        # lookup never grows the table
        self._log_delta("rules", ("del", subject_sids(rules, state.table)))
        return True

    def _kick_background_refresh(self) -> None:
        """Start (at most one) background full refresh (lock held)."""
        if self._bg_refresh is not None and self._bg_refresh.is_alive():
            return
        gen = self._install_gen  # what the bg result would replace

        def run():
            try:
                result = self._compute_full(
                    self.repo, self.registry, self._placement
                )
                with self._lock:
                    if self._install_gen != gen:
                        # someone installed a NEWER compile while this
                        # one ran (e.g. refresh(force=True)) — dropping
                        # ours is the only safe move: installing would
                        # roll enforcement back to an older rule set
                        return
                    self._install_compiled(*result)
            except Exception as e:
                # a failed background compile leaves the restored
                # tables serving; the next refresh() retries. Only
                # environmental failures are absorbed — a programmer
                # error (classified KIND_ERROR) re-raises and kills
                # this thread loudly via threading.excepthook instead
                # of hiding a TypeError behind a warning forever
                from . import faults as _faults

                if _faults.classify(e) == _faults.KIND_ERROR:
                    raise
                from .utils.logging import get_logger

                get_logger("engine").warning(
                    "background refresh failed",
                    fields={"err": f"{type(e).__name__}: {e}"},
                )

        t = threading.Thread(target=run, daemon=True)
        self._bg_refresh = t
        t.start()

    def wait_refreshed(self, timeout: Optional[float] = None) -> bool:
        """Block until a pending background refresh (if any) lands —
        tests and shutdown paths use this; serving paths never do."""
        t = self._bg_refresh
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    # -- compiled-state snapshots (pinned-map persistence analog) -------
    def save_snapshot(self, path: str, mats=None) -> None:
        """Persist the compiled arrays (+ optional materialized
        policymaps, {direction: MaterializedState}) so a restart can
        re-load instead of re-deriving (daemon/state.go:53,135 role —
        the kernel's pinned maps keep serving across agent restarts).

        Array COPIES are taken under the engine lock (the incremental
        paths mutate them in place); the serialize + fsync — the slow
        part at scale — runs outside it so verdict serving never stalls
        behind a disk write."""
        import copy as _copy
        import dataclasses as _dc

        from .compiler.snapshot import save_compiled_state

        with self._lock:
            if self._compiled is None or self._sel_match_host is None:
                raise RuntimeError("nothing compiled to snapshot")
            c = self._compiled

            def copy_arrays(obj):
                return _dc.replace(obj, **{
                    f.name: getattr(obj, f.name).copy()
                    for f in _dc.fields(obj)
                    if isinstance(getattr(obj, f.name), np.ndarray)
                })

            compiled = copy_arrays(c)
            compiled.id_to_row = dict(c.id_to_row)
            compiled.ingress = copy_arrays(c.ingress)
            compiled.egress = copy_arrays(c.egress)
            sel_match = self._sel_match_host.copy()
            mats_copy = None
            if mats:
                mats_copy = {
                    d: _dc.replace(
                        st,
                        allow_nc=st.allow_nc.copy(),
                        red_nc=st.red_nc.copy(),
                        ep_rows=st.ep_rows.copy(),
                        ep_slots=_copy.deepcopy(st.ep_slots),
                        endpoint_identity_ids=list(
                            st.endpoint_identity_ids
                        ),
                    )
                    for d, st in mats.items()
                }
        save_compiled_state(path, compiled, sel_match, mats_copy)

    def restore_snapshot(self, path: str, *, trust_counters: bool = False):
        """Load a snapshot and bring the device tables up on it.
        → {direction: MaterializedState} (empty if none were saved), or
        None when the file is absent/unreadable.

        Mirrors the reference's restore semantics: the LOADED state
        serves immediately (last-known-good continuity); the normal
        ``refresh()`` gate re-derives when the inputs move.

        ``trust_counters`` may ONLY be True when the live repo/registry
        are the very objects the snapshot was taken from (same
        process): then matching revision counters mean matching
        content and refresh() stays a no-op. Across a restart the
        counters come from a DEAD process — a fresh repository restarts
        its numbering, so an equal revision is a coincidence, not
        equality; the default re-stamps them to a sentinel that forces
        the first refresh() to recompile (serving the restored tables
        until it lands)."""
        from .compiler.snapshot import load_compiled_state
        from .ops.materialize import state_from_snapshot

        loaded = load_compiled_state(path)
        if loaded is None:
            return None
        compiled, sel_match_host, mat_fields = loaded
        if not trust_counters:
            compiled.revision = -1
            compiled.identity_version = -1
        with self._lock:
            # restore installs under the lock, as it always uploaded
            self._device = _policy_from_host(  # policyd-lint: disable=LOCK002
                compiled, sel_match_host, self._placement
            )
            self._sel_match_host = sel_match_host
            low = np.full(MAX_USER_IDENTITY + 1, -1, np.int32)
            high: dict = {}
            for ident, row in compiled.id_to_row.items():
                if ident < low.size:
                    low[ident] = row
                else:
                    high[ident] = row
            self._low_rows = low
            self._high_rows = high
            self._compiled = compiled
            self._state = None  # no incremental state: rule ops rebuild
            self._conj_unpacked = None
            self._pending_idents.clear()
            self._log_delta("full", ())
        return {
            d: state_from_snapshot(compiled.row_ids, f)
            for d, f in mat_fields.items()
        }

    def _set_row_index(self, ident_id: int, row: int) -> None:
        assert self._low_rows is not None
        if ident_id < self._low_rows.size:
            self._low_rows[ident_id] = row
        elif row < 0:
            self._high_rows.pop(ident_id, None)
        else:
            self._high_rows[ident_id] = row

    # ------------------------------------------------------------------
    @property
    def device_policy(self) -> DevicePolicy:
        self.refresh()
        assert self._device is not None
        return self._device

    def snapshot(self) -> Tuple[CompiledPolicy, DevicePolicy]:
        """A consistent (compiled, device) pair from one refresh —
        callers must never mix row/selector layouts across refreshes."""
        self.refresh()
        with self._lock:
            assert self._compiled is not None and self._device is not None
            return self._compiled, self._device

    def sel_match_rows(
        self,
        rows: Sequence[int],
        words: Optional[Sequence[int]] = None,
    ) -> Optional[np.ndarray]:
        """Bounded FINAL-STATE copy of the host sel_match mirror: the
        requested identity rows (× the requested packed words, all words
        when None) as a fresh array — the delta-replay source for the
        pipeline's placed-copy patching (ops/materialize
        patch_selector_rows / patch_selector_cols). Final-state reads
        make replay idempotent regardless of event ordering. None when
        the engine has no compile yet or an index is out of the mirror's
        bounds (layout moved — caller must full re-place)."""
        ridx = np.asarray(rows, np.int64)
        widx = None if words is None else np.asarray(words, np.int64)
        with self._lock:
            sm = self._sel_match_host
            if sm is None:
                return None
            if ridx.size and (ridx.min() < 0 or ridx.max() >= sm.shape[0]):
                return None
            if widx is not None and widx.size and (
                widx.min() < 0 or widx.max() >= sm.shape[1]
            ):
                return None
            if widx is None:
                return sm[ridx].copy()
            return sm[np.ix_(ridx, widx)].copy()

    def _rows_snapshot(
        self, low: np.ndarray, high: dict, identity_ids: Sequence[int]
    ) -> np.ndarray:
        ids = np.asarray(identity_ids, dtype=np.int64)
        rows = np.empty(ids.shape, np.int32)
        in_low = ids < low.size
        if (ids < 0).any():
            raise KeyError("negative identity in batch")
        rows[in_low] = low[ids[in_low]]
        for i in np.nonzero(~in_low)[0]:
            rows[i] = high.get(int(ids[i]), -1)
        if (rows < 0).any():
            raise KeyError("unknown identity in batch")
        return rows

    def rows(self, identity_ids: Sequence[int]) -> np.ndarray:
        self.refresh()
        assert self._low_rows is not None
        return self._rows_snapshot(self._low_rows, self._high_rows, identity_ids)

    def rows_or_negative(self, identity_ids: np.ndarray) -> np.ndarray:
        """[B] device rows with -1 for unknown/invalid identities — the
        tolerant variant for datapath inputs that CARRY an identity
        (overlay tunnel keys) where an unknown value must fall back,
        not raise."""
        self.refresh()
        with self._lock:
            low = self._low_rows
            high = dict(self._high_rows)
        assert low is not None
        ids = np.asarray(identity_ids, np.int64)
        rows = np.full(ids.shape, -1, np.int32)
        ok = (ids > 0) & (ids < low.size)
        rows[ok] = low[ids[ok]]
        hi = ids >= low.size
        if hi.any():
            # per-UNIQUE-id dict lookups, vectorized scatter: overlay
            # tunnel keys commonly carry high-range (local/CIDR)
            # identities and batches run to millions of flows
            uniq, inv = np.unique(ids[hi], return_inverse=True)
            vals = np.fromiter(
                (high.get(int(u), -1) for u in uniq), np.int32, len(uniq)
            )
            rows[hi] = vals[inv]
        return rows

    # -- verdict attribution (policyd-flows) ---------------------------
    def attribution(
        self, ingress: bool = True, expect_revision: Optional[int] = None
    ):
        """(AttribTables, n_rules) for the attribution kernel variant,
        or None when unavailable — a snapshot-restored engine carries no
        CompileState (no per-rule cell attribution) until its first full
        recompile lands. Cached per (install_gen, revision): identity
        churn keeps the cache, any rule movement (append, delete, full
        rebuild) rebuilds it from the packers' rule_cells refcounts.

        ``expect_revision`` lets a caller that already holds a
        (compiled, device) snapshot demand tables consistent with it: a
        rule mutation racing the two reads returns None (the caller's
        next rebuild re-materializes with matching tables) instead of
        shape-mismatched origin arrays."""
        self.refresh()
        with self._lock:
            state, c = self._state, self._compiled
            if state is None or c is None:
                return None
            if expect_revision is not None and c.revision != expect_revision:
                return None
            key = (self._install_gen, c.revision)
            cache = self._attrib_cache
            if cache is None or cache[0] != key:
                with self.repo._lock:
                    rules = list(self.repo.rules)
                keys = [id(r) for r in rules]
                tabs = {}
                for ing, packer in (
                    (True, state.ingress),
                    (False, state.egress),
                ):
                    d, a, k = rule_origin_arrays(packer, keys)
                    tabs[ing] = AttribTables(
                        # bounded static unroll (exactly 2 directions),
                        # control-plane cache build — not per-flow
                        deny_rule=jnp.asarray(d),  # policyd-lint: disable=TPU002
                        allow_rule=jnp.asarray(a),  # policyd-lint: disable=TPU002
                        combo_rule=jnp.asarray(k),  # policyd-lint: disable=TPU002
                    )
                cache = self._attrib_cache = (key, tabs, len(rules))
            return cache[1][ingress], cache[2]

    # ------------------------------------------------------------------
    def verdicts(
        self,
        subj_ids: Sequence[int],
        peer_ids: Sequence[int],
        dports: Sequence[int],
        protos: Sequence[int],
        *,
        ingress: bool = True,
        has_l4: Optional[Sequence[bool]] = None,
        attrib: bool = False,
    ):
        """Batched verdicts by identity number. ``subj`` is the endpoint
        whose policy applies (dst for ingress, src for egress). With
        ``attrib=True`` → (Verdict, Attribution, hits[R]); raises
        RuntimeError when rule-origin tables are unavailable
        (snapshot-restored engine before its first recompile)."""
        origin = n_rules = None
        if attrib:
            at = self.attribution(ingress)
            if at is None:
                raise RuntimeError(
                    "verdict attribution unavailable: engine has no "
                    "compile state (snapshot-restored?)"
                )
            origin, n_rules = at
        # Snapshot device + row tables under one lock acquisition so a
        # concurrent repo/registry mutation can't mix row indices from a
        # newer compilation into older device tables.
        self.refresh()
        with self._lock:
            device = self._device
            low = self._low_rows.copy() if self._low_rows is not None else None
            high = dict(self._high_rows)
        assert device is not None and low is not None
        _metrics.verdict_batches.inc({"path": "engine"})
        n = len(subj_ids)
        hl4 = np.ones(n, dtype=bool) if has_l4 is None else np.asarray(has_l4, bool)
        args = (
            device,
            jnp.asarray(self._rows_snapshot(low, high, subj_ids)),
            jnp.asarray(self._rows_snapshot(low, high, peer_ids)),
            jnp.asarray(np.asarray(dports, np.int32)),
            jnp.asarray(np.asarray(protos, np.int32)),
            jnp.asarray(hl4),
        )
        if not attrib:
            return verdict_batch(*args, ingress=ingress)
        return verdict_batch(
            *args, ingress=ingress, attrib=True, origin=origin, n_rules=n_rules
        )

    def explain_one(
        self,
        subj_id: int,
        peer_id: int,
        dport: int = 0,
        proto: int = PROTO_TCP,
        *,
        ingress: bool = True,
        l4: bool = True,
    ) -> dict:
        """Replay ONE flow through the verdict kernel with attribution
        on and name the deciding rule — the `cilium policy trace`-style
        explain backend."""
        verdict, at, _hits = self.verdicts(
            [subj_id], [peer_id], [dport], [proto],
            ingress=ingress, has_l4=[l4], attrib=True,
        )
        rule_idx = int(at.rule[0])
        reason = int(at.reason[0])
        origins = self.repo.rule_origins()
        return {
            "decision": int(verdict.decision[0]),
            "allowed": int(verdict.decision[0]) == ALLOW,
            "l3": int(verdict.l3[0]),
            "l7_redirect": bool(verdict.l7_redirect[0]),
            "reason_code": reason,
            "reason": ATTR_NAMES.get(reason, str(reason)),
            "rule_index": rule_idx,
            "rule": origins[rule_idx] if 0 <= rule_idx < len(origins) else None,
        }

    def verdict_one(
        self,
        subj_id: int,
        peer_id: int,
        dport: int = 0,
        proto: int = PROTO_TCP,
        *,
        ingress: bool = True,
        l4: bool = True,
    ) -> Tuple[int, int]:
        """Single query → (decision, l3_decision); the `cilium policy
        trace` fast path."""
        v = self.verdicts(
            [subj_id], [peer_id], [dport], [proto], ingress=ingress, has_l4=[l4]
        )
        return int(v.decision[0]), int(v.l3[0])
