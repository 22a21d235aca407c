"""Canonical tables for the package's STABLE APIs (policyd-contracts).

One importable, pure-stdlib module holding every name/number the
ROADMAP's standing contracts freeze: trace phase names, wire drop
reasons, attribution codes, the bucket ladder, the bench --diff
direction vocabulary, and the option↔DaemonConfig boot-field map.

Two kinds of consumers:

- runtime code imports what it can single-source directly (the
  pipeline's ``BUCKET_LADDER`` and bench's ``--diff`` suffix tuples
  live HERE and only here);
- ``cilium_tpu.analysis.contracts`` (rules API001 / BENCH001 / OPT001)
  machine-checks every *other* literal in the package against these
  tables at lint time, so wire constants that must stay put in their
  defining modules (monitor/events.py, ops/verdict.py) cannot drift
  silently.

Nothing here may import jax, numpy, or anything else from the
package: the analyzers load this in CI contexts with no device and
no heavyweight deps.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

# -- trace phases (observe/tracer.py) ---------------------------------
# Phase names are a stable waterfall vocabulary: TRACES_PR*.md files
# and bench --diff's phase comparison key on them across commits, so
# renaming one is a breaking change (observe/README.md). API001 checks
# every ``bt.phase("...")`` literal against this set.
TRACE_PHASES: Tuple[str, ...] = (
    "rebuild",
    "prepare",
    "lb_translate",
    "ct_prepass",
    "dispatch",
    "host_sync",
    "ct_create",
    "counters",
    "emit_events",
    # proxy-http traces (proxy/proxy.py check_http, l7/http_policy.py)
    "encode",
    "overlong",
    "rule_match",
    "access_log",
)

# -- drop reasons (monitor/events.py REASON_*) ------------------------
# u8 wire codes carried in the flow-event codec's "sub" field. STABLE:
# renumbering breaks stored flow logs and monitor consumers. API001
# checks every int-valued ``REASON_*`` assignment in the package
# against this map (string-valued REASON_* constants — e.g. the
# admission controller's shed-cause labels — are a different namespace
# and exempt).
WIRE_REASONS: Dict[str, int] = {
    "REASON_UNKNOWN": 0,
    "REASON_POLICY": 133,
    "REASON_CT_MAP_FULL": 135,
    "REASON_PREFILTER": 144,
    "REASON_NO_SERVICE": 146,
    "REASON_POLICY_DENY": 151,
    "REASON_POLICY_NO_L3": 152,
    "REASON_POLICY_NO_L4": 153,
    "REASON_PROXY_REDIRECT": 154,
    "REASON_PIPELINE_DEGRADED": 155,
}

# -- attribution codes (ops/verdict.py ATTR_*) ------------------------
# The device kernel's per-flow match-kind output (policyd-flows).
# ATTR_* → REASON_* is the 1→151 / 2→152 / 3→153 / 4→154 mapping the
# event path applies; both ends are frozen here.
ATTR_CODES: Dict[str, int] = {
    "ATTR_ALLOW": 0,
    "ATTR_DENY_RULE": 1,
    "ATTR_NO_L3": 2,
    "ATTR_NO_L4": 3,
    "ATTR_L7": 4,
}

# code → canonical display name (ops/verdict.py ATTR_NAMES must match)
ATTR_CODE_NAMES: Dict[int, str] = {
    0: "allowed",
    1: "deny-rule",
    2: "no-l3-match",
    3: "no-l4-match",
    4: "l7-redirect",
}

# -- dispatch bucket ladder (datapath/pipeline.py) --------------------
# The fixed padded-shape set for chunked CT-miss dispatch. A rung
# joins the jit cache per static-arg combination, so the ladder is a
# compile-count contract: bench compile_s and the ≤ ladder×directions
# program-count assertion both depend on it (policyd-autotune).
BUCKET_LADDER: Tuple[int, ...] = (1024, 2048, 4096, 8192)

# Effective pipeline depth domain: the DispatchAutoTune controller
# moves in [MIN, DaemonConfig.verdict_pipeline_max_depth], and the
# config validator caps the static depth at MAX.
PIPELINE_DEPTH_MIN = 1
PIPELINE_DEPTH_MAX = 64

# -- bench --diff direction vocabulary (bench.py) ---------------------
# A metric key's unit suffix decides which direction is a regression.
# Keys matching neither tuple are NOT compared — BENCH001 flags
# computed measurements that would silently fall out of regression
# coverage, and flags rate-shaped names (``*_per_s``, ``*_ops_s``)
# whose ``_s`` suffix would be mis-read as a duration.
DIFF_HIGHER_SUFFIXES: Tuple[str, ...] = (
    "_vps", "_rps", "_lps", "_qps", "_ratio",
)
DIFF_LOWER_SUFFIXES: Tuple[str, ...] = ("_ms", "_us", "_ns", "_s", "_pct")

# Environment/bookkeeping keys --diff must never fail a round on
# (calib_*-prefixed keys are skipped separately: they ARE the
# normalizers).
DIFF_SKIP_KEYS: Tuple[str, ...] = (
    "value", "vs_baseline", "build_s", "compile_s",
    "host_cpus", "sample_every",
)

# Keys BENCH001 additionally accepts without a direction suffix:
# scenario descriptors and diff-internal fields, not measurements a
# regression gate should compare across rounds.
BENCH_BOOKKEEPING_KEYS: Tuple[str, ...] = DIFF_SKIP_KEYS + (
    # traffic-mix descriptors: they parameterize the scenario (a
    # changed mix invalidates the round, it isn't a regression)
    "allow_fraction", "deny_fraction", "shed_fraction",
    # --diff's own verdict-entry fields
    "prev", "cur", "threshold_pct",
)

# -- metric label cardinality (observe/, analysis OBS002) -------------
# Label keys whose values are allowed to be INTERPOLATED at a metric
# call site in a hot module (f-string/str()/format of runtime data):
# each one is bounded by construction, so it cannot explode series
# cardinality. Everything else interpolated into a label value in a
# hot module is an OBS002 finding — identity ids, endpoint ids and
# addresses are the classic unbounded offenders.
METRIC_BOUNDED_LABEL_KEYS: Tuple[str, ...] = (
    # bounded by the mesh device complement (VerdictSharding per-device
    # verdict series; at most len(jax.devices()) values)
    "device",
    # bounded by the shape-bucket ladder (BUCKET_LADDER rungs)
    "bucket",
    # bounded by the SLO window vocabulary (observe/timeseries.WINDOWS)
    "window",
    # bounded by the IP family domain ("v4"/"v6" — pipeline dispatch
    # pad-lane accounting)
    "family",
    # bounded by the reason-144 producer taxonomy: the host admission
    # gate and the device prefilter kernel are the ONLY two emitters of
    # REASON_PREFILTER drops (observe/README.md "two producers" note)
    "producer",
)

# -- lifecycle journal event kinds (observe/journal.py) ---------------
# The structured lifecycle-event vocabulary (policyd-journal). STABLE:
# fleet timelines are merged across nodes running different commits,
# bugtool events.json archives are diffed offline, and bench --chaos
# asserts against specific kinds — renaming one breaks all three.
# OBS003 checks every ``emit(kind="...")`` literal in the package
# against this table (and flags stale rows no emitter references).
JOURNAL_KINDS: Tuple[str, ...] = (
    # daemon boot completed (attrs: pipeline_mode, policy_epoch)
    "boot",
    # CT snapshot restore verdict (attrs: kept/expired/flushed counts,
    # basis_match, snapshot_age_s)
    "ct_restore",
    # first verdict batch completed after a restart — closes the
    # boot-anchored downtime window (attrs: downtime_ms)
    "restore_done",
    # compiled-policy or CT snapshot written to disk (attrs: what,
    # basis / ct_epoch)
    "snapshot_save",
    # materialization rebuild committed a new served basis (attrs:
    # prev/new _mat_basis, policy_epoch)
    "rebuild",
    # shadow-built table generation installed (attrs: policy_epoch,
    # basis)
    "epoch_swap",
    # degradation-ladder transition (attrs: from/to mode names)
    "ladder_move",
    # device quarantined (attrs: device, ct_epoch, CT rescue outcome)
    "quarantine",
    # edge-triggered admission shed episode opened (attrs: reason)
    "shed_start",
    # shed episode closed (attrs: per-reason shed deltas, duration_s)
    "shed_end",
    # graceful drain entered (attrs: pipeline_mode, policy_epoch)
    "drain_begin",
    # drain finished (attrs: drain_s, verdicts_lost, flushed counts)
    "drain_end",
    # watchdog declared a verdict-path stall (attrs: site, age_ms)
    "watchdog_stall",
    # federation heartbeat found master keys lost to lease expiry and
    # re-asserted them (attrs: repaired count)
    "lease_lost",
    # federation GC reaped orphaned master identities (attrs: reaped
    # ids)
    "identity_reap",
)

# Journal severity domain: bounds the journal_events_total{severity}
# label and the GET /events?severity= filter.
JOURNAL_SEVERITIES: Tuple[str, ...] = ("info", "warning", "error")

# -- runtime options ↔ DaemonConfig boot fields (option.py) -----------
# OPT001: every option registered in OPTION_SPECS needs an entry here.
# The value is the DaemonConfig field that seeds the option at boot,
# or None for options that are structurally boot-only / runtime-only —
# each None carries its reason right here, where the exception is
# reviewed with the table.
OPTION_BOOT_FIELDS: Dict[str, Optional[str]] = {
    # None: wired from the Daemon ctor's ``conntrack`` argument (the
    # CT table object itself), not a bare flag a config field can hold
    "Conntrack": None,
    # None: log-level toggle, boots from the logging config
    "Debug": None,
    # None: boots unconditionally True (reference parity: DropNotify
    # defaults on); runtime-mutable for operators who want quiet
    "DropNotification": None,
    # None: boots off by definition — traces are an opt-in firehose
    "TraceNotification": None,
    # None: enforcement surface parity with the reference endpoint
    # option set; boots True, immutable (not in _MUTABLE_OPTIONS)
    "Policy": None,
    "PolicyVerdictNotification": "policy_verdict_notification",
    "PhaseTracing": "phase_tracing",
    "VerdictSharding": "verdict_sharding",
    "MeshSharding2D": "mesh_sharding_2d",
    "FlowAttribution": "flow_attribution",
    "DispatchAutoTune": "dispatch_autotune",
    "FailOpen": "fail_open",
    "EpochSwap": "policy_epoch_swap",
    "L7DeviceBatch": "l7_device_batch",
    "FaultInjection": "fault_injection",
    "AdmissionControl": "admission_control",
    "DeviceProfiling": "device_profiling",
    "FleetTelemetry": "fleet_telemetry",
    # None: requires an attached federation membership object (kvstore
    # join happens after boot), so there is nothing to enable at
    # DaemonConfig time
    "ClusterFederation": None,
    "Prefilter": "prefilter_shed",
    "SparseDeltas": "sparse_deltas",
    "PolicySubjectIndex": "policy_subject_index",
    "LifecycleJournal": "lifecycle_journal",
}
