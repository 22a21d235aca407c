"""Metrics registry with Prometheus text exposition.

Reference: pkg/metrics/metrics.go:37,87-180 — a process-wide registry
of counters/gauges/histograms covering endpoint regeneration, policy
revision/import counts, datapath errors, and event counts, served over
HTTP and bridged into the REST API. No external client library — the
text exposition format is trivial to emit.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

_LabelKey = Tuple[Tuple[str, str], ...]


def _labels_key(labels: Optional[Dict[str, str]]) -> _LabelKey:
    return tuple(sorted((labels or {}).items()))


def _fmt_labels(key: _LabelKey) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in key)
    return "{" + inner + "}"


class Counter:
    # Exposition TYPE word. Subclasses override this instead of
    # duplicating expose(): the HELP/TYPE header emission lives in
    # exactly one place, so the two can never drift apart.
    _TYPE = "counter"

    def __init__(self, name: str, help_: str) -> None:
        self.name, self.help = name, help_
        self._values: Dict[_LabelKey, float] = {}
        self._lock = threading.Lock()

    def inc(self, labels: Optional[Dict[str, str]] = None, value: float = 1.0) -> None:
        k = _labels_key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + value

    def get(self, labels: Optional[Dict[str, str]] = None) -> float:
        return self._values.get(_labels_key(labels), 0.0)

    def series(self) -> Dict[_LabelKey, float]:
        """Point-in-time snapshot of every label series (for /profile
        readers that want values, not exposition text)."""
        with self._lock:
            return dict(self._values)

    def expose(self) -> List[str]:
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} {self._TYPE}",
        ]
        # series() snapshots under the lock: a concurrent inc() on a
        # fresh label set would otherwise mutate the dict mid-iteration
        for k, v in sorted(self.series().items()):
            out.append(f"{self.name}{_fmt_labels(k)} {v}")
        return out


class Gauge(Counter):
    _TYPE = "gauge"

    def set(self, value: float, labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._values[_labels_key(labels)] = value


class SlotCounter(Counter):
    """A counter over the fixed values of one label, each a cell of the
    plain list ``slots``, which its one writer adds into with no lock.

    For a writer that may take none: a ``gc.callbacks`` hook runs at
    whatever allocation starts a collection, and that can be inside
    another counter's locked ``series()`` copy on the same thread,
    where taking that lock again would deadlock the thread."""

    def __init__(self, name: str, help_: str, label: str, values: Sequence[str]) -> None:
        super().__init__(name, help_)
        self._keys: Tuple[_LabelKey, ...] = tuple(((label, v),) for v in values)
        self.slots: List[float] = [0.0] * len(self._keys)

    def inc(self, labels: Optional[Dict[str, str]] = None, value: float = 1.0) -> None:
        self.slots[self._keys.index(_labels_key(labels))] += value

    def get(self, labels: Optional[Dict[str, str]] = None) -> float:
        return self.series().get(_labels_key(labels), 0.0)

    def series(self) -> Dict[_LabelKey, float]:
        return dict(zip(self._keys, self.slots))


class _HistSeries:
    """One (label-set) series of a histogram: per-bucket counts + sum/n."""

    __slots__ = ("counts", "sum", "n")

    def __init__(self, n_buckets: int) -> None:
        self.counts = [0] * (n_buckets + 1)
        self.sum = 0.0
        self.n = 0


class Histogram:
    DEFAULT_BUCKETS = (0.001, 0.01, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0)

    def __init__(self, name: str, help_: str, buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name, self.help = name, help_
        self.buckets = tuple(buckets)
        # label-set → series; the unlabeled series exists from the
        # start so an unobserved histogram still exposes its zeros
        self._series: Dict[_LabelKey, _HistSeries] = {
            (): _HistSeries(len(self.buckets))
        }
        self._lock = threading.Lock()

    def observe(self, value: float, labels: Optional[Dict[str, str]] = None) -> None:
        k = _labels_key(labels)
        with self._lock:
            s = self._series.get(k)
            if s is None:
                s = self._series[k] = _HistSeries(len(self.buckets))
            s.sum += value
            s.n += 1
            for i, b in enumerate(self.buckets):
                if value <= b:
                    s.counts[i] += 1
                    return
            s.counts[-1] += 1

    def get_count(self, labels: Optional[Dict[str, str]] = None) -> int:
        s = self._series.get(_labels_key(labels))
        return 0 if s is None else s.n

    def series_labels(self) -> List[Dict[str, str]]:
        """Label sets with at least one series (incl. the unlabeled
        {}) — lets /traces and /profile walk per-phase quantiles
        without reaching into the series dict."""
        with self._lock:
            keys = list(self._series.keys())
        return [dict(k) for k in keys]

    def quantile(
        self, q: float, labels: Optional[Dict[str, str]] = None
    ) -> Optional[float]:
        """Estimate the q-quantile (0 < q <= 1) of one label series by
        linear interpolation within the landing bucket — the standard
        Prometheus histogram_quantile() estimate. Returns None for an
        unobserved series. Values past the last finite bucket clamp to
        that bucket bound (+Inf has no upper edge to interpolate to)."""
        if not 0.0 < q <= 1.0:
            raise ValueError("quantile must be in (0, 1]")
        with self._lock:
            s = self._series.get(_labels_key(labels))
            if s is None or s.n == 0:
                return None
            counts = list(s.counts)
            n = s.n
        rank = q * n
        cum = 0
        for i, b in enumerate(self.buckets):
            prev_cum = cum
            cum += counts[i]
            if cum >= rank:
                lo = self.buckets[i - 1] if i > 0 else 0.0
                if counts[i] == 0:
                    return b
                return lo + (b - lo) * (rank - prev_cum) / counts[i]
        return self.buckets[-1]

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._lock:
            series = sorted(self._series.items())
        for key, s in series:
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += s.counts[i]
                out.append(
                    f"{self.name}_bucket"
                    f"{_fmt_labels(key + (('le', str(b)),))} {cum}"
                )
            cum += s.counts[-1]
            out.append(
                f"{self.name}_bucket{_fmt_labels(key + (('le', '+Inf'),))} {cum}"
            )
            out.append(f"{self.name}_sum{_fmt_labels(key)} {s.sum}")
            out.append(f"{self.name}_count{_fmt_labels(key)} {s.n}")
        return out


class Registry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}

    def counter(self, name: str, help_: str = "", slots=None) -> Counter:
        """``slots=(label, values)`` gives a lock-free SlotCounter."""
        if slots is not None:
            return self._get(name, lambda: SlotCounter(name, help_, *slots))
        return self._get(name, lambda: Counter(name, help_))

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(name, lambda: Gauge(name, help_))

    def histogram(self, name: str, help_: str = "", buckets=Histogram.DEFAULT_BUCKETS) -> Histogram:
        return self._get(name, lambda: Histogram(name, help_, buckets))

    def _get(self, name, ctor):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = ctor()
                self._metrics[name] = m
            return m

    def expose(self) -> str:
        lines: List[str] = []
        with self._lock:
            for m in self._metrics.values():
                lines.extend(m.expose())  # type: ignore[attr-defined]
        return "\n".join(lines) + "\n"


# Process-wide registry + the metric families of pkg/metrics/metrics.go.
registry = Registry()

endpoint_regeneration_count = registry.counter(
    "cilium_tpu_endpoint_regenerations_total", "Count of endpoint regenerations"
)
endpoint_regeneration_time = registry.histogram(
    "cilium_tpu_endpoint_regeneration_seconds", "Endpoint regeneration latency"
)
policy_count = registry.gauge("cilium_tpu_policy_count", "Rules in the repository")
policy_revision = registry.gauge("cilium_tpu_policy_max_revision", "Policy revision")
policy_import_errors = registry.counter(
    "cilium_tpu_policy_import_errors_total", "Failed policy imports"
)
verdict_batches = registry.counter(
    "cilium_tpu_datapath_batches_total", "Flow batches processed"
)
verdicts_total = registry.counter(
    "cilium_tpu_datapath_verdicts_total",
    "Flow verdicts by outcome (batches dispatched under VerdictSharding "
    "report per-device series via an extra device label instead of the "
    "plain outcome series — sum across labels for the total)",
)
identity_count = registry.gauge("cilium_tpu_identity_count", "Allocated identities")
l7_fallback_patterns = registry.counter(
    "cilium_tpu_l7_fallback_patterns_total",
    "L7 regex patterns demoted from the device DFA to host re",
)
l7_host_fallback_evaluations = registry.counter(
    "cilium_tpu_l7_host_fallback_evaluations_total",
    "Request-field evaluations that ran on host re instead of the DFA",
)
compile_time = registry.histogram(
    "cilium_tpu_policy_compile_seconds", "Policy tensor compile latency"
)

# -- policyd-trace (observe/) families -----------------------------------
# Verdict-path phases run µs–ms, far below DEFAULT_BUCKETS' 1ms floor;
# the top buckets still catch first-compile outliers.
PHASE_BUCKETS = (
    20e-6, 50e-6, 100e-6, 250e-6, 500e-6, 1e-3, 2.5e-3, 5e-3, 10e-3,
    25e-3, 50e-3, 100e-3, 250e-3, 1.0,
)
pipeline_phase_seconds = registry.histogram(
    "cilium_tpu_pipeline_phase_seconds",
    "Verdict-path phase latency (label: phase — a stable name set, "
    "see cilium_tpu/observe/README.md)",
    buckets=PHASE_BUCKETS,
)
batch_total_seconds = registry.histogram(
    "cilium_tpu_pipeline_batch_seconds",
    "End-to-end wall time of one traced verdict batch",
    buckets=PHASE_BUCKETS,
)
engine_refresh_seconds = registry.histogram(
    "cilium_tpu_engine_refresh_seconds",
    "Policy engine refresh latency (label kind: full|incremental|delta — "
    "delta is the pipeline's O(delta) materialization patch)",
    buckets=PHASE_BUCKETS,
)
engine_refreshes_total = registry.counter(
    "cilium_tpu_engine_refreshes_total",
    "Policy engine refreshes by kind (full recompile vs incremental patch)",
)

# -- policyd-delta (O(delta) refresh) families -----------------------------
engine_delta_rows_total = registry.counter(
    "cilium_tpu_engine_delta_rows_total",
    "Identity rows updated through the coalesced delta path (one per "
    "(row, identity, live) event scattered to the device tables)",
)
engine_delta_cols_total = registry.counter(
    "cilium_tpu_engine_delta_cols_total",
    "Identity rows carried by selector column-patch events (policyd-"
    "sparse): a new-selector append touching k identities logs one "
    "\"cols\" delta and scatters O(k·window) words instead of the full "
    "[N, S/32] sel_match matrix",
)
lpm_trie_patches_total = registry.counter(
    "cilium_tpu_lpm_trie_patches_total",
    "ipcache prefix upserts/deletes applied to the device LPM tries as "
    "O(delta) node patches (policyd-sparse; label family: 4|6) instead "
    "of whole-trie rebuilds",
)
engine_epoch_swaps_total = registry.counter(
    "cilium_tpu_engine_epoch_swaps_total",
    "Shadow-built device-table generations atomically swapped in at a "
    "batch boundary (full rebuilds that did NOT stop the verdict world)",
)
jit_shape_buckets_total = registry.counter(
    "cilium_tpu_jit_shape_buckets_total",
    "Shape-bucket cache outcomes (result=miss ≈ an XLA recompile)",
)
device_transfers_total = registry.counter(
    "cilium_tpu_device_transfers_total",
    "Host↔device array transfers on traced dispatches (label: direction): "
    "one h2d per packed verdict-chunk upload, counted once per mesh "
    "device under VerdictSharding (each device receives its lane slice), "
    "and one d2h per chunk result pull (replicated, so one device is read); "
    "table patches count one h2d each",
)
pipeline_inflight_depth = registry.gauge(
    "cilium_tpu_pipeline_inflight_depth",
    "Verdict batches enqueued on device but not yet pulled to host "
    "(bounded by VerdictPipelineDepth)",
)

# -- policyd-autotune (adaptive dispatch) families -------------------------
dispatch_pad_lanes_total = registry.counter(
    "cilium_tpu_dispatch_pad_lanes_total",
    "Device lanes dispatched as shape-bucket padding, not live flows "
    "(label: family — divide by live+pad for the pad-waste fraction; "
    "counted on every dispatch path, bucketed or not)",
)
pipeline_depth_current = registry.gauge(
    "cilium_tpu_pipeline_depth_current",
    "Effective verdict pipeline depth right now (moves between 1 and "
    "VerdictPipelineMaxDepth while DispatchAutoTune is on; otherwise "
    "the static configured depth)",
)
autotune_adjustments_total = registry.counter(
    "cilium_tpu_autotune_adjustments_total",
    "Depth steps taken by the dispatch auto-tuner "
    "(label direction: up|down)",
)

# -- policyd-failsafe (fault injection + degradation ladder) families ------
pipeline_faults_total = registry.counter(
    "cilium_tpu_pipeline_faults_total",
    "Classified verdict-path faults (labels: site = the stable "
    "cilium_tpu/faults.py site set, kind = transient|poisoned; counts "
    "injected faults at injection time and real classified errors at "
    "handling time)",
)
degradations_total = registry.counter(
    "cilium_tpu_pipeline_degradations_total",
    "Degradation-ladder transitions (labels from/to: "
    "sharded|single-device|host; re-promotions count too — a recovery "
    "probe is a transition back up)",
)
pipeline_mode = registry.gauge(
    "cilium_tpu_pipeline_mode",
    "Current verdict-path ladder level: 0 = full device complement "
    "(sharded when VerdictSharding is on), 1 = single-device (mesh "
    "re-formed excluding faulted devices), 2 = host/numpy fallback",
)

# -- policyd-mesh (placement + identity sharding) families -----------------
mesh_axis_size = registry.gauge(
    "cilium_tpu_mesh_axis_size",
    "Resolved verdict-mesh axis extents (label axis: flows|ident; 0 = "
    "axis absent — no mesh or no 2D split). flows × ident = devices in "
    "the active placement plan",
)
sharded_table_bytes = registry.gauge(
    "cilium_tpu_sharded_table_bytes",
    "PER-DEVICE bytes of the identity-indexed device tables under the "
    "active placement (label family: policymap|rule_tab; a 2D "
    "flows×ident plan divides the replicated footprint by the ident "
    "axis size, within last-shard padding)",
)

# -- policyd-l7batch (fused L7 classification) families --------------------
l7_batch_seconds = registry.histogram(
    "cilium_tpu_l7_batch_seconds",
    "End-to-end wall time of one L7 classification batch through the "
    "overlapped submit() pipeline (prep → device walk → mask pull)",
    buckets=PHASE_BUCKETS,
)
l7_dfa_tables_interned = registry.gauge(
    "cilium_tpu_l7_dfa_tables_interned",
    "Fused DFA device tables currently interned (shared across every "
    "endpoint whose policy compiles to the same pattern-set key)",
)
l7_dfa_intern_total = registry.counter(
    "cilium_tpu_l7_dfa_intern_total",
    "Fused-table intern outcomes (result=hit: an endpoint reused an "
    "existing device table; miss: a new table was built and "
    "transferred; evict: LRU displacement past the cap)",
)
l7_pad_lanes_total = registry.counter(
    "cilium_tpu_l7_pad_lanes_total",
    "L7 ladder padding (kind=lane: rows dispatched to fill a lane "
    "rung; kind=len_bytes: padded byte-steps under the length rung — "
    "divide by the live counterpart for the pad-waste fraction)",
)
l7_batches_total = registry.counter(
    "cilium_tpu_l7_batches_total",
    "L7 request batches classified through the fused device path "
    "(label parser: http|kafka)",
)
l7_device_transfers_total = registry.counter(
    "cilium_tpu_l7_device_transfers_total",
    "Host-device array transfers of the fused L7 walk's request batches "
    "(direction=h2d: packed-buffer uploads, d2h: mask pulls; label "
    "parser: http|kafka) — one each per lane chunk",
)

# -- policyd-flows (verdict attribution) families -------------------------
rule_hits_total = registry.counter(
    "cilium_tpu_rule_hits_total",
    "Verdicts attributed to a repository rule (labels: origin = the "
    "rule's label set or rule-<index>, direction = ingress|egress; "
    "only incremented while FlowAttribution is on — the [R] hit tensor "
    "is segment-summed on device and pulled at batch completion)",
)
drop_reasons_total = registry.counter(
    "cilium_tpu_drop_reasons_total",
    "Dropped flows by attribution reason (label: reason — the stable "
    "policyd-flows taxonomy in monitor/events.py; generic codes when "
    "FlowAttribution is off)",
)

# -- policyd-overload (admission control + watchdog) families --------------
admission_shed_total = registry.counter(
    "cilium_tpu_admission_shed_total",
    "Flows resolved by the admission gate instead of the full verdict "
    "path (label reason: prefilter = coarse drop-table match, code 144; "
    "deadline = deferred past the batch deadline and resolved via the "
    "fail-closed 155 / FailOpen semantics)",
)
queue_wait_seconds = registry.histogram(
    "cilium_tpu_queue_wait_seconds",
    "Wall time a submitted batch spent gated at admission before "
    "entering the verdict pipeline (only recorded while "
    "AdmissionControl is on; ungated batches observe ~0)",
    buckets=PHASE_BUCKETS,
)
admission_queue_depth = registry.gauge(
    "cilium_tpu_admission_queue_depth",
    "In-flight verdict batches as seen by the admission controller at "
    "its last gate decision (vs its AIMD limit, see GET /healthz)",
)
watchdog_stalls_total = registry.counter(
    "cilium_tpu_watchdog_stalls_total",
    "Stuck operations detected by the dispatch watchdog (label site: "
    "the faults.py site the stalled operation registered under — "
    "dispatch for in-flight batches, attach/compile for registered "
    "external waits, stall for injected sweeps)",
)

# -- policyd-prof (device profiler + memory/transfer ledger) families ------
profile_samples_total = registry.counter(
    "cilium_tpu_profile_samples_total",
    "Dispatches sampled by the device profiler (label site: dispatch|l7; "
    "every profile_sample_every-th batch while DeviceProfiling is on)",
)
profile_phase_seconds = registry.histogram(
    "cilium_tpu_profile_phase_seconds",
    "Sampled dispatch RTT decomposition from the profiler's "
    "block_until_ready sandwiches (label phase: h2d|device_compute|d2h; "
    "only sampled batches observe — scale rates by profile_sample_every)",
    buckets=PHASE_BUCKETS,
)
device_table_bytes = registry.gauge(
    "cilium_tpu_device_table_bytes",
    "PER-DEVICE resident bytes of each policy table family (labels: "
    "family = policymap|rule_tab|sel_match|lpm_trie|dfa, placement = "
    "replicated|ident-sharded; the memory-ledger counterpart of "
    "cilium_tpu_sharded_table_bytes, covering every family)",
)
device_transfer_bytes_total = registry.counter(
    "cilium_tpu_device_transfer_bytes_total",
    "Host↔device bytes moved on traced dispatches (label: direction — "
    "the byte-ledger sibling of the count-only "
    "cilium_tpu_device_transfers_total; logical bytes, not multiplied "
    "by mesh device count, since shard slices sum to the full array)",
)

# -- policyd-fed (cluster federation) families -----------------------------
cluster_nodes = registry.gauge(
    "cilium_tpu_cluster_nodes",
    "Nodes currently publishing in the federated policy plane (the "
    "epoch-exchange view; records are lease-bound, so a dead node "
    "ages out with its kvstore lease)",
)
cluster_identity_allocations_total = registry.counter(
    "cilium_tpu_cluster_identity_allocations_total",
    "Cluster identity-allocator outcomes (label result: new = won the "
    "reserve/confirm CAS, adopted = joined a peer's allocation, "
    "cached = local refcount hit, retry = CAS race or kvstore "
    "partition re-attempt, error = backoff budget exhausted or id "
    "space full)",
)
cluster_epoch_lag = registry.gauge(
    "cilium_tpu_cluster_epoch_lag",
    "Local policy_epoch minus the cluster convergence floor (the min "
    "over every published node); 0 means this node's last full "
    "rebuild is enforced fleet-wide as far as the exchange can prove",
)

# -- policyd-survive (restart/drain continuity) families -------------------
ct_restored_entries_total = registry.counter(
    "cilium_tpu_ct_restored_entries_total",
    "Conntrack entries processed by restore paths (label result: kept = "
    "re-placed live into the table, expired = TTL ran out while the "
    "process was down or the entry lost its probe neighborhood, "
    "flushed = dropped whole because the CT snapshot's policy basis "
    "did not match the restored compiled snapshot)",
)
restart_downtime_seconds = registry.gauge(
    "cilium_tpu_restart_downtime_seconds",
    "Wall time from the start of restore_state() to the first verdict "
    "batch completed after a restart (set once per process; the "
    "journal's restore_done event carries the same span as "
    "downtime_ms)",
)
drain_seconds = registry.histogram(
    "cilium_tpu_drain_seconds",
    "Wall time of one bounded graceful drain (SIGTERM/shutdown): shed "
    "new admissions, FIFO-complete in-flight verdict + L7 batches "
    "under the deadline, persist CT + compiled + state.json",
)
state_snapshot_bytes = registry.gauge(
    "cilium_tpu_state_snapshot_bytes",
    "Bytes of the last state-dir snapshot written (label kind: "
    "compiled|ct|state_json)",
)

# -- policyd-fleetobs (fleet telemetry plane) families ---------------------
timeseries_snapshots_total = registry.counter(
    "cilium_tpu_timeseries_snapshots_total",
    "Sampler ticks appended to the fleet time-series ring (one row "
    "per FleetTelemetry cadence tick; rate ~= 1/telemetry_sample_s "
    "while the option is on)",
)
slo_burn_ratio = registry.gauge(
    "cilium_tpu_slo_burn_ratio",
    "Observed/target burn ratio per declared SLO objective and "
    "reduction window (labels: objective = the observe/fleet.py "
    "DEFAULT_OBJECTIVES names, window = 10s|1m|5m; >= 1.0 means the "
    "objective is out of budget over that window)",
)
telemetry_frames_total = registry.counter(
    "cilium_tpu_telemetry_frames_total",
    "Fleet telemetry frame outcomes (label result: published = frame "
    "written to the exchange, publish_error = kvstore down at publish "
    "time, rejected = peer frame failed version/stamp validation, "
    "stale = peer frame aged past the staleness horizon at read time)",
)
fleet_nodes_reporting = registry.gauge(
    "cilium_tpu_fleet_nodes_reporting",
    "Nodes with a live (non-stale, version-compatible) telemetry "
    "frame in the last fleet aggregation — the scoreboard's liveness "
    "denominator; drops within seconds of a node dying, ahead of its "
    "kvstore lease expiry",
)

# -- policyd-journal (lifecycle event journal) families --------------------
journal_events_total = registry.counter(
    "cilium_tpu_journal_events_total",
    "Lifecycle events recorded by the EventJournal (labels: kind = "
    "contracts.JOURNAL_KINDS row, severity = info|warning|error); "
    "counts every emit, including events later evicted from the ring",
)
journal_dropped_total = registry.counter(
    "cilium_tpu_journal_dropped_total",
    "Lifecycle events evicted from the bounded journal ring to make "
    "room for newer ones (journal_ring_capacity overflow); the GET "
    "/events tail is complete iff this stayed 0 since boot",
)
journal_frames_total = registry.counter(
    "cilium_tpu_journal_frames_total",
    "Journal tail frame outcomes on the federation exchange (label "
    "result: published | publish_error | rejected | stale — same "
    "vocabulary as telemetry_frames_total)",
)

# -- conntrack (datapath/conntrack.py) families ---------------------------
# Always on: one inc per table call, from numbers the call already holds.
ct_lookups_total = registry.counter(
    "cilium_tpu_conntrack_lookups_total",
    "Keys searched in the flow conntrack table (label op: lookup = the "
    "CT pre-pass, forward and reply tuples each counting; create = the "
    "already-present check before an insert)",
)
ct_probe_rounds_total = registry.counter(
    "cilium_tpu_conntrack_probe_rounds_total",
    "Slots probed by those searches (label op, as lookups_total): "
    "divide by lookups_total for the mean probe chain; 16 is the cap, "
    "reached when the table is full",
)
ct_inserts_total = registry.counter(
    "cilium_tpu_conntrack_inserts_total",
    "New conntrack entries by outcome (label result: inserted | dropped "
    "= a new, unique key found no free slot among its probes; the flow "
    "re-verdicts on its next batch)",
)
ct_entries = registry.gauge(
    "cilium_tpu_conntrack_entries",
    "Occupied conntrack slots: live entries plus expired ones the GC "
    "has not reaped yet (the kernel map's view); kept current by "
    "inserts, GC and flushes, never by a pass over the table",
)

# -- proxymap hand-off (Daemon._record_proxy_flows) families --------------
# Always on: one inc per batch that redirects, never per flow.
proxymap_handoff_flows_total = registry.counter(
    "cilium_tpu_proxymap_handoff_flows_total",
    "Redirected flows handed from the verdict pipeline to the proxymap "
    "(one increment per batch, by the batch's redirected count)",
)
clustermesh_events_total = registry.counter(
    "cilium_tpu_clustermesh_events_total",
    "Remote-cluster kvstore events a clustermesh pump drained (label "
    "kind: identity | ip | node | service; one increment per kind per "
    "pump, by the count drained)",
)
policy_rules_visited_total = registry.counter(
    "cilium_tpu_policy_rules_visited_total",
    "Rules whose subject selector an endpoint's L4 policy resolution "
    "tested (label direction: ingress | egress; one increment per "
    "resolution): every rule on the per-rule walk, the candidates "
    "only under PolicySubjectIndex",
)
proxymap_handoff_resolves_total = registry.counter(
    "cilium_tpu_proxymap_handoff_resolves_total",
    "Distinct peer addresses those hand-offs resolved (address string "
    "and ipcache longest-prefix lookup), once per peer per batch; "
    "divide by handoff_flows_total for the share of flows that paid "
    "for a lookup",
)

# -- host runtime (observe/gcwatch.py) families ----------------------------
# Slot counters: the collector's callback adds into them with no lock.
GC_GENERATIONS = ("generation", ("0", "1", "2"))
gc_pause_seconds_total = registry.counter(
    "cilium_tpu_gc_pause_seconds_total",
    "Wall time the Python garbage collector stopped the process "
    "(label generation: 0|1|2); always on",
    slots=GC_GENERATIONS,
)
gc_collections_total = registry.counter(
    "cilium_tpu_gc_collections_total",
    "Python garbage collections (label generation: 0|1|2); always on",
    slots=GC_GENERATIONS,
)
