"""policyd-fleetobs: time-series rings, SLO burn rates, fleet frames.

Covers the fleet-plane acceptance contract: reset-safe counter deltas
and ring wraparound reduce correctly; Histogram.quantile holds at the
edges; the burn-rate state machine is multi-window (burning only on a
sustained burn); the frame codec rejects version/stamp drift; frames
age out by wall clock ahead of kvstore leases; the aggregator folds a
fleet into one scoreboard; and the FleetTelemetry option is a real
tripwire — OFF never imports the fleet plane, never starts the
sampler thread, and leaves the verdict path bit-identical.
"""

from __future__ import annotations

import json
import sys
import threading

import numpy as np
import pytest

from cilium_tpu import metrics
from cilium_tpu.daemon import Daemon
from cilium_tpu.kvstore.backend import InMemoryBackend, InMemoryStore
from cilium_tpu.observe.fleet import (
    DEFAULT_OBJECTIVES,
    FRAME_VERSION,
    FleetSampler,
    SLObjective,
    SLOEvaluator,
    TelemetryExchange,
    aggregate,
    decode_frame,
    encode_frame,
)
from cilium_tpu.observe.timeseries import WINDOWS, CounterDelta, TimeSeriesRing
from cilium_tpu.ops.lpm import ip_strings_to_u32

RULES = [{
    "endpointSelector": {"matchLabels": {"k8s:app": "web"}},
    "ingress": [{"fromEndpoints": [{"matchLabels": {"k8s:app": "client"}}],
                 "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}]}]}],
    "labels": ["k8s:policy=fleetobs"],
}]


# ---------------------------------------------------------------------------
class TestCounterDelta:
    def test_first_call_returns_zero(self):
        d = CounterDelta()
        assert d.update(100.0) == 0.0

    def test_monotonic_deltas(self):
        d = CounterDelta()
        d.update(100.0)
        assert d.update(150.0) == 50.0
        assert d.update(150.0) == 0.0
        assert d.update(151.5) == 1.5

    def test_counter_reset_counts_new_total_whole(self):
        """A decrease means the counter restarted from zero: the new
        total IS the delta (Prometheus rate() reset rule) — never a
        negative rate."""
        d = CounterDelta()
        d.update(1000.0)
        assert d.update(30.0) == 30.0
        assert d.update(40.0) == 10.0


class TestTimeSeriesRing:
    def test_ctor_validation(self):
        with pytest.raises(ValueError, match="at least one field"):
            TimeSeriesRing(())
        with pytest.raises(ValueError, match="capacity"):
            TimeSeriesRing(("x",), capacity=1)

    def test_wraparound_keeps_newest_capacity_rows(self):
        r = TimeSeriesRing(("x",), capacity=4)
        for i in range(10):
            r.append(float(i), {"x": float(i)})
        assert len(r) == 4
        assert r.appended == 10
        ts, vals = r.window("x", None)
        # oldest-first, exactly the last `capacity` rows
        assert list(ts) == [6.0, 7.0, 8.0, 9.0]
        assert list(vals) == [6.0, 7.0, 8.0, 9.0]
        assert r.last("x") == 9.0

    def test_missing_and_unknown_fields(self):
        r = TimeSeriesRing(("x", "y"), capacity=8)
        r.append(1.0, {"x": 1.0, "zzz": 5.0})   # unknown ignored
        r.append(2.0, {"y": 2.0})                # x stays NaN this row
        r.append(3.0, {"x": 3.0, "y": None})     # None == missing
        _, xs = r.window("x", None)
        _, ys = r.window("y", None)
        assert list(xs) == [1.0, 3.0]
        assert list(ys) == [2.0]
        hist = r.history()
        assert hist[0] == {"ts": 1.0, "x": 1.0}
        assert hist[1] == {"ts": 2.0, "y": 2.0}
        assert r.history(limit=1) == [{"ts": 3.0, "x": 3.0}]

    def test_window_filtering_and_reductions(self):
        r = TimeSeriesRing(("v",), capacity=64)
        for i in range(20):
            r.append(float(i), {"v": float(i)})
        # trailing 5s from the newest sample (ts 19): rows 14..19
        _, vals = r.window("v", 5.0)
        assert list(vals) == [14.0, 15.0, 16.0, 17.0, 18.0, 19.0]
        assert r.reduce("v", "mean", 5.0) == pytest.approx(16.5)
        assert r.reduce("v", "max", 5.0) == 19.0
        assert r.reduce("v", "last", 5.0) == 19.0
        # cumulative field: (19 - 14) / (19 - 14) = 1/s
        assert r.reduce("v", "rate", 5.0) == pytest.approx(1.0)
        # explicit `now` reduces a replayed ring identically
        assert r.reduce("v", "max", 5.0, now=10.0) == 10.0

    def test_rate_needs_two_samples_spanning_time(self):
        r = TimeSeriesRing(("v",), capacity=8)
        assert r.reduce("v", "rate") is None
        r.append(1.0, {"v": 10.0})
        assert r.reduce("v", "rate") is None        # one sample
        r.append(1.0, {"v": 20.0})
        assert r.reduce("v", "rate") is None        # zero span
        r.append(3.0, {"v": 30.0})
        assert r.reduce("v", "rate") == pytest.approx(10.0)

    def test_unknown_reduction_raises(self):
        r = TimeSeriesRing(("v",), capacity=8)
        with pytest.raises(ValueError, match="unknown reduction"):
            r.reduce("v", "median")

    def test_wraparound_rate_is_reset_free(self):
        """After wraparound the ring still reduces oldest-first: rate
        over a wrapped cumulative series never sees a seam."""
        r = TimeSeriesRing(("c",), capacity=5)
        for i in range(12):
            r.append(float(i), {"c": 100.0 * i})
        assert r.reduce("c", "rate") == pytest.approx(100.0)


class TestHistogramQuantile:
    def test_unobserved_series_is_none(self):
        h = metrics.Histogram("t_fo_q0", "h", buckets=(0.1, 1.0))
        assert h.quantile(0.99) is None
        assert h.quantile(0.5, {"phase": "nope"}) is None

    def test_quantile_bounds_validated(self):
        h = metrics.Histogram("t_fo_q1", "h", buckets=(1.0,))
        h.observe(0.5)
        with pytest.raises(ValueError):
            h.quantile(0.0)
        with pytest.raises(ValueError):
            h.quantile(1.5)
        assert h.quantile(1.0) is not None

    def test_single_bucket_interpolates_from_zero(self):
        h = metrics.Histogram("t_fo_q2", "h", buckets=(10.0,))
        h.observe(4.0)
        # one sample in [0, 10]: p50 interpolates to rank*width = 5.0
        assert h.quantile(0.5) == pytest.approx(5.0)
        assert h.quantile(1.0) == pytest.approx(10.0)

    def test_overflow_only_observations_clamp_to_last_bucket(self):
        """+Inf has no upper edge: values past the last finite bucket
        estimate AT that bound, never above it."""
        h = metrics.Histogram("t_fo_q3", "h", buckets=(0.1, 1.0))
        h.observe(50.0)
        h.observe(500.0)
        assert h.quantile(0.5) == 1.0
        assert h.quantile(0.99) == 1.0

    def test_interpolation_within_landing_bucket(self):
        h = metrics.Histogram("t_fo_q4", "h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.6, 3.0):
            h.observe(v)
        # rank(0.5) = 2 lands in bucket (1, 2] holding 2 of 4 samples:
        # 1 + (2-1) * (2-1)/2 = 1.5
        assert h.quantile(0.5) == pytest.approx(1.5)
        # per-label series stay independent
        h.observe(3.0, {"phase": "a"})
        assert h.quantile(0.5, {"phase": "a"}) == pytest.approx(3.0)


# ---------------------------------------------------------------------------
def _slo_ring(values):
    """Ring with one objective field `x`: [(ts, value), ...]."""
    r = TimeSeriesRing(("x",), capacity=512)
    for ts, v in values:
        r.append(float(ts), {"x": float(v)})
    return r


OBJ = (SLObjective("lat", "x", 10.0, "max"),)


class TestSLOEvaluator:
    def test_target_must_be_positive(self):
        with pytest.raises(ValueError, match="target"):
            SLOEvaluator(_slo_ring([]), (SLObjective("z", "x", 0.0),))

    def test_ok_when_under_budget_everywhere(self):
        ev = SLOEvaluator(_slo_ring([(0, 5), (299, 5)]), OBJ)
        out = ev.evaluate(now=299.0)
        o = out["objectives"]["lat"]
        assert o["state"] == "ok" and not out["burning"]
        assert out["worst"]["objective"] == "lat"
        assert o["windows"] == {"10s": 0.5, "1m": 0.5, "5m": 0.5}

    def test_warn_on_single_window_burn(self):
        """Old burn that already stopped: the 5m window is out of
        budget but the 10s window recovered — warn, not burning."""
        ev = SLOEvaluator(_slo_ring([(0, 20), (299, 5)]), OBJ)
        out = ev.evaluate(now=299.0)
        o = out["objectives"]["lat"]
        assert o["state"] == "warn" and not out["burning"]
        assert o["windows"]["5m"] == 2.0 and o["windows"]["10s"] == 0.5

    def test_burning_needs_short_and_long_window(self):
        ev = SLOEvaluator(_slo_ring([(0, 20), (299, 20)]), OBJ)
        out = ev.evaluate(now=299.0)
        assert out["objectives"]["lat"]["state"] == "burning"
        assert out["burning"] and out["worst"]["state"] == "burning"
        assert out["worst"]["ratio"] == 2.0

    def test_gauge_family_refreshed(self):
        ev = SLOEvaluator(_slo_ring([(0, 20), (299, 20)]), OBJ)
        ev.evaluate(now=299.0)
        for label, _secs in WINDOWS:
            got = metrics.slo_burn_ratio.get(
                {"objective": "lat", "window": label}
            )
            assert got == 2.0, label

    def test_empty_window_burns_nothing(self):
        ev = SLOEvaluator(_slo_ring([]), OBJ)
        out = ev.evaluate(now=0.0)
        assert out["objectives"]["lat"]["windows"] == {
            "10s": 0.0, "1m": 0.0, "5m": 0.0,
        }
        assert out["objectives"]["lat"]["state"] == "ok"

    def test_default_objectives_cover_issue_set(self):
        assert {o.name for o in DEFAULT_OBJECTIVES} == {
            "verdict_latency_p99", "drop_mix_ratio",
            "epoch_lag", "restart_downtime",
        }


# ---------------------------------------------------------------------------
class TestFrameCodec:
    def test_roundtrip(self):
        f = encode_frame("node-a", 3, {"vps": 100.0}, cluster="c1", ts=50.0)
        d = decode_frame(f)
        assert d == {
            "v": FRAME_VERSION, "node": "node-a", "cluster": "c1",
            "seq": 3, "ts": 50.0, "vps": 100.0,
        }

    def test_rejects_version_and_stamp_drift(self):
        good = encode_frame("n", 1, {}, ts=1.0)
        assert decode_frame(good) is not None
        assert decode_frame(None) is None
        assert decode_frame("junk") is None
        assert decode_frame({**good, "v": FRAME_VERSION + 1}) is None
        assert decode_frame({**good, "node": ""}) is None
        assert decode_frame({**good, "node": 7}) is None
        assert decode_frame({**good, "seq": "x"}) is None
        bad_ts = dict(good)
        del bad_ts["ts"]
        assert decode_frame(bad_ts) is None


class TestTelemetryExchange:
    def _pair(self, store=None):
        store = store or InMemoryStore()
        a = TelemetryExchange(
            InMemoryBackend(store, "a"), "node-a", cluster="t")
        b = TelemetryExchange(
            InMemoryBackend(store, "b"), "node-b", cluster="t")
        return a, b

    def test_publish_and_peer_view(self):
        a, b = self._pair()
        assert a.publish({"vps": 10.0}, ts=100.0)
        assert b.publish({"vps": 20.0}, ts=100.0)
        a.pump()
        b.pump()
        fa = a.frames(now=101.0)
        fb = b.frames(now=101.0)
        assert set(fa) == set(fb) == {"node-a", "node-b"}
        assert fa["node-b"]["vps"] == 20.0 and fa["node-b"]["seq"] == 1
        a.close()
        b.close()

    def test_stale_frames_age_out_by_wall_clock(self):
        """The kill -9 path: the record is still in the store (its
        lease is alive for another ~minute) but the frame's wall-clock
        ts is past the horizon — it must vanish from frames() now."""
        a, b = self._pair()
        a.publish({"vps": 10.0}, ts=100.0)
        b.pump()
        stale0 = metrics.telemetry_frames_total.get({"result": "stale"})
        assert set(b.frames(now=110.0)) == {"node-a"}     # inside 15s
        assert b.frames(now=200.0) == {}                  # aged out
        assert metrics.telemetry_frames_total.get(
            {"result": "stale"}) == stale0 + 1
        # per-call override tightens the horizon
        assert b.frames(now=104.0, stale_s=3.0) == {}
        a.close()
        b.close()

    def test_three_node_fleet_then_a_dead_node_ages_out(self):
        """Three nodes publish over one store and a separate aggregator
        folds them: every frame arrives and the fleet rate is the sum
        of the nodes' rates. One node then dies without revoking its
        lease; once its frame is stale the scoreboard and the
        fleet_nodes_reporting gauge drop to the two survivors."""
        store = InMemoryStore()
        names = ["node-0", "node-1", "node-2"]
        nodes = [
            TelemetryExchange(InMemoryBackend(store, n[-1]), n, cluster="t")
            for n in names
        ]
        agg = TelemetryExchange(
            InMemoryBackend(store, "agg"), "agg", cluster="t")
        try:
            rates = [100.0, 200.0, 300.0]
            for ex, vps in zip(nodes, rates):
                assert ex.publish({"vps": vps}, ts=100.0)
            agg.pump()
            frames = agg.frames(now=101.0)
            assert set(frames) == set(names)
            out = aggregate(frames, now=101.0)
            assert out["nodes_reporting"] == 3
            assert out["fleet_vps"] == sum(rates)

            for ex, vps in zip(nodes[:2], rates):
                assert ex.publish({"vps": vps}, ts=104.0)
            agg.pump()
            out = aggregate(agg.frames(now=105.0, stale_s=3.0), now=105.0)
            assert out["nodes_reporting"] == 2
            assert {r["node"] for r in out["nodes"]} == set(names[:2])
            assert metrics.fleet_nodes_reporting.get() == 2.0
        finally:
            for ex in nodes + [agg]:
                ex.close()

    def test_version_mismatch_counted_rejected(self):
        a, b = self._pair()
        a.store.update_local_key_sync(
            "t/evil", {"v": FRAME_VERSION + 1, "node": "evil",
                       "seq": 1, "ts": 100.0})
        b.pump()
        rej0 = metrics.telemetry_frames_total.get({"result": "rejected"})
        assert b.frames(now=100.0) == {}
        assert metrics.telemetry_frames_total.get(
            {"result": "rejected"}) == rej0 + 1
        a.close()
        b.close()

    def test_other_cluster_frames_invisible(self):
        store = InMemoryStore()
        a, _ = self._pair(store)
        other = TelemetryExchange(
            InMemoryBackend(store, "o"), "node-o", cluster="other")
        other.publish({"vps": 5.0}, ts=100.0)
        a.pump()
        assert a.frames(now=100.0) == {}
        other.close()
        a.close()

    def test_publish_counts_and_seq_advance(self):
        a, _b = self._pair()
        pub0 = metrics.telemetry_frames_total.get({"result": "published"})
        a.publish({}, ts=1.0)
        a.publish({}, ts=2.0)
        a.pump()
        assert a.frames(now=2.0)["node-a"]["seq"] == 2
        assert metrics.telemetry_frames_total.get(
            {"result": "published"}) == pub0 + 2
        a.close()


class TestAggregate:
    def _frame(self, node, **kw):
        body = {"vps": 0.0, "slo": {"worst": {
            "objective": "verdict_latency_p99", "state": "ok", "ratio": 0.1,
        }}}
        body.update(kw)
        return encode_frame(node, 1, body, ts=kw.pop("ts", 100.0))

    def test_scoreboard_math(self):
        frames = {
            "a": self._frame("a", vps=100.0, policy_epoch=7, epoch_lag=0.0),
            "b": self._frame("b", vps=50.0, policy_epoch=9, epoch_lag=2.0),
        }
        frames["b"]["slo"] = {"worst": {
            "objective": "epoch_lag", "state": "burning", "ratio": 1.5,
        }}
        out = aggregate(frames, now=101.0)
        assert out["nodes_reporting"] == 2
        assert out["fleet_vps"] == 150.0
        assert out["epoch_skew"] == 2
        assert out["epoch_lag_max"] == 2.0
        assert out["worst_burn"] == {
            "objective": "epoch_lag", "state": "burning",
            "ratio": 1.5, "node": "b",
        }
        rows = {r["node"]: r for r in out["nodes"]}
        assert rows["a"]["vps"] == 100.0 and rows["a"]["slo_state"] == "ok"
        assert rows["b"]["age_s"] == 1.0
        assert metrics.fleet_nodes_reporting.get() == 2.0

    def test_empty_fleet(self):
        out = aggregate({}, now=0.0)
        assert out["nodes_reporting"] == 0 and out["fleet_vps"] == 0.0
        assert out["epoch_skew"] == 0 and out["nodes"] == []
        assert out["worst_burn"]["state"] == "ok"
        assert metrics.fleet_nodes_reporting.get() == 0.0


# ---------------------------------------------------------------------------
class TestFleetSampler:
    def test_sample_once_derives_rates_from_counters(self):
        s = FleetSampler(interval_s=1.0, capacity=16)
        s.sample_once(now=100.0)                  # priming tick
        metrics.verdicts_total.inc({"outcome": "forwarded"}, 500.0)
        sample = s.sample_once(now=101.0)
        assert sample["vps"] == pytest.approx(500.0, rel=0.01)
        assert sample["drop_ratio"] == 0.0
        assert s.ring.appended == 2
        assert s.last_slo is not None

    def test_drop_mix_ratio(self):
        s = FleetSampler(interval_s=1.0, capacity=16)
        s.sample_once(now=100.0)
        metrics.verdicts_total.inc({"outcome": "forwarded"}, 75.0)
        metrics.verdicts_total.inc({"outcome": "dropped"}, 25.0)
        sample = s.sample_once(now=101.0)
        assert sample["drop_ratio"] == pytest.approx(0.25)

    def test_frame_body_and_publication(self):
        store = InMemoryStore()
        s = FleetSampler(interval_s=1.0, capacity=16,
                         epoch_source=lambda: 42)
        s.attach_exchange(TelemetryExchange(
            InMemoryBackend(store, "x"), "node-x", cluster="t"))
        metrics.verdicts_total.inc({"outcome": "forwarded"}, 10.0)
        s.sample_once(now=100.0)
        s.sample_once(now=101.0)
        body = s.frame_body()
        assert body["policy_epoch"] == 42
        assert set(body["slo"]["states"]) == {
            o.name for o in DEFAULT_OBJECTIVES}
        frames = s.exchange.frames()
        assert frames["node-x"]["seq"] == 2
        agg = aggregate(frames)
        assert agg["nodes_reporting"] == 1
        s.stop()
        assert s.exchange is None                 # stop() closed it

    def test_snapshot_counter_and_summary(self):
        c0 = metrics.timeseries_snapshots_total.get()
        s = FleetSampler(interval_s=1.0, capacity=16)
        s.sample_once(now=1.0)
        assert metrics.timeseries_snapshots_total.get() == c0 + 1
        summary = s.slo_summary()
        assert set(summary) == {"worst_objective", "state", "ratio",
                                "burning"}
        st = s.local_status()
        assert st["samples"] == 1 and st["capacity"] == 16


# ---------------------------------------------------------------------------
def _sampler_threads():
    return [t for t in threading.enumerate() if t.name == "fleet-sampler"]


class TestFleetTelemetryOption:
    def test_off_path_never_imports_fleet_plane(self):
        """The FleetTelemetry OFF tripwire: boot, serve a batch, read
        every surface — the sampler thread never starts and the fleet
        plane (frame codec included) is never even imported."""
        sys.modules.pop("cilium_tpu.observe.fleet", None)
        sys.modules.pop("cilium_tpu.observe.timeseries", None)
        d = Daemon(pod_cidr="10.7.0.0/16")
        try:
            d.policy_add(json.dumps(RULES))
            d.endpoint_add(1, ["k8s:app=web"], ipv4="10.7.0.10")
            d.endpoint_add(2, ["k8s:app=client"], ipv4="10.7.0.11")
            src = ip_strings_to_u32(["10.7.0.11"])
            ep = d.pipeline.endpoint_index(1)
            d.pipeline.process(
                src, np.full(1, ep, np.int32),
                np.array([80], np.int32), np.array([6], np.int32),
            )
            st = d.status()
            assert st["slo"] is None and st["slo_burning"] is False
            assert d.fleet_status() == {"enabled": False}
            assert d.fleet_history() == {"enabled": False, "history": []}
            assert not _sampler_threads()
            assert "cilium_tpu.observe.fleet" not in sys.modules
            assert "cilium_tpu.observe.timeseries" not in sys.modules
        finally:
            d.shutdown()

    def test_on_starts_sampler_and_surfaces_answer(self):
        d = Daemon(pod_cidr="10.8.0.0/16")
        try:
            d.config_patch({"FleetTelemetry": True})
            sampler = d._fleet_sampler
            assert sampler is not None and _sampler_threads()
            sampler.sample_once()
            st = d.status()
            assert st["slo"] is not None
            assert set(st["slo"]) == {"worst_objective", "state",
                                      "ratio", "burning"}
            assert isinstance(st["slo_burning"], bool)
            fs = d.fleet_status()
            assert fs["enabled"] is True and fs["nodes_reporting"] == 1
            assert fs["node"] == "local"           # unfederated fold
            assert fs["local"]["samples"] >= 1
            fh = d.fleet_history(limit=4)
            assert fh["enabled"] and len(fh["history"]) >= 1
            # toggle back off: thread stops, surfaces report disabled
            d.config_patch({"FleetTelemetry": False})
            assert d._fleet_sampler is None
            assert not _sampler_threads()
            assert d.fleet_status() == {"enabled": False}
        finally:
            d.shutdown()

    def test_off_path_bit_identical(self):
        """FleetTelemetry toggled on and back off must leave the exact
        pre-option verdict path: same programs, same verdicts as a
        daemon that never enabled it."""
        ctrl = Daemon(pod_cidr="10.9.0.0/16")     # never enabled
        dut = Daemon(pod_cidr="10.9.0.0/16")
        try:
            dut.config_patch({"FleetTelemetry": True})
            dut.config_patch({"FleetTelemetry": False})
            for d in (ctrl, dut):
                d.policy_add(json.dumps(RULES))
                d.endpoint_add(1, ["k8s:app=web"], ipv4="10.9.0.10")
                d.endpoint_add(2, ["k8s:app=client"], ipv4="10.9.0.11")
                d.endpoint_add(3, ["k8s:app=other"], ipv4="10.9.0.12")
            src = ip_strings_to_u32(["10.9.0.11", "10.9.0.12"])
            dports = np.array([80, 80], np.int32)
            protos = np.array([6, 6], np.int32)
            v_c, r_c = ctrl.pipeline.process(
                src, np.full(2, ctrl.pipeline.endpoint_index(1), np.int32),
                dports, protos,
            )
            v_d, r_d = dut.pipeline.process(
                src, np.full(2, dut.pipeline.endpoint_index(1), np.int32),
                dports, protos,
            )
            np.testing.assert_array_equal(v_c, v_d)
            np.testing.assert_array_equal(r_c, r_d)
        finally:
            ctrl.shutdown()
            dut.shutdown()

    def test_boot_enabled_via_config(self):
        from cilium_tpu.option import DaemonConfig, get_config, set_config

        saved = get_config()
        d = None
        try:
            set_config(DaemonConfig(fleet_telemetry=True,
                                    telemetry_sample_s=30.0,
                                    telemetry_ring_rows=8))
            d = Daemon(pod_cidr="10.6.0.0/16")
            assert d.options.get("FleetTelemetry")
            assert d._fleet_sampler is not None
            assert d._fleet_sampler.interval_s == 30.0
            assert d._fleet_sampler.ring.capacity == 8
        finally:
            set_config(saved)
            if d is not None:
                d.shutdown()
