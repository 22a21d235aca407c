"""policyd-journal: HLC causal order, the bounded event ring, the
frame codec + exchange, merged fleet timelines, edge-triggered shed
episodes, and the LifecycleJournal option tripwires.

The acceptance contract: HLC ticks stay monotone under wall-clock
regression and the receive rule keeps cross-node merges causal under
skew; ring overflow is accounted (``journal_dropped_total``); frames
reject version drift; ``merge_timelines`` is deterministic for any
arrival order and dedupes overlapping tails; shed episodes are one
``shed_start``/``shed_end`` pair per storm, never one event per batch;
and LifecycleJournal OFF never imports the journal plane, never starts
the publisher thread, and leaves the verdict path bit-identical.
"""

from __future__ import annotations

import json
import sys
import threading

import numpy as np
import pytest

from cilium_tpu import metrics
from cilium_tpu.contracts import JOURNAL_KINDS, JOURNAL_SEVERITIES
from cilium_tpu.daemon import Daemon
from cilium_tpu.datapath import admission as admission_mod
from cilium_tpu.datapath.admission import (
    REASON_SHED_DEADLINE,
    REASON_SHED_PREFILTER,
    AdmissionController,
)
from cilium_tpu.kvstore.backend import InMemoryBackend, InMemoryStore
from cilium_tpu.observe.journal import (
    FRAME_VERSION,
    HLC,
    SCHEMA_VERSION,
    EventJournal,
    JournalExchange,
    JournalPublisher,
    decode_frame,
    encode_frame,
    merge_timelines,
    order_key,
    timeline_consistent,
)
from cilium_tpu.ops.lpm import ip_strings_to_u32

RULES = [{
    "endpointSelector": {"matchLabels": {"k8s:app": "web"}},
    "ingress": [{"fromEndpoints": [{"matchLabels": {"k8s:app": "client"}}],
                 "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}]}]}],
    "labels": ["k8s:policy=journal"],
}]


class _Clock:
    """Injectable wall clock (seconds, settable — HLC and EventJournal
    both take ``clock=``)."""

    def __init__(self, t: float = 1000.0) -> None:
        self.t = float(t)

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
class TestHLC:
    def test_tick_monotone_under_wall_regression(self):
        clk = _Clock(100.0)
        h = HLC(clock=clk)
        keys = [h.tick()]
        for t in (100.5, 99.0, 98.0, 98.0, 100.5):
            clk.t = t
            keys.append(h.tick())
        # strictly increasing despite the clock stepping backwards
        assert keys == sorted(keys)
        assert len(set(keys)) == len(keys)
        # the regression rode the logical component, not physical time
        assert keys[-1][0] == int(100.5 * 1000)

    def test_observe_receive_rule(self):
        clk = _Clock(100.0)
        h = HLC(clock=clk)
        h.tick()
        # fold a peer timestamp 100s AHEAD of our wall clock
        l, c = h.observe(200_000, 5)
        assert (l, c) == (200_000, 6)
        # subsequent local ticks order after the peer's event even
        # though our wall clock never caught up
        assert h.tick() == (200_000, 7)
        # a stale peer timestamp never moves the clock backwards
        before = h.read()
        assert h.observe(50_000, 9) > before

    def test_order_key_total_order(self):
        evs = [
            {"hlc": [5, 0], "node": "b", "seq": 1},
            {"hlc": [5, 0], "node": "a", "seq": 2},
            {"hlc": [4, 9], "node": "z", "seq": 9},
        ]
        assert sorted(evs, key=order_key) == [evs[2], evs[1], evs[0]]
        # missing hlc sorts first, not a crash
        assert order_key({"node": "n", "seq": 3}) == (0, 0, "n", 3)


# ---------------------------------------------------------------------------
class TestEventJournal:
    def test_emit_validates_vocabulary(self):
        j = EventJournal(node="n", capacity=8)
        with pytest.raises(ValueError, match="unknown journal kind"):
            j.emit(kind="not-a-kind")
        with pytest.raises(ValueError, match="unknown journal severity"):
            j.emit(kind="boot", severity="fatal")
        assert j.seq == 0 and j.events() == []

    def test_event_shape_and_attr_isolation(self):
        clk = _Clock(123.456789)
        j = EventJournal(node="node-a", capacity=8, clock=clk)
        attrs = {"policy_epoch": 7}
        ev = j.emit(kind="boot", attrs=attrs)
        attrs["policy_epoch"] = 99  # caller mutation must not leak
        assert ev["seq"] == 1
        assert ev["node"] == "node-a"
        assert ev["kind"] == "boot" and ev["severity"] == "info"
        assert ev["wall_ts"] == pytest.approx(123.456789)
        assert j.events()[0]["attrs"] == {"policy_epoch": 7}
        c0 = metrics.journal_events_total.get(
            {"kind": "boot", "severity": "info"})
        j.emit(kind="boot")
        assert metrics.journal_events_total.get(
            {"kind": "boot", "severity": "info"}) == c0 + 1

    def test_ring_overflow_accounting(self):
        d0 = metrics.journal_dropped_total.get()
        j = EventJournal(node="n", capacity=4)
        for _ in range(10):
            j.emit(kind="boot")
        assert j.seq == 10 and j.dropped == 6
        assert metrics.journal_dropped_total.get() == d0 + 6
        # the ring keeps exactly the newest `capacity`, oldest first
        assert [e["seq"] for e in j.tail(64)] == [7, 8, 9, 10]
        snap = j.snapshot()
        assert snap["journal_schema"] == SCHEMA_VERSION
        assert snap["recorded"] == 10 and snap["dropped"] == 6
        assert snap["capacity"] == 4
        with pytest.raises(ValueError, match="capacity"):
            EventJournal(capacity=0)

    def test_events_filters(self):
        clk = _Clock(10.0)
        j = EventJournal(node="n", capacity=32, clock=clk)
        j.emit(kind="boot")
        clk.t = 20.0
        j.emit(kind="shed_start", severity="warning")
        clk.t = 30.0
        j.emit(kind="shed_end")
        assert [e["kind"] for e in j.events()] == [
            "boot", "shed_start", "shed_end"]
        assert [e["kind"] for e in j.events(kind="shed_start")] == [
            "shed_start"]
        assert [e["kind"] for e in j.events(severity="warning")] == [
            "shed_start"]
        assert [e["kind"] for e in j.events(since=20.0)] == [
            "shed_start", "shed_end"]
        assert [e["kind"] for e in j.events(1)] == ["shed_end"]


# ---------------------------------------------------------------------------
class TestFrameCodec:
    def _frame(self, **over):
        f = encode_frame("node-a", 3, [{"seq": 1, "hlc": [5, 0]}],
                         cluster="t", ts=100.0)
        f.update(over)
        return f

    def test_round_trip(self):
        f = self._frame()
        assert f["v"] == FRAME_VERSION
        assert f["journal_schema"] == SCHEMA_VERSION
        assert f["seq"] == 3 and f["ts"] == 100.0 and f["cluster"] == "t"
        assert decode_frame(f) == f

    def test_rejections(self):
        assert decode_frame(None) is None
        assert decode_frame([1, 2]) is None
        assert decode_frame(self._frame(v=FRAME_VERSION + 1)) is None
        assert decode_frame(
            self._frame(journal_schema=SCHEMA_VERSION + 1)) is None
        assert decode_frame(self._frame(node="")) is None
        assert decode_frame(self._frame(node=7)) is None
        assert decode_frame(self._frame(events={"not": "a list"})) is None
        assert decode_frame(self._frame(seq="x")) is None
        assert decode_frame(self._frame(ts=None)) is None


# ---------------------------------------------------------------------------
class TestMergeTimelines:
    def _skewed_pair(self):
        """node-a's wall clock runs 120s AHEAD of node-b's."""
        ca, cb = _Clock(1120.0), _Clock(1000.0)
        return (EventJournal(node="node-a", capacity=32, clock=ca),
                EventJournal(node="node-b", capacity=32, clock=cb))

    def test_merge_dedupes_and_is_deterministic(self):
        ja, jb = self._skewed_pair()
        ja.emit(kind="boot")
        ja.emit(kind="rebuild")
        jb.emit(kind="boot")
        frame_a = encode_frame("node-a", 1, ja.tail(), ts=1120.0)
        # node-a appears twice: as a peer frame AND as a local tail —
        # overlap must collapse on (node, seq)
        m1 = merge_timelines({"node-a": frame_a, "node-b": jb.tail(),
                              "local": ja.tail()})
        m2 = merge_timelines({"local": ja.tail(), "node-b": jb.tail(),
                              "node-a": frame_a})
        assert m1 == m2
        assert len(m1) == 3
        assert sorted(e["seq"] for e in m1 if e["node"] == "node-a") == [1, 2]
        assert timeline_consistent(m1)
        assert merge_timelines({"node-a": frame_a}, limit=1) == [
            ja.tail()[-1]]

    def test_observe_keeps_causal_order_under_skew(self):
        ja, jb = self._skewed_pair()
        e1 = ja.emit(kind="quarantine", severity="error")
        # without the receive rule, node-b (120s behind) would emit its
        # causally-LATER rescue event with a smaller HLC
        naive = jb.hlc.read()
        assert naive < tuple(e1["hlc"])
        jb.hlc.observe(*e1["hlc"])
        e2 = jb.emit(kind="ct_restore")
        merged = merge_timelines({"a": ja.tail(), "b": jb.tail()})
        assert [e["kind"] for e in merged] == ["quarantine", "ct_restore"]
        assert timeline_consistent(merged)

    def test_timeline_consistent_negatives(self):
        ja, jb = self._skewed_pair()
        jb.emit(kind="boot")
        ja.emit(kind="boot")
        good = merge_timelines({"a": ja.tail(), "b": jb.tail()})
        assert timeline_consistent(good)
        # global HLC order violated
        assert not timeline_consistent(list(reversed(good)))
        # per-node seq order violated (same node, non-increasing seq)
        dup = good + [dict(good[0])]
        assert not timeline_consistent(dup)
        assert timeline_consistent([])


# ---------------------------------------------------------------------------
class TestExchangeAndPublisher:
    def _node(self, store, name, clock):
        j = EventJournal(node=name, capacity=32, clock=clock)
        pub = JournalPublisher(j, tail_n=16)
        pub.attach_exchange(JournalExchange(
            InMemoryBackend(store, name[-1]), name, cluster="t"))
        return j, pub

    def test_publish_iff_moved_and_peer_view(self):
        store = InMemoryStore()
        ja, pa = self._node(store, "node-a", _Clock(100.0))
        jb, pb = self._node(store, "node-b", _Clock(100.0))
        try:
            ja.emit(kind="boot")
            assert pa.publish_once() is True
            # no journal movement since: nothing to publish
            assert pa.publish_once() is False
            jb.emit(kind="boot")
            assert pb.publish_once() is True
            merged = pb.merged_timeline()
            assert {e["node"] for e in merged} == {"node-a", "node-b"}
            assert timeline_consistent(merged)
        finally:
            pa.stop()
            pb.stop()
        # stop() detached and closed the exchange: later ticks no-op
        assert pa.exchange is None and pa.publish_once() is False

    def test_publisher_folds_peer_clocks(self):
        """A 300s-skewed fleet still merges HLC-consistently because
        publish_once folds every peer frame's newest HLC into the
        local clock (the chaos-round invariant)."""
        store = InMemoryStore()
        ja, pa = self._node(store, "node-a", _Clock(1300.0))  # ahead
        jb, pb = self._node(store, "node-b", _Clock(1000.0))  # behind
        try:
            ja.emit(kind="drain_begin")
            pa.publish_once()
            pb.publish_once()  # pumps + observes node-a's tail HLC
            jb.emit(kind="boot")  # causally after the drain it saw
            pb.publish_once()
            pa.publish_once()
            for pub in (pa, pb):
                merged = pub.merged_timeline()
                assert [e["kind"] for e in merged] == [
                    "drain_begin", "boot"]
                assert timeline_consistent(merged)
        finally:
            pa.stop()
            pb.stop()

    def test_three_node_skewed_chain_merges_in_causal_order(self):
        """Three journals whose wall clocks run 120s ahead, on time and
        120s behind take turns in one causal chain. Every node hears
        the fleet before its own step, so each node's merged timeline
        replays the emission order across all three."""
        store = InMemoryStore()
        nodes = {
            "node-a": self._node(store, "node-a", _Clock(1120.0)),
            "node-b": self._node(store, "node-b", _Clock(1000.0)),
            "node-c": self._node(store, "node-c", _Clock(880.0)),
        }
        chain = [
            ("node-a", "drain_begin"), ("node-b", "boot"),
            ("node-c", "ct_restore"), ("node-a", "drain_end"),
            ("node-b", "rebuild"), ("node-c", "restore_done"),
        ]
        try:
            for name, kind in chain:
                for _j, pub in nodes.values():
                    pub.publish_once()
                nodes[name][0].emit(kind=kind)
                nodes[name][1].publish_once()
            for _j, pub in nodes.values():
                pub.publish_once()
            for _j, pub in nodes.values():
                merged = pub.merged_timeline(limit=64)
                assert {e["node"] for e in merged} == set(nodes)
                assert [e["kind"] for e in merged] == [k for _, k in chain]
                assert timeline_consistent(merged)
        finally:
            for _j, pub in nodes.values():
                pub.stop()

    def test_frames_age_out_and_reject_drift(self):
        store = InMemoryStore()
        ja, pa = self._node(store, "node-a", _Clock(100.0))
        try:
            ex = pa.exchange
            ja.emit(kind="boot")
            assert ex.publish(ja.tail(), ts=100.0)
            ex.pump()
            assert set(ex.frames(now=101.0)) == {"node-a"}
            # past the staleness horizon the frame disappears
            assert ex.frames(now=100.0 + ex.stale_s + 1.0) == {}
            # a frame from a future codec version is rejected
            bad = encode_frame("node-z", 1, [], cluster="t", ts=100.0)
            bad["v"] = FRAME_VERSION + 1
            ex.store.update_local_key_sync("t/node-z", bad)
            ex.pump()
            r0 = metrics.journal_frames_total.get({"result": "rejected"})
            assert set(ex.frames(now=101.0)) == {"node-a"}
            assert metrics.journal_frames_total.get(
                {"result": "rejected"}) == r0 + 1
        finally:
            pa.stop()


# ---------------------------------------------------------------------------
class _FakeTime:
    """Stand-in for the admission module's ``time`` (monotonic only —
    the episode hysteresis must be tested at exact hold boundaries)."""

    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def monotonic(self) -> float:
        return self.t


class TestShedEpisodes:
    @pytest.fixture
    def adm(self, monkeypatch):
        fake = _FakeTime()
        monkeypatch.setattr(admission_mod, "time", fake)
        a = AdmissionController(max_depth=8)
        a.events = []
        a.on_journal = lambda **kw: a.events.append(kw)
        a.clock = fake
        return a

    def test_one_start_per_episode(self, adm):
        adm.note_shed(REASON_SHED_PREFILTER, 3)
        adm.clock.t += 0.5
        adm.note_shed(REASON_SHED_PREFILTER, 2)
        adm.clock.t += 0.4
        adm.note_shed(REASON_SHED_DEADLINE, 1)
        # three shed batches inside the hold: exactly ONE edge event
        assert [e["kind"] for e in adm.events] == ["shed_start"]
        assert adm.events[0]["severity"] == "warning"
        assert adm.events[0]["attrs"] == {"reason": REASON_SHED_PREFILTER}

    def test_poll_closes_quiet_episode_with_deltas(self, adm):
        adm.note_shed(REASON_SHED_PREFILTER, 3)
        adm.clock.t += 0.5
        adm.note_shed(REASON_SHED_PREFILTER, 2)
        adm.clock.t += adm.SHED_HOLD_S  # hold expires
        adm.episode_poll()
        assert [e["kind"] for e in adm.events] == ["shed_start", "shed_end"]
        end = adm.events[-1]["attrs"]
        # per-reason deltas for THIS episode; duration spans first to
        # last shed, not to the poll that noticed the quiet
        assert end["shed"] == {REASON_SHED_PREFILTER: 5}
        assert end["duration_s"] == pytest.approx(0.5)
        # a second poll finds nothing to close
        adm.episode_poll()
        assert len(adm.events) == 2
        # the next storm opens a fresh episode
        adm.clock.t += 5.0
        adm.note_shed(REASON_SHED_DEADLINE, 1)
        assert [e["kind"] for e in adm.events] == [
            "shed_start", "shed_end", "shed_start"]
        assert adm.events[-1]["attrs"] == {"reason": REASON_SHED_DEADLINE}

    def test_late_burst_closes_previous_episode_first(self, adm):
        adm.note_shed(REASON_SHED_PREFILTER, 3)
        adm.clock.t += adm.SHED_HOLD_S + 1.0
        # no poll ran: the burst itself must retire the stale episode,
        # and the old episode's deltas must NOT include the new burst
        adm.note_shed(REASON_SHED_PREFILTER, 7)
        assert [e["kind"] for e in adm.events] == [
            "shed_start", "shed_end", "shed_start"]
        assert adm.events[1]["attrs"]["shed"] == {REASON_SHED_PREFILTER: 3}
        assert adm.events[1]["attrs"]["duration_s"] == pytest.approx(0.0)

    def test_off_path_keeps_counters_without_events(self, monkeypatch):
        fake = _FakeTime()
        monkeypatch.setattr(admission_mod, "time", fake)
        a = AdmissionController(max_depth=8)  # on_journal stays None
        a.note_shed(REASON_SHED_PREFILTER, 4)
        fake.t += a.SHED_HOLD_S
        a.episode_poll()
        assert a.shed[REASON_SHED_PREFILTER] == 4
        assert a._episode is None


# ---------------------------------------------------------------------------
def _publisher_threads():
    return [t for t in threading.enumerate()
            if t.name == "journal-publisher"]


def _serve_one(d, ip_web, ip_client):
    d.policy_add(json.dumps(RULES))
    d.endpoint_add(1, ["k8s:app=web"], ipv4=ip_web)
    d.endpoint_add(2, ["k8s:app=client"], ipv4=ip_client)
    src = ip_strings_to_u32([ip_client])
    ep = d.pipeline.endpoint_index(1)
    return d.pipeline.process(
        src, np.full(1, ep, np.int32),
        np.array([80], np.int32), np.array([6], np.int32),
    )


class TestLifecycleJournalOption:
    def test_off_path_never_imports_journal(self):
        """The LifecycleJournal OFF tripwire: boot, serve a batch, read
        every surface — the publisher thread never starts and the
        journal plane (HLC + frame codec included) is never even
        imported."""
        sys.modules.pop("cilium_tpu.observe.journal", None)
        d = Daemon(pod_cidr="10.21.0.0/16")
        try:
            _serve_one(d, "10.21.0.10", "10.21.0.11")
            assert d.events() == {"enabled": False, "events": []}
            assert d.fleet_timeline() == {"enabled": False, "events": []}
            assert d.pipeline.on_journal is None
            assert not _publisher_threads()
            assert "cilium_tpu.observe.journal" not in sys.modules
        finally:
            d.shutdown()

    def test_on_surfaces_events_and_toggle_off(self):
        d = Daemon(pod_cidr="10.22.0.0/16")
        try:
            d.config_patch({"LifecycleJournal": True})
            assert d._journal is not None and _publisher_threads()
            # hot-module slots armed to the journal's bound emit
            assert d.pipeline.on_journal == d._journal.emit
            # first batch rebuilds → lifecycle events
            _serve_one(d, "10.22.0.10", "10.22.0.11")
            out = d.events()
            assert out["enabled"] is True
            assert out["journal_schema"] == SCHEMA_VERSION
            kinds = [e["kind"] for e in out["events"]]
            assert "rebuild" in kinds
            assert set(kinds) <= set(JOURNAL_KINDS)
            for e in out["events"]:
                assert e["severity"] in JOURNAL_SEVERITIES
            only = d.events(kind="rebuild")["events"]
            assert only and all(e["kind"] == "rebuild" for e in only)
            ft = d.fleet_timeline()
            assert ft["enabled"] is True and ft["nodes"] == ["local"]
            assert ft["consistent"] is True
            assert [e["seq"] for e in ft["events"]] == sorted(
                e["seq"] for e in ft["events"])
            # toggle back off: thread stops, slots disarm, surfaces
            # report disabled
            d.config_patch({"LifecycleJournal": False})
            assert d._journal is None and not _publisher_threads()
            assert d.pipeline.on_journal is None
            assert d.events() == {"enabled": False, "events": []}
        finally:
            d.shutdown()

    def test_drain_events_bracket_zero_loss(self):
        d = Daemon(pod_cidr="10.23.0.0/16")
        try:
            d.config_patch({"LifecycleJournal": True})
            _serve_one(d, "10.23.0.10", "10.23.0.11")
            d.drain(deadline_s=2.0)
            evs = d.events(limit=256)["events"]
            kinds = [e["kind"] for e in evs]
            assert kinds.index("drain_begin") < kinds.index("drain_end")
            end = [e for e in evs if e["kind"] == "drain_end"][-1]
            assert end["attrs"]["verdicts_lost"] == 0
            assert end["attrs"]["drain_s"] >= 0.0
        finally:
            d.shutdown()

    def test_off_path_bit_identical(self):
        """LifecycleJournal toggled on and back off must leave the
        exact pre-option verdict path: same verdicts and reasons as a
        daemon that never enabled it."""
        ctrl = Daemon(pod_cidr="10.24.0.0/16")    # never enabled
        dut = Daemon(pod_cidr="10.24.0.0/16")
        try:
            dut.config_patch({"LifecycleJournal": True})
            dut.config_patch({"LifecycleJournal": False})
            for d in (ctrl, dut):
                d.policy_add(json.dumps(RULES))
                d.endpoint_add(1, ["k8s:app=web"], ipv4="10.24.0.10")
                d.endpoint_add(2, ["k8s:app=client"], ipv4="10.24.0.11")
                d.endpoint_add(3, ["k8s:app=other"], ipv4="10.24.0.12")
            src = ip_strings_to_u32(["10.24.0.11", "10.24.0.12"])
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

    def test_boot_enabled_via_config_captures_boot_event(self):
        from cilium_tpu.option import DaemonConfig, get_config, set_config

        saved = get_config()
        d = None
        try:
            set_config(DaemonConfig(lifecycle_journal=True,
                                    journal_ring_capacity=32,
                                    journal_publish_s=30.0,
                                    journal_tail_n=16))
            d = Daemon(pod_cidr="10.25.0.0/16")
            assert d.options.get("LifecycleJournal")
            assert d._journal is not None
            assert d._journal.capacity == 32
            assert d._journal_publisher.interval_s == 30.0
            assert d._journal_publisher.tail_n == 16
            # the ctor's boot marker landed — ONLY a boot-enabled
            # journal can anchor the restart-downtime window
            boots = d.events(kind="boot")["events"]
            assert len(boots) == 1
            assert "policy_epoch" in boots[0]["attrs"]
        finally:
            set_config(saved)
            if d is not None:
                d.shutdown()
