"""policyd-overload: admission control, prefilter shed, watchdog.

The load-bearing guarantees:

- the shed table is sound by construction: a ``[identity, class]``
  cell is 1 only when NO policymap column of ANY local endpoint could
  allow ANY flow in it, so a shed verdict (DROP_PREFILTER, monitor
  reason 144) is always a verdict the full path would also deny;
- admitted flows are bit-identical to an unloaded pipeline: the gate
  either returns None (unchanged submit path) or subsets the batch
  before the UNCHANGED programs run;
- over-budget flows are never silently dropped: prefilter-shed lanes
  carry 144, deadline-deferred lanes resolve through the failsafe
  semantics (155 fail-closed, FORWARD under FailOpen), and every
  ``result()`` returns a verdict per submitted flow;
- the watchdog bounds how long a caller can block on a wedged
  completion pull: the waiter unblocks with degraded verdicts well
  inside 2x the stall budget while the wedged thread is left to die;
- both options default OFF and the off path runs the exact pre-option
  programs (tripwire-spied, bit-identical).

Runs on the virtual 8-device CPU mesh from conftest.py.
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from __graft_entry__ import _build_datapath_world, _make_ip_flows

from cilium_tpu import faults as _faults
from cilium_tpu import metrics as _m
from cilium_tpu.datapath import pipeline as pipeline_mod
from cilium_tpu.datapath.admission import (
    N_SHED_CLASSES,
    AdmissionController,
    Watchdog,
    compile_shed_table,
    flow_class,
)
from cilium_tpu.datapath.pipeline import (
    DROP_DEGRADED,
    DROP_PREFILTER,
    FORWARD,
    DatapathPipeline,
    ipv4_to_bytes,
)
from cilium_tpu.option import DaemonConfig
from cilium_tpu.utils.backoff import Backoff


@pytest.fixture(autouse=True)
def _clean_hub():
    _faults.hub.reset()
    yield
    _faults.hub.reset()


def _flows(idents, b=96, seed=5):
    return _make_ip_flows(idents, b, seed=seed)


def _world():
    pipe, _eng, idents = _build_datapath_world(seed=3)
    return pipe, idents


def _gated_world(**kw):
    """A fresh pipeline over the shared world with overload features
    armed (baseline ``pipe`` stays untouched for parity checks)."""
    pipe, engine, idents = _build_datapath_world(seed=3)
    gp = DatapathPipeline(
        engine, pipe.ipcache, pipe.prefilter, conntrack=None,
        pipeline_depth=2,
        **{"admission": True, "prefilter_shed": True, **kw},
    )
    gp.set_endpoints([i.id for i in idents[:4]])
    gp.rebuild()
    return gp, pipe, idents


# ---------------------------------------------------------------------------
class TestFlowClass:
    def test_known_cells(self):
        # (dport, proto) -> class: 3 proto rows (tcp/udp/other) x 3
        # port buckets (<1024, <32768, ephemeral)
        cases = [
            (80, 6, 0), (8080, 6, 1), (40000, 6, 2),
            (53, 17, 3), (8080, 17, 4), (40000, 17, 5),
            (500, 47, 6), (2000, 47, 7), (65535, 132, 8),
            (0, 6, 0),
        ]
        for dport, proto, want in cases:
            assert int(flow_class(dport, proto)) == want, (dport, proto)

    def test_numpy_vectorized_matches_scalar(self):
        rng = np.random.default_rng(9)
        d = rng.integers(0, 65536, 256).astype(np.int32)
        p = rng.choice(np.array([6, 17, 47, 132, 1], np.int32), 256)
        vec = flow_class(d, p)
        ref = np.array([flow_class(int(a), int(b)) for a, b in zip(d, p)])
        np.testing.assert_array_equal(vec, ref)

    def test_jnp_parity(self):
        """The SAME operator-only law must run inside the jitted shed
        walk — host numpy and jnp classes may never diverge."""
        import jax.numpy as jnp

        d = np.array([80, 8080, 40000, 53, 0, 65535], np.int32)
        p = np.array([6, 6, 6, 17, 47, 17], np.int32)
        host = flow_class(d, p)
        dev = np.asarray(flow_class(jnp.asarray(d), jnp.asarray(p)))
        np.testing.assert_array_equal(host, dev)


# ---------------------------------------------------------------------------
class TestCompileShedTable:
    def test_column_coverage_semantics(self):
        # ep0 columns: [l3, (80,tcp), (0,udp)]; ep1 columns: [l3]
        ep_slots = [[(80, 6), (0, 17)], []]
        allow = np.zeros((4, 4), bool)
        allow[0, 0] = True   # ident0: ep0 L3 allow -> whole row covered
        allow[1, 1] = True   # ident1: (80,tcp) -> covers cell 0 only
        allow[2, 2] = True   # ident2: (0,udp) -> covers udp row (3,4,5)
        # ident3: nothing -> fully sheddable
        tab = compile_shed_table(allow, ep_slots)
        assert tab.shape == (4, N_SHED_CLASSES) and tab.dtype == np.uint8
        assert not tab[0].any()
        np.testing.assert_array_equal(
            tab[1], np.array([0, 1, 1, 1, 1, 1, 1, 1, 1], np.uint8)
        )
        np.testing.assert_array_equal(
            tab[2], np.array([1, 1, 1, 0, 0, 0, 1, 1, 1], np.uint8)
        )
        assert tab[3].all()

    def test_wildcard_proto_covers_every_row(self):
        # (443, proto=0): the wildcard proto must clear bucket 0 of ALL
        # three proto rows — anything less sheds flows a wildcard rule
        # would have allowed
        tab = compile_shed_table(
            np.array([[False, True]]), [[(443, 0)]]
        )
        np.testing.assert_array_equal(
            tab[0], np.array([0, 1, 1, 0, 1, 1, 0, 1, 1], np.uint8)
        )

    def test_port_wildcard_covers_every_bucket(self):
        tab = compile_shed_table(np.array([[False, True]]), [[(0, 6)]])
        np.testing.assert_array_equal(
            tab[0], np.array([0, 0, 0, 1, 1, 1, 1, 1, 1], np.uint8)
        )

    def test_unknown_proto_maps_to_other_row(self):
        tab = compile_shed_table(np.array([[False, True]]), [[(500, 47)]])
        assert tab[0, 6] == 0  # other row, well-known bucket
        assert tab[0, :6].all() and tab[0, 7:].all()

    def test_merged_over_endpoints(self):
        """Shed only when NO endpoint allows: the table must be valid
        for any ep_idx in the batch."""
        ep_slots = [[(80, 6)], [(0, 0)]]  # ep1 allows everything
        allow = np.zeros((2, 4), bool)
        allow[0, 1] = True  # ident0 allowed on ep0's (80,tcp)
        allow[0, 3] = True  # ident0 allowed on ep1's wildcard
        tab = compile_shed_table(allow, ep_slots)
        assert not tab[0].any()
        assert tab[1].all()  # ident1 allowed nowhere

    def test_no_endpoints_sheds_nothing(self):
        tab = compile_shed_table(np.zeros((3, 0), bool), [])
        assert tab.shape == (3, N_SHED_CLASSES) and not tab.any()

    def test_world_table_l3_rows_clear(self):
        """Invariant on the REAL materialized world: any identity row
        with an L3-only allow column set must be completely unsheddable."""
        gp, _pipe, _idents = _gated_world()
        shed = gp._dp_state[7]
        assert shed is not None
        mat = next(iter(gp._mat.values()))
        tab = compile_shed_table(mat.allow_nc, mat.ep_slots)
        assert tab.shape[1] == N_SHED_CLASSES
        col = 0
        for slots in mat.ep_slots:
            l3 = np.asarray(mat.allow_nc[:, col], bool)
            col += 1 + len(slots)
            assert not tab[l3].any()


# ---------------------------------------------------------------------------
class TestAdmissionController:
    def test_aimd_halve_and_regrow(self):
        c = AdmissionController(max_depth=8)
        assert c.limit == 8.0
        assert not c.over_budget(6)
        assert c.over_budget(8)
        c.note_queue_full()
        assert c.limit == 4.0
        assert c.over_budget(4) and not c.over_budget(3)
        prev = c.limit
        for _ in range(64):
            c.observe_completion(0.001)
            assert c.limit >= prev
            prev = c.limit
        assert c.limit == 8.0  # additive regrowth caps at max_depth

    def test_deadline_overrun_halves(self):
        c = AdmissionController(max_depth=4, deadline_ms=10.0)
        c.observe_completion(0.05)  # 50ms > 10ms budget
        assert c.limit == 2.0
        assert c.snapshot()["ewma_completion_ms"] == pytest.approx(50.0)

    def test_littles_law_projection(self):
        c = AdmissionController(max_depth=8, deadline_ms=100.0)
        c._ewma_s = 0.04
        # (depth+1) * ewma vs budget: 2*40=80ms ok, 3*40=120ms over
        assert not c.over_budget(1)
        assert c.over_budget(2)

    def test_shed_accounting_and_armistice(self):
        c = AdmissionController(max_depth=4)
        assert not c.shedding()
        c.note_admitted(50)
        c.note_shed("prefilter", 30)
        c.note_shed("deadline", 20)
        assert c.shedding()  # the tuner must not probe UP right now
        snap = c.snapshot()
        assert snap["shed"] == {"prefilter": 30, "deadline": 20}
        assert snap["admitted_flows"] == 50
        assert snap["shed_ratio"] == pytest.approx(0.5)
        assert snap["shedding"] is True


# ---------------------------------------------------------------------------
class TestShedGate:
    def test_under_budget_bit_identical(self):
        gp, base, idents = _gated_world()
        for seed in (11, 12):
            bt = _flows(idents, 128, seed=seed)
            v_g, r_g = gp.process(*bt)
            v_b, r_b = base.process(*bt)
            np.testing.assert_array_equal(v_g, v_b)
            np.testing.assert_array_equal(r_g, r_b)
        snap = gp._admission.snapshot()
        assert snap["shed_ratio"] == 0.0 and snap["admitted_flows"] > 0

    def test_shed_walk_sound_against_full_path(self):
        """End-to-end soundness: no flow the full path FORWARDs may
        appear in the shed mask (covers the table compile, the row
        mapping through the LPM walk, and the gather)."""
        gp, base, idents = _gated_world()
        bt = _flows(idents, 512, seed=21)
        v_b, _ = base.process(*bt)
        mask = gp._shed_walk(
            ipv4_to_bytes(bt[0]), bt[2], bt[3], family=4
        )
        assert mask is not None and mask.any()
        assert not np.any(mask & (v_b == FORWARD))

    def test_forced_queue_full_sheds_and_merges(self):
        """SITE_QUEUE_FULL forces the gate over budget: shed lanes
        carry DROP_PREFILTER + reason 144 + admission metrics, kept
        lanes stay bit-identical to the unloaded run."""
        gp, base, idents = _gated_world()
        bt = _flows(idents, 128, seed=31)
        v_b, _ = base.process(*bt)
        mask = gp._shed_walk(ipv4_to_bytes(bt[0]), bt[2], bt[3], family=4)
        assert mask.any() and not mask.all()  # partial shed exercises merge
        m0 = _m.admission_shed_total.get({"reason": "prefilter"})
        # the admission gate is reason 144's HOST producer
        r0 = _m.drop_reasons_total.get(
            {"reason": "prefilter", "producer": "admission"})
        limit0 = gp._admission.limit
        _faults.hub.fail(
            _faults.SITE_QUEUE_FULL, _faults.KIND_TRANSIENT, times=1
        )
        v, red = gp.process(*bt)
        n_shed = int(mask.sum())
        assert (v[mask] == DROP_PREFILTER).all()
        np.testing.assert_array_equal(v[~mask], v_b[~mask])
        assert not red[mask].any()
        # overload halved the limit; the kept part's own completion
        # already regrew it additively (+1/limit), so bound, not pin
        assert limit0 / 2.0 <= gp._admission.limit < limit0
        assert _m.admission_shed_total.get(
            {"reason": "prefilter"}
        ) - m0 == n_shed
        assert _m.drop_reasons_total.get(
            {"reason": "prefilter", "producer": "admission"}
        ) - r0 == n_shed
        # overload is NOT a device fault: the ladder must not move
        assert gp.pipeline_mode == "sharded"

    def test_gated_merge_with_rev_nat(self):
        gp, _base, idents = _gated_world()
        bt = _flows(idents, 96, seed=33)
        gp.process(*bt)  # warm
        _faults.hub.fail(
            _faults.SITE_QUEUE_FULL, _faults.KIND_TRANSIENT, times=1
        )
        out = gp.submit(*bt, return_rev_nat=True).result()
        assert len(out) == 3
        v, red, rev = out
        assert v.shape[0] == bt[0].shape[0]
        assert rev.dtype == np.uint16
        assert not rev[v == DROP_PREFILTER].any()

    def test_deadline_deferral_fail_closed_then_open(self, monkeypatch):
        """A spent deadline resolves the remainder through the failsafe
        semantics: 155 fail-closed, FORWARD under FailOpen — bounded,
        never queued forever, never silently dropped."""
        gp, _base, idents = _gated_world(deadline_ms=5.0)
        bt = _flows(idents, 64, seed=41)
        gp.process(*bt)  # warm
        p1 = gp.submit(*bt)  # occupy the queue (empty queue admits)
        adm = gp._admission
        adm._ewma_s = 10.0  # projection: nothing further can make it
        # pin the queue depth: deferral must give up on the budget, not
        # on a conveniently fast completion
        monkeypatch.setattr(gp, "_complete_oldest", lambda: True)
        r0 = _m.drop_reasons_total.get({"reason": "pipeline-degraded"})
        t0 = time.monotonic()
        v, _red = gp.submit(*bt).result()
        waited = time.monotonic() - t0
        assert waited < 1.0  # bounded by the 5ms budget, not the queue
        shed = v == DROP_PREFILTER
        assert (v[~shed] == DROP_DEGRADED).all()
        n_deferred = int((~shed).sum())
        assert adm.shed["deadline"] == n_deferred
        assert _m.drop_reasons_total.get(
            {"reason": "pipeline-degraded"}
        ) - r0 == n_deferred
        gp.set_fail_open(True)
        v2, _ = gp.submit(*bt).result()
        assert (v2[~shed] == FORWARD).all()
        gp.set_fail_open(False)
        monkeypatch.undo()
        p1.result()  # drain

    def test_faulted_submit_storm_loses_no_verdict(self):
        """An oversubscribed submit storm with queue_full and stall
        faults injected: every submitted flow comes back with a
        verdict, each DROP_PREFILTER lane is counted once under reason
        144 (its admission or its prefilter producer), and clean
        traffic afterwards walks the ladder back to sharded with the
        pre-storm verdicts."""
        gp, _base, idents = _gated_world(deadline_ms=250.0)
        gp.set_attribution(True)  # counts the device producer's drops
        gp.breaker_threshold = 2
        gp.recover_after_clean = 3
        gp.retry_min_s = gp.retry_max_s = 0.001
        storm = [_flows(idents, 96, seed=31 + i) for i in range(4)]
        v_warm, _ = gp.process(*storm[0])
        gp._shed_walk(
            ipv4_to_bytes(storm[0][0]), storm[0][2], storm[0][3], family=4
        )
        producers = ("admission", "prefilter")

        def reason_144():
            return sum(_m.drop_reasons_total.get(
                {"reason": "prefilter", "producer": p}) for p in producers)

        r0 = reason_144()
        gp.set_stall_ms(100.0)
        try:
            _faults.hub.fail(
                _faults.SITE_QUEUE_FULL, _faults.KIND_TRANSIENT, times=2)
            _faults.hub.fail(
                _faults.SITE_STALL, _faults.KIND_TRANSIENT, times=2)
            pendings = [gp.submit(*bt) for bt in storm]
            results = [p.result() for p in pendings]
            submitted = sum(bt[0].shape[0] for bt in storm)
            assert sum(v.shape[0] for v, _red in results) == submitted
            dropped = sum(int((v == DROP_PREFILTER).sum()) for v, _ in results)
            assert dropped > 0
            assert reason_144() - r0 == dropped
            rounds = 0
            while gp.pipeline_mode != "sharded" and rounds < 64:
                gp.process(*storm[rounds % 2])
                rounds += 1
        finally:
            gp.set_stall_ms(0)
        v_after, _ = gp.process(*storm[0])
        assert gp.pipeline_mode == "sharded"
        np.testing.assert_array_equal(v_after, v_warm)

    def test_shed_table_published_and_retracted(self):
        gp, _base, _idents = _gated_world()
        shed = gp._dp_state[7]
        assert shed is not None
        gp.set_prefilter_shed(False)
        gp.rebuild()
        assert gp._dp_state[7] is None
        assert gp._shed_walk(
            ipv4_to_bytes(np.array([0x0A000001], np.uint32)),
            np.array([80], np.int32), np.array([6], np.int32), family=4,
        ) is None


# ---------------------------------------------------------------------------
class TestOffPath:
    def test_off_path_never_touches_gate_or_shed(self, monkeypatch):
        """Options toggled on and back off must leave the exact
        pre-option submit path: tripwires on the gate, the shed walk,
        and the table compile prove none of them runs."""
        a, engine, idents = _build_datapath_world(seed=3)
        b = DatapathPipeline(
            engine, a.ipcache, a.prefilter, conntrack=None,
            pipeline_depth=2,
        )
        b.set_endpoints([i.id for i in idents[:4]])
        b.rebuild()
        b.set_admission(True)
        b.set_prefilter_shed(True)
        b.rebuild()
        b.set_admission(False)
        b.set_prefilter_shed(False)

        def boom(*_a, **_k):
            raise AssertionError("off path touched policyd-overload code")

        monkeypatch.setattr(pipeline_mod, "compile_shed_table", boom)
        b.rebuild()  # off: no shed compile
        assert b._dp_state[7] is None
        monkeypatch.setattr(b, "_admission_gate", boom)
        monkeypatch.setattr(b, "_shed_walk", boom)
        for seed in (51, 52):
            bt = _flows(idents, 160, seed=seed)
            v_a, r_a = a.process(*bt)
            v_b, r_b = b.process(*bt)
            np.testing.assert_array_equal(v_a, v_b)
            np.testing.assert_array_equal(r_a, r_b)


# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_poll_interval_clamped(self):
        assert Watchdog(object(), 1000.0)._poll_s == 0.25
        assert Watchdog(object(), 0.8)._poll_s == 0.001

    def test_abandons_stuck_completion(self):
        """The acceptance bound: a waiter on a wedged completion pull
        unblocks with degraded verdicts well inside 2x the stall
        budget; the wedged thread is sacrificed, not saved."""
        gp, _base, idents = _gated_world()
        bt = _flows(idents, 64, seed=61)
        gp.process(*bt)  # warm the jit so the wedge is the only delay
        pend = gp.submit(*bt)
        inf = gp._inflight[-1]
        orig = inf.finish
        release = threading.Event()

        def wedged():
            release.wait(5.0)
            return orig()

        inf.finish = wedged
        gp.set_stall_ms(50.0)
        try:
            sacrificial = threading.Thread(
                target=lambda: pend.result(), daemon=True
            )
            sacrificial.start()
            time.sleep(0.01)  # let it enter the wedge
            t0 = time.monotonic()
            v, _red = pend.result()
            waited = time.monotonic() - t0
            assert waited < 2 * 0.05 + 0.25  # 2x budget + one sweep
            assert (v == DROP_DEGRADED).all()
            wd = gp._watchdog
            assert wd.stalls >= 1
            assert wd.last_stall["site"] == "dispatch"
        finally:
            release.set()
            gp.set_stall_ms(0)
        assert gp._watchdog is None

    def test_injected_stall_counts_and_feeds_breaker(self):
        gp, _base, idents = _gated_world()
        s0 = _m.watchdog_stalls_total.get({"site": "stall"})
        _faults.hub.fail(_faults.SITE_STALL, _faults.KIND_TRANSIENT, times=2)
        gp.set_stall_ms(20.0)
        try:
            deadline = time.monotonic() + 2.0
            while (
                _m.watchdog_stalls_total.get({"site": "stall"}) - s0 < 2
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert _m.watchdog_stalls_total.get({"site": "stall"}) - s0 == 2
            assert gp._watchdog.last_stall["site"] == "stall"
        finally:
            gp.set_stall_ms(0)

    def test_watching_external_op(self):
        gp, _base, _idents = _gated_world()
        gp.set_stall_ms(30.0)
        try:
            wd = gp._watchdog
            with wd.watching("compile"):
                deadline = time.monotonic() + 2.0
                while wd.stalls == 0 and time.monotonic() < deadline:
                    time.sleep(0.01)
            assert wd.stalls == 1  # one note per op, not per sweep
            assert wd.last_stall["site"] == "compile"
            assert wd.snapshot()["watching"] == []
        finally:
            gp.set_stall_ms(0)


# ---------------------------------------------------------------------------
class TestDaemonWiring:
    def test_config_validation(self):
        with pytest.raises(ValueError):
            DaemonConfig(verdict_deadline_ms=-1).validate()
        with pytest.raises(ValueError):
            DaemonConfig(dispatch_stall_ms=-0.5).validate()
        DaemonConfig(verdict_deadline_ms=50, dispatch_stall_ms=100).validate()

    def test_admission_in_status_traces_and_patch(self, tmp_path):
        """GET /healthz and /status serve daemon.status(); bugtool
        bundles status()+traces() — the admission block rides all of
        them through this one surface."""
        from cilium_tpu.daemon import Daemon

        d = Daemon(state_dir=str(tmp_path), conntrack=False)
        try:
            st = d.status()
            assert st["admission"]["enabled"] is False
            assert st["admission"]["prefilter"] is False
            out = d.config_patch(
                {"AdmissionControl": "true", "Prefilter": "true"}
            )
            assert {"AdmissionControl", "Prefilter"} <= set(out["changed"])
            adm = d.status()["admission"]
            assert adm["enabled"] is True and adm["prefilter"] is True
            assert adm["limit"] > 0 and "shed" in adm
            assert d.traces()["admission"]["enabled"] is True
            d.config_patch(
                {"AdmissionControl": "false", "Prefilter": "false"}
            )
            assert d.status()["admission"]["enabled"] is False
        finally:
            d.shutdown()


# ---------------------------------------------------------------------------
class TestBackoff:
    def test_full_jitter_spans_the_range(self):
        b = Backoff(min_s=1.0, max_s=1.0, factor=1.0, full_jitter=True)
        samples = [b.duration() for _ in range(400)]
        assert all(0.0 <= s <= 1.0 for s in samples)
        # the half-floor of equal-jitter keeps retries synchronized —
        # full jitter must actually use the low half of the range
        assert min(samples) < 0.25 and max(samples) > 0.75

    def test_equal_jitter_keeps_half_floor(self):
        b = Backoff(min_s=1.0, max_s=1.0, factor=1.0)
        assert all(0.5 <= b.duration() <= 1.0 for _ in range(200))

    def test_max_elapsed_cap(self):
        b = Backoff(
            min_s=0.4, max_s=0.4, factor=1.0, jitter=False,
            max_elapsed_s=1.0,
        )
        assert b.duration() == pytest.approx(0.4)
        assert b.duration() == pytest.approx(0.4)
        assert b.duration() == pytest.approx(0.2)  # clamped to remainder
        assert b.duration() == 0.0
        assert b.exhausted
        b.reset()
        assert not b.exhausted
        assert b.duration() == pytest.approx(0.4)

    def test_wait_credits_back_unspent_budget(self):
        b = Backoff(
            min_s=0.2, max_s=0.2, factor=1.0, jitter=False,
            max_elapsed_s=0.2,
        )
        ev = threading.Event()
        ev.set()
        assert b.wait(ev) is True  # woke immediately
        assert not b.exhausted  # the unslept remainder was credited back
        assert b._elapsed < 0.1


