"""policyd-failsafe: fault injection, self-healing, and the ladder.

The load-bearing guarantees:

- every injection site (h2d, dispatch, complete, ct_epoch, kvstore,
  attach) fires deterministically, and the pipeline classifies:
  transient faults retry invisibly (verdicts bit-identical to clean),
  poisoned faults quarantine (degraded RESULT, never an exception),
  programmer errors surface raw (the pre-failsafe contract);
- the degradation ladder descends sharded → single-device → host on a
  tripped breaker and re-promotes on clean streaks, re-forming the
  mesh each way; host-mode verdicts match device verdicts;
- fail-closed degraded batches carry DROP_DEGRADED → monitor reason
  155 and never touch rule_hits_total; FailOpen flips them to FORWARD;
- the OFF path (FaultInjection/FailOpen untouched) is bit-identical
  to an untouched pipeline: verdicts, counters, compiled shape keys;
- the proxy satellites reject HPACK bombs, excess streams, short
  priority blocks, and over-long huffman padding.

Runs on the virtual 8-device CPU mesh from conftest.py.
"""

from __future__ import annotations

import os
import socket
import struct
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from __graft_entry__ import _build_datapath_world, _make_ip_flows

from cilium_tpu import faults as _faults
from cilium_tpu import metrics as _m
from cilium_tpu.datapath.conntrack import FlowConntrack
from cilium_tpu.datapath.pipeline import (
    DROP_DEGRADED,
    FORWARD,
    DatapathPipeline,
)
from cilium_tpu.monitor.events import REASON_PIPELINE_DEGRADED, reason_name


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


def _ct_world(depth: int = 1):
    pipe, engine, idents = _build_datapath_world(seed=3)
    ct = DatapathPipeline(
        engine, pipe.ipcache, pipe.prefilter,
        conntrack=FlowConntrack(capacity_bits=12), pipeline_depth=depth,
    )
    ct.set_endpoints([i.id for i in idents[:4]])
    ct.rebuild()
    return ct, idents


# ---------------------------------------------------------------------------
class TestFaultHub:
    def test_fail_rule_after_times(self):
        hub = _faults.FaultHub()
        hub.fail("x", _faults.KIND_TRANSIENT, times=2, after=1)
        hub.check("x")  # skipped (after=1)
        for _ in range(2):
            with pytest.raises(_faults.TransientFault):
                hub.check("x")
        hub.check("x")  # rule consumed
        assert hub.injected[("x", "transient")] == 2

    def test_poisoned_rule_kind(self):
        hub = _faults.FaultHub()
        hub.fail("y", _faults.KIND_POISONED)
        with pytest.raises(_faults.PoisonedFault):
            hub.check("y")
        with pytest.raises(ValueError):
            hub.fail("y", "bogus")

    def test_probabilistic_determinism(self):
        """Same seed → same per-site injection sequence, regardless of
        visit interleaving across other sites."""

        def seq(hub, site, n=200):
            out = []
            for _ in range(n):
                try:
                    hub.check(site)
                    out.append(0)
                except _faults.FaultError as e:
                    out.append(1 if e.kind == "transient" else 2)
            return out

        a = _faults.FaultHub()
        a.arm(seed=7, rate=0.25, poison_every=3)
        b = _faults.FaultHub()
        b.arm(seed=7, rate=0.25, poison_every=3)
        # identical visit patterns → identical sequences incl. kinds
        seq_a = seq(a, _faults.SITE_H2D)
        assert seq_a == seq(b, _faults.SITE_H2D)
        assert 1 in seq_a and 2 in seq_a
        # interleaving visits to ANOTHER site must not move which h2d
        # visits fire (per-site RNGs); only the transient/poisoned
        # split may shift (poison_every is a hub-global cadence)
        c = _faults.FaultHub()
        c.arm(seed=7, rate=0.25, poison_every=3)
        seq_c = []
        for _ in range(200):
            seq(c, _faults.SITE_DISPATCH, 1)
            seq_c += seq(c, _faults.SITE_H2D, 1)
        assert [min(x, 1) for x in seq_c] == [min(x, 1) for x in seq_a]
        d = _faults.FaultHub()
        d.arm(seed=8, rate=0.25)
        assert [min(x, 1) for x in seq(d, _faults.SITE_H2D)] != [
            min(x, 1) for x in seq_a
        ]

    def test_disable_keeps_rules_reset_drops(self):
        hub = _faults.FaultHub()
        hub.fail("z")
        assert hub.active
        hub.disable()
        assert not hub.active
        hub.enable()
        with pytest.raises(_faults.TransientFault):
            hub.check("z")
        hub.fail("z")
        hub.reset()
        assert not hub.active and hub.snapshot()["pending_rules"] == {}

    def test_classify(self):
        assert _faults.classify(TimeoutError()) == "transient"
        assert _faults.classify(ConnectionResetError()) == "transient"
        assert _faults.classify(OSError()) == "transient"
        assert _faults.classify(RuntimeError("xla bad")) == "poisoned"
        assert _faults.classify(Exception("?")) == "poisoned"
        for e in (TypeError(), KeyError(), ValueError(), AssertionError(),
                  KeyboardInterrupt(), MemoryError()):
            assert _faults.classify(e) == "error"
        assert _faults.classify(_faults.TransientFault("s")) == "transient"
        assert _faults.classify(_faults.PoisonedFault("s")) == "poisoned"

    def test_injection_counts_metric_once(self):
        before = _m.pipeline_faults_total.get(
            {"site": "h2d", "kind": "transient"}
        )
        _faults.hub.fail(_faults.SITE_H2D, times=3)
        for _ in range(3):
            with pytest.raises(_faults.TransientFault):
                _faults.hub.check(_faults.SITE_H2D)
        assert _m.pipeline_faults_total.get(
            {"site": "h2d", "kind": "transient"}
        ) == before + 3


# ---------------------------------------------------------------------------
class TestClassifiedSites:
    """Every pipeline site × {transient, poisoned}."""

    @pytest.mark.parametrize(
        "site",
        [_faults.SITE_H2D, _faults.SITE_DISPATCH, _faults.SITE_COMPLETE],
    )
    def test_transient_is_invisible(self, site):
        pipe, idents = _world()
        bt = _flows(idents)
        ref_v, ref_r = pipe.process(*bt)
        _faults.hub.fail(site, _faults.KIND_TRANSIENT, times=1)
        v, r = pipe.process(*bt)
        np.testing.assert_array_equal(v, ref_v)
        np.testing.assert_array_equal(r, ref_r)
        assert pipe.pipeline_mode == "sharded"
        assert pipe.failsafe_state()["quarantined_batches"] == 0

    @pytest.mark.parametrize(
        "site",
        [_faults.SITE_H2D, _faults.SITE_DISPATCH, _faults.SITE_COMPLETE],
    )
    def test_poisoned_quarantines_fail_closed(self, site):
        pipe, idents = _world()
        bt = _flows(idents)
        pipe.process(*bt)  # warm
        _faults.hub.fail(site, _faults.KIND_POISONED, times=1)
        v, r = pipe.process(*bt)
        assert (v == DROP_DEGRADED).all()
        assert not r.any()
        assert pipe.failsafe_state()["quarantined_batches"] == 1
        # one poisoned batch must not trip the breaker (threshold 3)
        assert pipe.pipeline_mode == "sharded"
        # and the NEXT batch is healthy again
        ref_v, _ = pipe.process(*bt)
        assert (ref_v != DROP_DEGRADED).any()

    def test_transient_exhaustion_quarantines(self):
        pipe, idents = _world()
        bt = _flows(idents)
        pipe.process(*bt)
        pipe.retry_min_s = pipe.retry_max_s = 0.001
        # retry_limit=2 → 1 + 2 attempts all fault → quarantine
        _faults.hub.fail(
            _faults.SITE_COMPLETE, _faults.KIND_TRANSIENT, times=3
        )
        v, _ = pipe.process(*bt)
        assert (v == DROP_DEGRADED).all()
        assert pipe.failsafe_state()["quarantined_batches"] == 1

    def test_ct_epoch_site_transient_and_poisoned(self):
        pipe, idents = _ct_world()
        bt = _flows(idents)
        sports = np.arange(bt[0].shape[0], dtype=np.int32) + 1024
        ref_v, _ = pipe.process(*bt, sports=sports)
        epoch0 = pipe._ct_epoch
        # a basis move (ipcache change) makes the next rebuild advance
        # the CT epoch — the injection point
        pipe.ipcache.upsert("10.99.0.0/16", idents[0].id, source="k8s")
        _faults.hub.fail(_faults.SITE_CT_EPOCH, _faults.KIND_TRANSIENT, 1)
        v, _ = pipe.process(*bt, sports=sports)
        np.testing.assert_array_equal(v, ref_v)  # retried rebuild
        assert pipe._ct_epoch > epoch0
        pipe.ipcache.upsert("10.98.0.0/16", idents[0].id, source="k8s")
        _faults.hub.fail(_faults.SITE_CT_EPOCH, _faults.KIND_POISONED, 1)
        v, _ = pipe.process(*bt, sports=sports)
        assert (v == DROP_DEGRADED).all()

    def test_kvstore_site(self):
        from cilium_tpu.kvstore.backend import InMemoryBackend, InMemoryStore
        from cilium_tpu.kvstore.store import SharedStore

        store = SharedStore(InMemoryBackend(InMemoryStore()), "fs")
        store.backend.update(store._key_path("a"), b'{"n": 1}')
        _faults.hub.fail(_faults.SITE_KVSTORE, _faults.KIND_TRANSIENT, 1)
        # transient partition: nothing applied, nothing LOST
        assert store.pump() == 0
        assert "a" not in store.shared
        assert store.pump() >= 1
        assert store.shared["a"] == {"n": 1}
        _faults.hub.fail(_faults.SITE_KVSTORE, _faults.KIND_POISONED, 1)
        with pytest.raises(_faults.PoisonedFault):
            store.pump()

    @pytest.mark.parametrize("site", _faults.SITES)
    def test_one_shot_fault_is_consumed(self, site):
        _faults.hub.fail(site, _faults.KIND_TRANSIENT, 1)
        with pytest.raises(_faults.TransientFault):
            _faults.hub.check(site)
        _faults.hub.check(site)  # consumed → clean

    def test_programmer_error_still_raises_raw(self):
        """KIND_ERROR exceptions must pass through self-healing
        untouched — a bug is a bug, not a fault."""
        pipe, idents = _world()
        bt = _flows(idents)
        pipe.process(*bt)
        with pytest.raises((TypeError, ValueError)):
            pipe.process(np.asarray(bt[0]), "not-an-array", bt[2], bt[3])
        assert pipe.failsafe_state()["quarantined_batches"] == 0


# ---------------------------------------------------------------------------
class TestLadder:
    def _trippy(self, sharding=False):
        if sharding:
            base, engine, idents = _build_datapath_world(seed=3)
            pipe = DatapathPipeline(
                engine, base.ipcache, base.prefilter, sharding=True
            )
            pipe.set_endpoints([i.id for i in idents[:4]])
            pipe.rebuild()
        else:
            pipe, idents = _world()
        pipe.breaker_threshold = 2
        pipe.recover_after_clean = 3
        pipe.retry_min_s = pipe.retry_max_s = 0.001
        return pipe, idents

    def test_descend_and_repromote_full_ladder(self):
        import jax

        pipe, idents = self._trippy(sharding=True)
        bt = _flows(idents)
        ref_v, ref_r = pipe.process(*bt)
        d0 = _m.degradations_total.get(
            {"from": "sharded", "to": "single-device"}
        )

        for _ in range(2):
            _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
            pipe.process(*bt)
        assert pipe.pipeline_mode == "single-device"
        assert _m.degradations_total.get(
            {"from": "sharded", "to": "single-device"}
        ) == d0 + 1
        assert _m.pipeline_mode.get() == 1.0
        # the mesh re-forms over ONE healthy device
        excl = pipe.failsafe_state()["excluded_devices"]
        assert len(excl) == len(jax.devices()) - 1
        v, r = pipe.process(*bt)
        np.testing.assert_array_equal(v, ref_v)
        # one healthy device left → no mesh, plain placement
        assert pipe._mesh is None

        for _ in range(2):
            _faults.hub.fail(_faults.SITE_DISPATCH, _faults.KIND_POISONED, 1)
            pipe.process(*bt)
        assert pipe.pipeline_mode == "host"
        assert _m.pipeline_mode.get() == 2.0
        # host/numpy fallback still issues CORRECT verdicts
        v, r = pipe.process(*bt)
        np.testing.assert_array_equal(v, ref_v)
        np.testing.assert_array_equal(r, ref_r)

        # clean streaks walk back up, one level per probe
        rounds = 0
        while pipe.pipeline_mode != "sharded" and rounds < 32:
            pipe.process(*bt)
            rounds += 1
        assert pipe.pipeline_mode == "sharded"
        assert pipe.failsafe_state()["excluded_devices"] == []
        assert _m.pipeline_mode.get() == 0.0
        v, r = pipe.process(*bt)
        np.testing.assert_array_equal(v, ref_v)
        assert pipe._mesh is not None
        assert pipe._mesh.devices.size == len(jax.devices())

    def test_clean_streak_clears_breaker_without_descent(self):
        pipe, idents = self._trippy()
        bt = _flows(idents)
        pipe.process(*bt)
        _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
        pipe.process(*bt)  # breaker_faults = 1 of 2
        for _ in range(2):  # streak ≥ threshold clears the count
            pipe.process(*bt)
        assert pipe.failsafe_state()["breaker_faults"] == 0
        _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
        pipe.process(*bt)  # 1 again — NOT 2: no descent
        assert pipe.pipeline_mode == "sharded"

    def test_host_mode_ct_world_parity(self):
        """Host fallback under the CT pipeline: device-CT selection is
        gated off and verdicts still match the level-0 path."""
        pipe, idents = self._trippy()
        ct, _ = _ct_world()
        ct.breaker_threshold = 2
        bt = _flows(idents)
        sports = np.arange(bt[0].shape[0], dtype=np.int32) + 2048
        ref_v, _ = ct.process(*bt, sports=sports)
        ct._set_level(2)
        assert ct.pipeline_mode == "host"
        v, _ = ct.process(*bt, sports=sports)
        np.testing.assert_array_equal(v, ref_v)


# ---------------------------------------------------------------------------
class TestFailPolicy:
    def test_reason_155_stable(self):
        assert REASON_PIPELINE_DEGRADED == 155
        assert DROP_DEGRADED == 5
        assert "degraded" in reason_name(REASON_PIPELINE_DEGRADED).lower()

    def test_fail_closed_counts_reason_155(self):
        pipe, idents = _world()
        bt = _flows(idents, b=64)
        pipe.process(*bt)
        before = _m.drop_reasons_total.get({"reason": "pipeline-degraded"})
        dd = _m.verdicts_total.get({"outcome": "dropped_degraded"})
        _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
        v, _ = pipe.process(*bt)
        assert (v == DROP_DEGRADED).all()
        assert _m.drop_reasons_total.get(
            {"reason": "pipeline-degraded"}
        ) == before + 64
        assert _m.verdicts_total.get(
            {"outcome": "dropped_degraded"}
        ) == dd + 64

    def test_fail_open_forwards(self):
        pipe, idents = _world()
        bt = _flows(idents, b=64)
        pipe.process(*bt)
        pipe.set_fail_open(True)
        before = _m.drop_reasons_total.get({"reason": "pipeline-degraded"})
        _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
        v, _ = pipe.process(*bt)
        assert (v == FORWARD).all()
        # fail-open emits no degraded-drop reasons
        assert _m.drop_reasons_total.get(
            {"reason": "pipeline-degraded"}
        ) == before

    def test_degraded_batch_never_touches_rule_hits(self):
        """rule_hits_total attributes DEVICE verdicts; a degraded batch
        has none — the invariant the dashboards rely on."""
        pipe, idents = _world()
        pipe.set_attribution(True)
        pipe.rebuild()
        bt = _flows(idents)
        pipe.process(*bt)
        hits = {
            k: v for k, v in _m.rule_hits_total._values.items()
        }
        _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
        v, _ = pipe.process(*bt)
        assert (v == DROP_DEGRADED).all()
        assert _m.rule_hits_total._values == hits

    def test_degraded_result_preserves_rev_nat_shape(self):
        ct, idents = _ct_world()
        bt = _flows(idents, b=48)
        sports = np.arange(48, dtype=np.int32) + 1024
        ct.process(*bt, sports=sports, return_rev_nat=True)
        _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
        out = ct.process(*bt, sports=sports, return_rev_nat=True)
        assert len(out) == 3
        v, red, rev = out
        assert v.shape == (48,) and red.shape == (48,)
        assert rev.shape == (48,) and rev.dtype == np.uint16


# ---------------------------------------------------------------------------
class TestOffPathParity:
    def test_off_path_bit_identical(self):
        """FaultInjection off (the default): verdicts, counters, and
        the compiled shape-key set match an untouched pipeline — the
        failsafe plumbing costs the OFF path nothing observable."""
        assert not _faults.hub.active
        pipe_a, idents = _world()
        pipe_b, _ = _world()
        batches = [_flows(idents, 300, seed=70 + i) for i in range(6)]
        for bt in batches:
            v_a, r_a = pipe_a.process(*bt)
            v_b, r_b = pipe_b.process(*bt)
            np.testing.assert_array_equal(v_a, v_b)
            np.testing.assert_array_equal(r_a, r_b)
        np.testing.assert_array_equal(pipe_a.counters, pipe_b.counters)
        assert pipe_a._seen_shapes == pipe_b._seen_shapes
        assert pipe_a.pipeline_mode == "sharded"
        assert pipe_a.failsafe_state()["excluded_devices"] == []

    def test_hub_enabled_but_quiet_is_transparent(self):
        """FaultInjection ON with no rules due: the checks run but
        nothing fires — verdicts and compiled shape keys unchanged."""
        pipe_a, idents = _world()
        pipe_b, _ = _world()
        bt = _flows(idents)
        v_a, r_a = pipe_a.process(*bt)
        _faults.hub.enable()
        v_b, r_b = pipe_b.process(*bt)
        np.testing.assert_array_equal(v_a, v_b)
        np.testing.assert_array_equal(r_a, r_b)
        assert pipe_a._seen_shapes == pipe_b._seen_shapes

    def test_recovered_pipeline_matches_untouched(self):
        """After a full degrade→recover cycle the pipeline's verdicts
        are bit-identical to one that never degraded."""
        pipe_a, idents = _world()
        pipe_a.breaker_threshold = 2
        pipe_a.recover_after_clean = 2
        pipe_b, _ = _world()
        bt = _flows(idents)
        pipe_a.process(*bt)
        for _ in range(4):
            _faults.hub.fail(_faults.SITE_COMPLETE, _faults.KIND_POISONED, 1)
            pipe_a.process(*bt)
        assert pipe_a.pipeline_mode == "host"
        rounds = 0
        while pipe_a.pipeline_mode != "sharded" and rounds < 32:
            pipe_a.process(*bt)
            rounds += 1
        _faults.hub.reset()
        for seed in (81, 82):
            bt2 = _flows(idents, 200, seed=seed)
            v_a, r_a = pipe_a.process(*bt2)
            v_b, r_b = pipe_b.process(*bt2)
            np.testing.assert_array_equal(v_a, v_b)
            np.testing.assert_array_equal(r_a, r_b)


# ---------------------------------------------------------------------------
class TestDaemonWiring:
    def test_options_status_and_traces(self, tmp_path):
        from cilium_tpu.daemon import Daemon

        d = Daemon(state_dir=str(tmp_path), conntrack=False)
        try:
            st = d.status()
            assert st["pipeline_mode"] == "sharded"
            assert st["pipeline_degraded"] is False
            fs = d.traces()["failsafe"]
            assert fs["mode"] == "sharded" and not fs["degraded"]
            assert fs["fail_open"] is False

            out = d.config_patch({"FailOpen": "true"})
            assert "FailOpen" in out["changed"]
            assert d.pipeline._fail_open is True
            d.config_patch({"FailOpen": "false"})
            assert d.pipeline._fail_open is False

            d.config_patch({"FaultInjection": "true"})
            assert _faults.hub.active
            assert d.traces()["failsafe"]["fault_injection"] is True
            d.config_patch({"FaultInjection": "false"})
            assert not _faults.hub.active
        finally:
            d.shutdown()

    def test_degraded_status_surfaces(self, tmp_path):
        from cilium_tpu.daemon import Daemon

        d = Daemon(state_dir=str(tmp_path), conntrack=False)
        try:
            d.pipeline._set_level(2)
            st = d.status()
            assert st["pipeline_mode"] == "host"
            assert st["pipeline_degraded"] is True
            assert d.traces()["failsafe"]["level"] == 2
        finally:
            d.shutdown()


# ---------------------------------------------------------------------------
class TestProxyHardening:
    def test_hpack_bomb_rejected(self):
        from cilium_tpu.proxy.hpack import (
            MAX_DECODED_HEADER_BYTES,
            HpackDecoder,
            HpackError,
            encode_int,
        )

        # one literal-with-indexing inserts a 4KB value into the
        # dynamic table; indexed references then re-emit it for ~16
        # wire bytes each — classic decompression bomb
        name, value = b"x-bomb", b"v" * 1024
        block = bytearray()
        block += encode_int(0, 6, 0x40)
        block += encode_int(len(name), 7) + name
        block += encode_int(len(value), 7) + value
        from cilium_tpu.proxy.hpack import STATIC_TABLE

        idx = len(STATIC_TABLE) + 1  # newest dynamic entry
        refs = MAX_DECODED_HEADER_BYTES // (len(name) + len(value)) + 2
        for _ in range(refs):
            block += encode_int(idx, 7, 0x80)
        with pytest.raises(HpackError, match="exceeds"):
            HpackDecoder().decode(bytes(block))
        # a normal block stays under the cap and decodes fine
        ok = bytearray()
        ok += encode_int(0, 4, 0x00)
        ok += encode_int(3, 7) + b"abc"
        ok += encode_int(3, 7) + b"def"
        assert HpackDecoder().decode(bytes(ok)) == [(b"abc", b"def")]

    def test_hpack_bomb_maps_to_compression_error(self):
        import threading

        from cilium_tpu.proxy.hpack import HpackEncoder, encode_int
        from cilium_tpu.proxy.http2 import (
            FLAG_END_HEADERS,
            FRAME_GOAWAY,
            FRAME_HEADERS,
            FRAME_SETTINGS,
            PREFACE,
            H2ServerConnection,
            pack_frame,
            read_frame,
        )
        from cilium_tpu.proxy.hpack import STATIC_TABLE

        s_cli, s_srv = socket.socketpair()
        s_cli.settimeout(10)
        conn = H2ServerConnection(s_srv, on_request=lambda c, st: None)
        t = threading.Thread(target=lambda: (conn.handshake(), conn.serve()))
        t.start()
        try:
            s_cli.sendall(PREFACE + pack_frame(FRAME_SETTINGS, 0, 0, b""))
            name, value = b"x-bomb", b"v" * 1024
            block = bytearray()
            block += encode_int(0, 6, 0x40)
            block += encode_int(len(name), 7) + name
            block += encode_int(len(value), 7) + value
            for _ in range(64):
                block += encode_int(len(STATIC_TABLE) + 1, 7, 0x80)
            s_cli.sendall(
                pack_frame(FRAME_HEADERS, FLAG_END_HEADERS, 1, bytes(block))
            )
            goaway_code = None
            while True:
                fr = read_frame(s_cli)
                if fr is None:
                    break
                ftype, _fl, _sid, payload = fr
                if ftype == FRAME_GOAWAY:
                    _last, goaway_code = struct.unpack(">II", payload)
                    break
            assert goaway_code == 0x9  # COMPRESSION_ERROR
        finally:
            s_cli.close()
            t.join(10)

    def test_huffman_padding_over_7_bits_rejected(self):
        from cilium_tpu.proxy.hpack import (
            HpackError,
            huffman_decode,
            huffman_encode,
        )

        enc = huffman_encode(b"abc")
        assert huffman_decode(enc) == b"abc"
        # a full extra byte of all-ones: still an EOS prefix, but ≥8
        # bits of padding — RFC 7541 §5.2 says decoding error
        with pytest.raises(HpackError, match="8 or more"):
            huffman_decode(enc + b"\xff")
        # a zero bit in padding is the OTHER error class: 'a' is the
        # 5-bit code 00011, so 0x1f is valid (111 padding) and 0x1e
        # (110 padding) is not
        assert huffman_decode(b"\x1f") == b"a"
        with pytest.raises(HpackError, match="0 bits"):
            huffman_decode(b"\x1e")

    def test_excess_streams_refused_but_hpack_state_kept(self):
        import threading

        from cilium_tpu.proxy.hpack import HpackEncoder
        from cilium_tpu.proxy.http2 import (
            ERR_REFUSED_STREAM,
            FLAG_END_HEADERS,
            FRAME_HEADERS,
            FRAME_RST_STREAM,
            FRAME_SETTINGS,
            MAX_CONCURRENT_STREAMS,
            PREFACE,
            H2ServerConnection,
            pack_frame,
            read_frame,
        )

        s_cli, s_srv = socket.socketpair()
        s_cli.settimeout(10)
        conn = H2ServerConnection(s_srv, on_request=lambda c, st: None)
        t = threading.Thread(target=lambda: (conn.handshake(), conn.serve()))
        t.start()
        try:
            s_cli.sendall(PREFACE + pack_frame(FRAME_SETTINGS, 0, 0, b""))
            enc = HpackEncoder()
            fields = [
                (b":method", b"GET"), (b":scheme", b"http"),
                (b":path", b"/"), (b":authority", b"svc"),
            ]
            # open the advertised maximum (no END_STREAM → stay open)
            for i in range(MAX_CONCURRENT_STREAMS + 1):
                sid = 1 + 2 * i
                s_cli.sendall(pack_frame(
                    FRAME_HEADERS, FLAG_END_HEADERS, sid,
                    enc.encode(fields),
                ))
            rst = None
            while rst is None:
                fr = read_frame(s_cli)
                assert fr is not None, "server closed before RST_STREAM"
                ftype, _fl, sid, payload = fr
                if ftype == FRAME_RST_STREAM:
                    (code,) = struct.unpack(">I", payload)
                    rst = (sid, code)
            assert rst == (
                1 + 2 * MAX_CONCURRENT_STREAMS, ERR_REFUSED_STREAM
            )
            assert len(conn.streams) == MAX_CONCURRENT_STREAMS
            # the refused stream's block was still decoded: HPACK
            # state stays in sync for the NEXT stream (this would
            # desync and kill the connection otherwise)
        finally:
            s_cli.close()
            conn.close()
            t.join(10)

    def test_client_short_priority_block_rejected(self):
        from cilium_tpu.proxy.http2 import (
            FLAG_END_HEADERS,
            FLAG_PRIORITY,
            FRAME_HEADERS,
            H2ClientConnection,
            H2Error,
        )

        s_a, s_b = socket.socketpair()
        try:
            conn = H2ClientConnection(s_a)
            with pytest.raises(H2Error, match="priority"):
                conn._handle((
                    FRAME_HEADERS, FLAG_END_HEADERS | FLAG_PRIORITY, 1,
                    b"\x00\x00\x00",  # < 5 bytes of priority block
                ))
        finally:
            s_a.close()
            s_b.close()


# ---------------------------------------------------------------------------
class TestLintRule:
    def test_robust001_flags_and_exempts(self, tmp_path):
        from cilium_tpu.analysis.core import ModuleSource
        from cilium_tpu.analysis.hotpath import analyze_hotpath

        src = (
            "# policyd: hot\n"
            "def a():\n"
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        pass\n"
            "def b():\n"
            "    try:\n"
            "        work()\n"
            "    except Exception as e:\n"
            "        if faults.classify(e) == 'error':\n"
            "            raise\n"
            "        log(e)\n"
            "def c():\n"
            "    try:\n"
            "        work()\n"
            "    except (ValueError, KeyError):\n"
            "        pass\n"
            "def d():\n"
            "    try:\n"
            "        work()\n"
            "    except BaseException:\n"
            "        raise\n"
        )
        p = tmp_path / "hotmod.py"
        p.write_text(src)
        mod = ModuleSource(str(p))
        assert mod.is_hot()
        found = [
            f for f in analyze_hotpath(mod) if f.rule == "ROBUST001"
        ]
        assert len(found) == 1
        assert found[0].line == 5  # only a(): b/c/d are exempt

    def test_shipped_hot_modules_are_clean(self):
        """The PR's own hot-path code must satisfy its own rule."""
        from cilium_tpu.analysis import analyze_paths
        from cilium_tpu.analysis.baseline import (
            default_baseline_path, load_baseline, new_findings,
        )
        from cilium_tpu.analysis import default_target

        counts, _ = load_baseline(default_baseline_path())
        fresh = new_findings(analyze_paths([default_target()]), counts)
        assert [f for f in fresh if f.rule == "ROBUST001"] == []
