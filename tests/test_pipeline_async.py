"""Overlapped verdict dispatch parity: the bounded in-flight queue
(submit/result, depth > 1) and VerdictSharding flow sharding must both
produce bit-identical verdicts/redirects/counters to the synchronous
single-device path. Runs on the virtual 8-device CPU mesh from
conftest.py.
"""

from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from __graft_entry__ import _build_datapath_world, _make_ip_flows

from cilium_tpu.datapath.conntrack import FlowConntrack
from cilium_tpu.datapath.pipeline import DatapathPipeline
from cilium_tpu.observe import tracer as tracer_mod


def _batches(idents, k: int, b: int, seed0: int):
    return [_make_ip_flows(idents, b, seed=seed0 + i) for i in range(k)]


def _ct_world(seed: int = 3, depth: int = 1):
    """_build_datapath_world, but with a host conntrack attached (the
    CT pre-pass + ct_create completion path)."""
    pipe, engine, idents = _build_datapath_world(seed=seed)
    ct_pipe = DatapathPipeline(
        engine, pipe.ipcache, pipe.prefilter,
        conntrack=FlowConntrack(capacity_bits=12),
        pipeline_depth=depth,
    )
    ct_pipe.set_endpoints([i.id for i in idents[:4]])
    ct_pipe.rebuild()
    return ct_pipe, idents


class TestPipelinedParity:
    def test_depth_pipelined_matches_sync(self):
        """N batches submitted back-to-back at depth 3 vs the same
        batches processed synchronously on a fresh pipeline."""
        pipe_a, _, idents = _build_datapath_world(seed=3)
        pipe_a.pipeline_depth = 3
        pipe_b, _, _ = _build_datapath_world(seed=3)
        batches = _batches(idents, 6, 384, seed0=40)

        pend = [
            pipe_a.submit(p, e, d, pr) for (p, e, d, pr) in batches
        ]
        assert pipe_a.inflight_depth <= pipe_a.pipeline_depth
        got = [pb.result() for pb in pend]
        assert pipe_a.inflight_depth == 0

        for (p, e, d, pr), (v_a, red_a) in zip(batches, got):
            v_b, red_b = pipe_b.process(p, e, d, pr)
            np.testing.assert_array_equal(v_a, v_b)
            np.testing.assert_array_equal(red_a, red_b)
        np.testing.assert_array_equal(pipe_a.counters, pipe_b.counters)

    def test_result_is_idempotent_and_fifo(self):
        pipe, _, idents = _build_datapath_world(seed=3)
        pipe.pipeline_depth = 4
        batches = _batches(idents, 3, 256, seed0=90)
        pend = [pipe.submit(p, e, d, pr) for (p, e, d, pr) in batches]
        # resolving the NEWEST first must complete the older ones too
        # (FIFO: events/counters land in submission order)
        v_last, _ = pend[-1].result()
        assert all(pb.done for pb in pend)
        v_again, _ = pend[-1].result()
        np.testing.assert_array_equal(v_last, v_again)

    def test_ct_pipelined_matches_sync(self):
        """CT pre-pass path at depth 2 (ct_create deferred to the
        completion half) vs fully synchronous, repeated flows included
        so later batches mix CT hits and misses."""
        pipe_a, idents = _ct_world(depth=2)
        pipe_b, _ = _ct_world(depth=1)
        rng = np.random.default_rng(7)
        batches = _batches(idents, 5, 300, seed0=60)
        sports = [
            rng.integers(1024, 4096, 300).astype(np.int32)
            for _ in batches
        ]
        # replay batch 0 at the end: by then its allowed flows are
        # established entries on both pipelines
        batches.append(batches[0])
        sports.append(sports[0])

        pend = [
            pipe_a.submit(p, e, d, pr, sports=sp)
            for (p, e, d, pr), sp in zip(batches, sports)
        ]
        got = [pb.result() for pb in pend]
        for (p, e, d, pr), sp, (v_a, red_a) in zip(batches, sports, got):
            v_b, red_b = pipe_b.process(p, e, d, pr, sports=sp)
            np.testing.assert_array_equal(v_a, v_b)
            np.testing.assert_array_equal(red_a, red_b)
        np.testing.assert_array_equal(pipe_a.counters, pipe_b.counters)
        assert len(pipe_a.conntrack) == len(pipe_b.conntrack)

    def test_drain_completes_everything(self):
        pipe, _, idents = _build_datapath_world(seed=3)
        pipe.pipeline_depth = 8
        pend = [
            pipe.submit(p, e, d, pr)
            for (p, e, d, pr) in _batches(idents, 4, 128, seed0=70)
        ]
        assert pipe.inflight_depth > 0
        pipe.drain()
        assert pipe.inflight_depth == 0
        assert all(pb.done for pb in pend)


class TestWarmBucketChunking:
    def test_oversize_batch_chunks_into_warm_buckets(self):
        """A CT-miss tail must decompose over the fixed bucket ladder:
        3000 flows dispatch as 2048 + 1024 (3072 lanes, two chunks) —
        fewer enqueues than the old 3×1024 largest-warm-bucket reuse
        and 1024 lanes less pad than a single 4096 bucket."""
        pipe, idents = _ct_world()
        rng = np.random.default_rng(11)
        warm = _make_ip_flows(idents, 700, seed=80)
        pipe.process(*warm, sports=rng.integers(1024, 4096, 700).astype(np.int32))
        assert pipe._warm_buckets == {1024}

        pipe.tracer.enable()
        big = _make_ip_flows(idents, 3000, seed=81)
        v_a, red_a = pipe.process(
            *big, sports=rng.integers(8192, 16384, 3000).astype(np.int32)
        )
        pipe.tracer.disable()
        assert pipe._warm_buckets == {1024, 2048}  # no 4096 compile
        (t,) = pipe.tracer.traces(1)
        assert t["notes"]["chunks"] == 2
        assert t["notes"]["padded"] == 3072

        fresh, _ = _ct_world()
        v_b, red_b = fresh.process(
            *big, sports=rng.integers(8192, 16384, 3000).astype(np.int32)
        )
        np.testing.assert_array_equal(v_a, v_b)
        np.testing.assert_array_equal(red_a, red_b)


class TestShardedParity:
    @pytest.fixture(autouse=True)
    def _need_devices(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device for VerdictSharding")

    @pytest.mark.parametrize("b", [512, 509])
    def test_sharded_matches_single_device(self, b):
        """Flow-sharded dispatch (tables replicated, batch split over
        the mesh) vs the unsharded path — even and odd batch sizes (odd
        forces pad-to-multiple-of-ndev, host-side counters)."""
        pipe_s, _, idents = _build_datapath_world(seed=3)
        pipe_s.set_sharding(True)
        pipe_s.rebuild()
        assert pipe_s._mesh is not None
        pipe_u, _, _ = _build_datapath_world(seed=3)

        for seed in (20, 21):
            p, e, d, pr = _make_ip_flows(idents, b, seed=seed)
            v_s, red_s = pipe_s.process(p, e, d, pr)
            v_u, red_u = pipe_u.process(p, e, d, pr)
            np.testing.assert_array_equal(v_s, v_u)
            np.testing.assert_array_equal(red_s, red_u)
        np.testing.assert_array_equal(pipe_s.counters, pipe_u.counters)

    def test_sharded_ct_pipelined_matches_sync(self):
        """Sharding + depth-2 pipelining + CT pre-pass together."""
        pipe_s, idents = _ct_world(depth=2)
        pipe_s.set_sharding(True)
        pipe_s.rebuild()
        pipe_u, _ = _ct_world(depth=1)
        rng = np.random.default_rng(5)
        batches = _batches(idents, 4, 250, seed0=30)
        sports = [
            rng.integers(1024, 4096, 250).astype(np.int32) for _ in batches
        ]
        pend = [
            pipe_s.submit(p, e, d, pr, sports=sp)
            for (p, e, d, pr), sp in zip(batches, sports)
        ]
        got = [pb.result() for pb in pend]
        for (p, e, d, pr), sp, (v_s, red_s) in zip(batches, sports, got):
            v_u, red_u = pipe_u.process(p, e, d, pr, sports=sp)
            np.testing.assert_array_equal(v_s, v_u)
            np.testing.assert_array_equal(red_s, red_u)
        np.testing.assert_array_equal(pipe_s.counters, pipe_u.counters)

    def test_sharding_toggles_off(self):
        pipe, _, idents = _build_datapath_world(seed=3)
        pipe.set_sharding(True)
        pipe.rebuild()
        assert pipe._mesh is not None
        pipe.set_sharding(False)
        pipe.rebuild()
        assert pipe._mesh is None
        p, e, d, pr = _make_ip_flows(idents, 128, seed=1)
        pipe.process(p, e, d, pr)  # still dispatches


class TestTracesUnderOverlap:
    def test_trace_attaches_to_completing_batch(self):
        """With two batches in flight the spans recorded at completion
        (host_sync/counters/emit_events) must land on the trace of the
        batch being COMPLETED, not the one being prepared, and the
        thread-local span stack must end clean."""
        pipe, _, idents = _build_datapath_world(seed=3)
        pipe.pipeline_depth = 2
        pipe.tracer.enable()
        b1 = _make_ip_flows(idents, 200, seed=50)
        b2 = _make_ip_flows(idents, 100, seed=51)
        p1 = pipe.submit(*b1)
        p2 = pipe.submit(*b2)
        assert pipe.inflight_depth == 2
        p2.result()  # FIFO: completes batch 1 then batch 2
        assert p1.done
        pipe.tracer.disable()
        # TLS span stack must end clean (current() falls back to the
        # no-op singleton only when nothing is left open)
        assert not getattr(tracer_mod._TLS, "stack", None)

        t1, t2 = pipe.tracer.traces(2)  # oldest→newest = completion order
        assert t1["batch"] == 200 and t2["batch"] == 100
        for t in (t1, t2):
            names = [ph[0] for ph in t["phases"]]  # [name, t0, dur]
            assert "dispatch" in names and "host_sync" in names
            # enqueue-half phases precede completion-half phases
            assert names.index("dispatch") < names.index("host_sync")


class TestDaemonWiring:
    def test_verdict_sharding_option_and_traces_depth(self, tmp_path):
        from cilium_tpu.daemon import Daemon

        d = Daemon(state_dir=str(tmp_path), conntrack=False)
        try:
            out = d.config_patch({"VerdictSharding": "true"})
            assert "VerdictSharding" in out["changed"]
            assert d.pipeline._sharding_requested
            d.config_patch({"VerdictSharding": "false"})
            assert not d.pipeline._sharding_requested
            out = d.traces()
            assert out["pipeline_depth"] == d.pipeline.pipeline_depth
            assert out["in_flight"] == 0
        finally:
            d.shutdown()
