"""policyd-prof: device-time profiler cost contract, Histogram
quantiles, registry concurrency, new-family exposition, the
/profile + `cilium-tpu top` surfaces.

The acceptance contract (ISSUE 13): disabled profiling costs one
attribute read per batch (the exact pre-option programs); sampled
batches decompose dispatch RTT into h2d/device_compute/d2h with rung
occupancy notes.
"""

from __future__ import annotations

import json
import os
import tarfile
import threading

import numpy as np
import pytest

from cilium_tpu import metrics
from cilium_tpu.datapath.pipeline import DatapathPipeline
from cilium_tpu.engine import PolicyEngine
from cilium_tpu.identity import IdentityRegistry
from cilium_tpu.ipcache.ipcache import IPCache
from cilium_tpu.ipcache.prefilter import PreFilter
from cilium_tpu.labels import parse_label_array
from cilium_tpu.observe import profiler as profiler_mod
from cilium_tpu.ops.lpm import ip_strings_to_u32
from cilium_tpu.policy.api import (
    EndpointSelector,
    IngressRule,
    PortProtocol,
    PortRule,
    rule,
)
from cilium_tpu.policy.repository import Repository


def _pipeline():
    repo = Repository()
    repo.add_list([
        rule(
            ["k8s:app=web"],
            ingress=[IngressRule(
                from_endpoints=(EndpointSelector.make(["k8s:app=lb"]),),
                to_ports=(PortRule(ports=(PortProtocol(80, "TCP"),)),),
            )],
            labels=["k8s:policy=prof"],
        ),
    ])
    reg = IdentityRegistry()
    web = reg.allocate(parse_label_array(["k8s:app=web"]))
    lb = reg.allocate(parse_label_array(["k8s:app=lb"]))
    cache = IPCache()
    cache.upsert("10.0.0.2/32", lb.id, source="k8s")
    pipe = DatapathPipeline(PolicyEngine(repo, reg), cache, PreFilter())
    pipe.set_endpoints([(7, web.id)])
    return pipe


def _batch(n=8):
    return (
        ip_strings_to_u32(["10.0.0.2"] * n),
        np.zeros(n, np.int32),
        np.full(n, 80),
        np.full(n, 6),
    )


# --------------------------------------------------- Histogram.quantile


class TestHistogramQuantile:
    def _hist(self):
        h = metrics.Histogram("t_prof_q", "help", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 3.5):
            h.observe(v)
        return h

    def test_interpolates_within_landing_bucket(self):
        h = self._hist()
        # rank 2 lands at the end of the (1, 2] bucket
        assert h.quantile(0.5) == pytest.approx(2.0)
        # rank 4 exhausts the (2, 4] bucket
        assert h.quantile(1.0) == pytest.approx(4.0)
        # rank 1 exhausts the first bucket, interpolated from 0
        assert h.quantile(0.25) == pytest.approx(1.0)

    def test_unobserved_series_is_none(self):
        h = metrics.Histogram("t_prof_q2", "help", buckets=(1.0,))
        assert h.quantile(0.5) is None
        assert h.quantile(0.5, {"phase": "ghost"}) is None

    def test_overflow_clamps_to_last_finite_bucket(self):
        h = metrics.Histogram("t_prof_q3", "help", buckets=(1.0, 4.0))
        h.observe(100.0)
        # +Inf has no upper edge to interpolate to
        assert h.quantile(0.5) == 4.0

    def test_label_series_are_independent(self):
        h = metrics.Histogram("t_prof_q4", "help", buckets=(1.0, 2.0))
        h.observe(0.5, {"phase": "a"})
        h.observe(1.5, {"phase": "b"})
        assert h.quantile(1.0, {"phase": "a"}) == pytest.approx(1.0)
        assert h.quantile(1.0, {"phase": "b"}) == pytest.approx(2.0)
        assert h.quantile(1.0) is None  # unlabeled series unobserved

    def test_rejects_out_of_range_q(self):
        h = self._hist()
        for q in (0.0, -0.1, 1.5):
            with pytest.raises(ValueError):
                h.quantile(q)


# ------------------------------------------------- registry concurrency


class TestRegistryConcurrency:
    def test_concurrent_inc_observe_and_expose(self):
        """Incs on FRESH label sets racing expose() must neither crash
        (dict-mutated-during-iteration) nor lose counts."""
        reg = metrics.Registry()
        c = reg.counter("t_conc_total", "h")
        h = reg.histogram("t_conc_seconds", "h", buckets=(0.5, 1.0))
        errs = []
        n_workers, n_iter = 4, 200

        def work(w):
            try:
                for j in range(n_iter):
                    c.inc({"w": str(w), "j": str(j % 7)})
                    h.observe(0.25, {"w": str(w)})
            except Exception as e:  # pragma: no cover
                errs.append(e)

        def scrape():
            try:
                for _ in range(50):
                    text = reg.expose()
                    assert "t_conc_total" in text
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(n_workers)]
        threads += [threading.Thread(target=scrape) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        assert sum(c.series().values()) == n_workers * n_iter
        for w in range(n_workers):
            assert h.get_count({"w": str(w)}) == n_iter


# ------------------------------------------- new families on /metrics


class TestNewFamilyExposition:
    def test_profile_ledger_families_expose(self):
        metrics.profile_samples_total.inc({"site": "t-expo"})
        metrics.profile_phase_seconds.observe(0.002, {"phase": "t-expo"})
        metrics.device_table_bytes.set(
            4096.0, {"family": "t-expo", "placement": "replicated"})
        metrics.device_transfer_bytes_total.inc(
            {"direction": "t-expo"}, 512.0)
        text = metrics.registry.expose()
        assert 'cilium_tpu_profile_samples_total{site="t-expo"} 1.0' in text
        assert ('cilium_tpu_profile_phase_seconds_bucket'
                '{phase="t-expo",le="+Inf"} 1') in text
        assert 'cilium_tpu_profile_phase_seconds_count{phase="t-expo"} 1' in text
        assert ('cilium_tpu_device_table_bytes'
                '{family="t-expo",placement="replicated"} 4096.0') in text
        assert ('cilium_tpu_device_transfer_bytes_total'
                '{direction="t-expo"} 512.0') in text
        # TYPE lines: the ledger gauge really is a gauge
        assert "# TYPE cilium_tpu_device_table_bytes gauge" in text
        assert ("# TYPE cilium_tpu_device_transfer_bytes_total counter"
                in text)


# ------------------------------------------------- cost contract (off)


class TestDisabledOverhead:
    def test_off_builds_no_profiler_objects(self, monkeypatch):
        """With DeviceProfiling off the pipeline holds profiler=None —
        a batch must construct neither a DeviceProfiler nor a
        _DispatchSample (the one-attribute-read contract)."""
        pipe = _pipeline()
        assert pipe.profiler is None

        class _Boom:
            def __init__(self, *a, **k):
                raise AssertionError("profiler object built while off")

        monkeypatch.setattr(profiler_mod, "DeviceProfiler", _Boom)
        monkeypatch.setattr(profiler_mod, "_DispatchSample", _Boom)
        v, red = pipe.process(*_batch())
        assert (v == 1).all()
        assert pipe.profiler is None

    def test_on_unsampled_builds_no_sample(self, monkeypatch):
        """While on, the N-1 unsampled batches pay one counter tick —
        never a _DispatchSample construction."""
        pipe = _pipeline()
        pipe.set_profiling(True, sample_every=10 ** 6)

        class _Boom:
            def __init__(self, *a, **k):
                raise AssertionError("sample built on unsampled batch")

        monkeypatch.setattr(profiler_mod, "_DispatchSample", _Boom)
        for _ in range(3):
            v, _ = pipe.process(*_batch())
            assert (v == 1).all()
        assert pipe.profiler.samples() == []

    def test_off_path_program_unchanged(self):
        """A pipeline that had profiling toggled on and back off traces
        the exact phase set (and verdicts) of one that never profiled —
        the off path runs the pre-option programs."""
        a, b = _pipeline(), _pipeline()
        b.set_profiling(True, sample_every=1)
        b.process(*_batch())  # one sampled batch
        b.set_profiling(False)
        a.tracer.enable()
        b.tracer.enable()
        for _ in range(2):
            va, _ = a.process(*_batch())
            vb, _ = b.process(*_batch())
            np.testing.assert_array_equal(va, vb)
        names_a = {p[0] for t in a.tracer.traces() for p in t["phases"]}
        names_b = {p[0] for t in b.tracer.traces() for p in t["phases"]}
        assert names_a == names_b


# ------------------------------------------------- sampled path (on)


class TestSampledPath:
    def test_sampled_verdicts_identical_and_decomposed(self):
        """sample_every=1: every batch pays the sandwiches, verdicts
        stay bit-identical, and each sample carries the RTT split plus
        rung-occupancy notes."""
        plain, prof = _pipeline(), _pipeline()
        prof.set_profiling(True, sample_every=1)
        n0 = metrics.profile_samples_total.get({"site": "dispatch"})
        for n in (8, 16):
            vp, rp = plain.process(*_batch(n))
            vq, rq = prof.process(*_batch(n))
            np.testing.assert_array_equal(vp, vq)
            np.testing.assert_array_equal(rp, rq)
        samples = prof.profiler.samples()
        assert len(samples) == 2
        for s in samples:
            assert s["site"] == "dispatch"
            assert s["h2d_ms"] >= 0.0
            assert s["device_compute_ms"] > 0.0
            assert s["d2h_ms"] >= 0.0
            notes = s["notes"]
            assert notes["lanes"] in (8, 16)
            assert notes["chunks"] >= 1
            assert len(notes["rungs"]) == notes["chunks"]
            assert notes["pad_lanes"] >= 0
            assert notes["ndev"] >= 1
        assert metrics.profile_samples_total.get(
            {"site": "dispatch"}) == n0 + 2

    def test_jit_cost_ledger_keyed_by_site_and_shape(self):
        pipe = _pipeline()
        pipe.set_profiling(True, sample_every=1)
        pipe.process(*_batch())
        pipe.process(*_batch())  # same ladder shape: no second entry
        costs = pipe.profiler.jit_costs()
        assert costs
        assert all(k.startswith("dispatch:") for k in costs)
        assert all(
            set(v) == {"flops", "bytes_accessed"} for v in costs.values()
        )
        # stable shape → exactly one ledger entry for the repeat batch
        assert len(costs) == 1

    def test_device_table_bytes_published_at_rebuild(self):
        pipe = _pipeline()
        pipe.process(*_batch())  # forces the first rebuild
        series = metrics.device_table_bytes.series()
        fams = {dict(k).get("family") for k in series}
        assert "policymap" in fams
        assert all(v >= 0 for v in series.values())

    def test_snapshot_aggregates_per_site(self):
        pipe = _pipeline()
        pipe.set_profiling(True, sample_every=1)
        pipe.process(*_batch())
        snap = pipe.profile_state()
        assert snap["enabled"] is True
        assert snap["sample_every"] == 1
        agg = snap["sites"]["dispatch"]
        assert agg["samples"] == 1
        assert agg["device_compute_ms"] > 0.0
        # toggling off returns the one-attribute-read state
        pipe.set_profiling(False)
        assert pipe.profile_state() == {
            "enabled": False, "sample_every": 1,
        }

    def test_reenable_retunes_live_sample_rate(self):
        """set_profiling(True, sample_every=N) on an ALREADY-on
        profiler must retune the live instance, not just the config."""
        pipe = _pipeline()
        pipe.set_profiling(True, sample_every=64)
        pipe.set_profiling(True, sample_every=1)
        assert pipe.profiler.sample_every == 1
        pipe.process(*_batch())
        assert len(pipe.profiler.samples()) == 1


# --------------------------------------------------------- surfaces


class TestSurfaces:
    def test_daemon_profile_and_option_toggle(self):
        from cilium_tpu.daemon import Daemon

        d = Daemon()
        try:
            out = d.profile()
            assert out["enabled"] is False
            assert out["sample_every"] == 64
            assert "device_table_bytes" in out
            assert set(out["device_transfers"]) == {"counts", "bytes"}
            d.config_patch({"DeviceProfiling": True})
            assert d.pipeline.profiler is not None
            d.pipeline.process(*_batch())
            out = d.profile()
            assert out["enabled"] is True
            assert {"sites", "samples", "jit_costs"} <= set(out)
            d.config_patch({"DeviceProfiling": False})
            assert d.pipeline.profiler is None
        finally:
            d.shutdown()

    def test_rest_profile_roundtrip(self, tmp_path):
        from cilium_tpu.api import APIClient, APIServer
        from cilium_tpu.daemon import Daemon

        d = Daemon()
        sock = str(tmp_path / "api.sock")
        srv = None
        try:
            from cilium_tpu.api.server import APIServer as _S

            srv = _S(d, sock)
            srv.start()
            cli = APIClient(sock)
            out = cli.profile_get()
            assert out["enabled"] is False
            assert "device_transfers" in out
        finally:
            if srv is not None:
                srv.stop()
            d.shutdown()

    def test_cli_top_subcommand_parses(self):
        from cilium_tpu.cli import build_parser

        args = build_parser().parse_args(["top"])
        assert args.cmd == "top"
        args = build_parser().parse_args(["top", "--json"])
        assert args.json is True

    def test_bugtool_bundle_carries_profile_and_exposition(self, tmp_path):
        from cilium_tpu.bugtool import collect_debuginfo, write_archive
        from cilium_tpu.daemon import Daemon

        d = Daemon()
        try:
            info = collect_debuginfo(d)
            assert info["profile"]["enabled"] is False
            assert "cilium_tpu_" in info["metrics"]
            path = write_archive(d, str(tmp_path / "bundle.tar.gz"))
            with tarfile.open(path) as tar:
                by_base = {os.path.basename(n): n for n in tar.getnames()}
                assert "profile.json" in by_base
                assert "metrics.prom" in by_base
                raw = tar.extractfile(
                    by_base["metrics.prom"]).read().decode()
                assert "cilium_tpu_" in raw
                prof = json.loads(tar.extractfile(
                    by_base["profile.json"]).read().decode())
                assert prof["enabled"] is False
        finally:
            d.shutdown()
