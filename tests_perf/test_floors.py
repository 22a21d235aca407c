"""Order-of-magnitude perf floors over the bench's own building blocks.
Each floor sits ~5-10x under a chip number measured before PR 1 (that
record is deleted and the number is not measured on this tree), so
real regressions fail here while environment jitter passes.

Device floors skip off-accelerator (the CPU backend is not the
measured regime); host floors (Kafka ACL, native C++ front-end) run
anywhere but scale with the host, hence the wide margins.
"""

from __future__ import annotations

import os
import random
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

import bench
from bench import N_ENDPOINTS, build_world

pytestmark = pytest.mark.perf


@pytest.fixture(scope="module")
def world():
    """The bench's 10k-rule world — floors must measure the same
    in-world regime the driver records (see the bench-measurement
    note in bench.py history: empty-process rates are not comparable)."""
    rng = random.Random(42)
    repo, reg, idents = build_world(rng)
    from cilium_tpu.engine import PolicyEngine
    from cilium_tpu.ops.materialize import materialize_endpoints

    engine = PolicyEngine(repo, reg)
    compiled = engine.refresh()
    jax.block_until_ready(engine.device_policy.sel_match)
    ep_ids = [idents[i].id for i in range(N_ENDPOINTS)]
    tables, snaps = materialize_endpoints(
        compiled, engine.device_policy, ep_ids, ingress=True
    )
    jax.block_until_ready(tables.id_bits)
    return repo, reg, idents, engine, compiled, tables, snaps


def _rate(fn, n, iters=5):
    jax.block_until_ready(fn())
    t0 = time.time()
    r = None
    for _ in range(iters):
        r = fn()
    jax.block_until_ready(r)
    return iters * n / (time.time() - t0)


class TestDeviceFloors:
    def test_verdict_lookup_floor(self, world, on_accelerator):
        """Policymap lookup ≥ 10M verdicts/s (r04: 131.9M)."""
        if not on_accelerator:
            pytest.skip("device floor: accelerator regime only")
        from cilium_tpu.ops.lookup import lookup_batch

        _repo, _reg, idents, engine, compiled, tables, _ = world
        nrng = np.random.default_rng(7)
        b = 1 << 20
        rows = np.array(
            [compiled.id_to_row[i.id] for i in idents], np.int32
        )
        ep = jnp.asarray(nrng.integers(0, N_ENDPOINTS, b, dtype=np.int32))
        src = jnp.asarray(nrng.choice(rows, b).astype(np.int32))
        dport = jnp.asarray(
            nrng.choice(np.array([80, 443, 0], np.int32), b)
        )
        proto = jnp.asarray(np.full(b, 6, np.int32))
        rate = _rate(
            lambda: lookup_batch(tables, ep, src, dport, proto)[0], b
        )
        assert rate >= 10e6, f"verdict floor: {rate/1e6:.1f}M/s < 10M/s"

    def test_lpm_floor(self, world, on_accelerator):
        """50k-prefix LPM ≥ 2M lookups/s (r04: 22M)."""
        if not on_accelerator:
            pytest.skip("device floor: accelerator regime only")
        scattered, _clustered = bench._bench_lpm_50k(
            np.random.default_rng(3)
        )
        assert scattered >= 2e6, f"LPM floor: {scattered/1e6:.1f}M/s < 2M/s"

    def test_pipeline_floor(self, world, on_accelerator):
        """Full datapath chain ≥ 3M flows/s (r04: 27.8M)."""
        if not on_accelerator:
            pytest.skip("device floor: accelerator regime only")
        repo, reg, idents, *_ = world
        v4, _v6, pf = bench._bench_pipeline_e2e(
            repo, reg, idents, np.random.default_rng(13)
        )
        assert v4 >= 3e6, f"pipeline floor: {v4/1e6:.1f}M/s < 3M/s"
        # the fused deny+identity walk must exist (pf > 0) and not be
        # slower than half the deny-skipped chain
        assert pf >= v4 / 2, f"fused-prefilter floor: {pf/1e6:.1f}M/s"

    def test_device_ct_floor(self, world, on_accelerator):
        """Fused device-CT datapath step ≥ 1M flows/s."""
        if not on_accelerator:
            pytest.skip("device floor: accelerator regime only")
        from cilium_tpu.datapath.pipeline import (
            TRAFFIC_INGRESS,
            DatapathPipeline,
        )
        from cilium_tpu.ipcache.ipcache import IPCache
        from cilium_tpu.ipcache.prefilter import PreFilter

        repo, reg, idents, engine, *_ = world
        cache = IPCache()
        for i, ident in enumerate(idents):
            cache.upsert(
                f"10.{(i >> 8) & 255}.{i & 255}.1/32", ident.id,
                source="k8s",
            )
        pipe = DatapathPipeline(
            engine, cache, PreFilter(), conntrack=None, device_ct_bits=20
        )
        pipe.set_endpoints([idents[j].id for j in range(N_ENDPOINTS)])
        nrng = np.random.default_rng(11)
        b = 1 << 18
        i_sel = nrng.integers(0, len(idents), b)
        ips = (
            np.uint32(10) << 24
            | ((i_sel >> 8) & 255).astype(np.uint32) << 16
            | (i_sel & 255).astype(np.uint32) << 8
            | 1
        ).astype(np.uint32)
        eps = nrng.integers(0, N_ENDPOINTS, b).astype(np.int32)
        dports = nrng.choice(np.array([80, 443, 53], np.int32), b)
        protos = np.where(dports == 53, 17, 6).astype(np.int32)
        sports = nrng.integers(1024, 60000, b).astype(np.int32)
        pipe.process(ips, eps, dports, protos, sports=sports)  # warm
        t0 = time.time()
        iters = 5
        for _ in range(iters):
            pipe.process(ips, eps, dports, protos, sports=sports)
        rate = iters * b / (time.time() - t0)
        assert rate >= 1e6, f"device-CT floor: {rate/1e6:.1f}M/s < 1M/s"


class TestHostFloors:
    def test_kafka_acl_floor(self):
        """Kafka ACL batch check ≥ 50k req/s on one host core
        (r04: 400k on 1 cpu; r03: 945k)."""
        rate = bench._bench_kafka_acl()
        assert rate >= 50e3, f"kafka floor: {rate/1e3:.0f}k/s < 50k/s"

    def test_native_verdict_floor(self, world):
        """Native C++ front-end ≥ 500k verdicts/s (r04: 6.2M)."""
        from cilium_tpu.native import native_available

        if not native_available():
            pytest.skip("native front-end not built")
        _repo, _reg, idents, _e, _c, _t, snaps = world
        single, _mt = bench._bench_native(
            snaps, idents, np.random.default_rng(5)
        )
        assert single >= 500e3, f"native floor: {single/1e3:.0f}k/s < 500k/s"

    def test_native_l7_floor(self):
        """Native L7 HTTP DFA ≥ 1M req/s (r04: 28.2M)."""
        from cilium_tpu.native import native_available

        if not native_available():
            pytest.skip("native front-end not built")
        rate = bench._bench_native_l7()
        assert rate >= 1e6, f"native L7 floor: {rate/1e6:.1f}M/s < 1M/s"
