"""The packed verdict chunk: one int32 [rows, lanes] buffer up, one int32
result vector down (``pack_flow_chunk`` → ``process_flows_wide_packed`` /
``process_flows_packed`` → ``unpack_flow_result``).

- The packed entries are bit-identical to the per-array kernels on every
  lane, pad lanes included: verdict, redirect, counters and attribution,
  v4 and v6, ingress and egress, prefilter on and off, with and without
  an overlay row_override (trusted rows and -1 lanes).
- ``DatapathPipeline`` dispatches (padded, unpadded and multi-chunk
  batches, one device and the 2x2 flows x ident plan on four CPU
  devices) answer as the per-array kernel does over the whole batch.
- ``cilium_tpu_device_transfers_total`` counts what a traced dispatch
  moves: one upload a chunk per mesh device, one pull a chunk.
"""

from __future__ import annotations

import ipaddress

import jax
import numpy as np
import pytest

from cilium_tpu import metrics
from cilium_tpu.datapath.conntrack import FlowConntrack
from cilium_tpu.datapath.pipeline import (
    L4_COVERED_BIT,
    REDIRECT_BIT,
    TRAFFIC_EGRESS,
    TRAFFIC_INGRESS,
    DatapathPipeline,
    _pack_v4_u32,
    _pad_flows,
    flow_rows,
    pack_flow_chunk,
    process_flows,
    process_flows_packed,
    process_flows_wide,
    process_flows_wide_packed,
    unpack_flow_result,
)
from cilium_tpu.datapath.placement import PlacementConfig
from cilium_tpu.engine import PolicyEngine
from cilium_tpu.identity import IdentityRegistry
from cilium_tpu.ipcache.ipcache import IPCache
from cilium_tpu.ipcache.prefilter import PreFilter
from cilium_tpu.labels import parse_label_array
from cilium_tpu.policy.api import (
    EgressRule,
    EndpointSelector,
    HTTPRule,
    IngressRule,
    L7Rules,
    PortProtocol,
    PortRule,
    rule,
)
from cilium_tpu.policy.repository import Repository

APPS = ("web", "api", "lb", "db", "other", "a5", "a6", "a7")
DPORTS = np.array([80, 443, 5432, 8080, 22], np.int32)


def _sel(app):
    return (EndpointSelector.make([f"k8s:app={app}"]),)


def _port(port, l7=False):
    if not l7:
        return (PortRule(ports=(PortProtocol(port, "TCP"),)),)
    return (PortRule(ports=(PortProtocol(port, "TCP"),),
                     rules=L7Rules(http=(HTTPRule(method="GET"),))),)


def _world(placement=None):
    """web and api are the local endpoints; lb reaches web:80 through
    the HTTP proxy (redirect), api reaches web on every port, web
    reaches api:443; egress: web to db:5432 and to lb:8080 through the
    proxy. ``other``'s addresses and two ranges sit in the prefilter."""
    repo = Repository()
    repo.add_list([
        rule(["k8s:app=web"], ingress=[
            IngressRule(from_endpoints=_sel("lb"), to_ports=_port(80, l7=True)),
            IngressRule(from_endpoints=_sel("api")),
        ], egress=[
            EgressRule(to_endpoints=_sel("db"), to_ports=_port(5432)),
            EgressRule(to_endpoints=_sel("lb"), to_ports=_port(8080, l7=True)),
        ]),
        rule(["k8s:app=api"], ingress=[
            IngressRule(from_endpoints=_sel("web"), to_ports=_port(443)),
        ], egress=[EgressRule(to_endpoints=_sel("web"))]),
    ])
    reg = IdentityRegistry()
    idents = [reg.allocate(parse_label_array([f"k8s:app={a}"])) for a in APPS]
    engine = PolicyEngine(repo, reg)
    cache = IPCache()
    for i, ident in enumerate(idents):
        cache.upsert(f"10.0.0.{i + 1}/32", ident.id, source="k8s")
        cache.upsert(f"fd00::{i + 1:x}/128", ident.id, source="k8s")
    cache.upsert("10.7.0.0/16", idents[2].id, source="k8s")
    pf = PreFilter()
    pf.insert(pf.revision, ["10.0.0.5/32", "192.0.2.0/24", "fd00::5/128", "2001:db8::/32"])
    pipe = DatapathPipeline(engine, cache, pf, conntrack=FlowConntrack(capacity_bits=14),
                            placement=placement)
    pipe.set_endpoints([idents[0].id, idents[1].id])
    pipe.set_attribution(True)
    pipe.rebuild()
    return pipe, idents


def _addresses(family, n_idents):
    if family == 4:
        known = [f"10.0.0.{i + 1}" for i in range(n_idents)]
        extra = ["10.7.3.9", "192.0.2.77", "8.8.8.8"]
    else:
        known = [f"fd00::{i + 1:x}" for i in range(n_idents)]
        extra = ["2001:db8::9", "fe80::7", "2606:4700::1111"]
    return np.array([list(ipaddress.ip_address(a).packed) for a in known + extra], np.int32)


def _flows(family, m, seed):
    rng = np.random.default_rng(seed)
    pool = _addresses(family, len(APPS))
    return (pool[rng.integers(0, len(pool), m)], rng.integers(0, 2, m).astype(np.int32),
            DPORTS[rng.integers(0, len(DPORTS), m)],
            rng.choice(np.array([6, 6, 6, 17], np.int32), m))


def _row_override(pipe, idents, m, seed):
    """Half the lanes trusted to a real identity row, half -1 (LPM)."""
    rng = np.random.default_rng(seed)
    rows = pipe.engine.rows_or_negative(np.array([i.id for i in idents], np.int64))
    ro = rows[rng.integers(0, len(rows), m)].astype(np.int32)
    ro[rng.random(m) < 0.5] = -1
    return ro


def _per_array(pipe, family, ingress, prefilter, peer_bytes, ep, dp, pr, ro, attrib):
    """The per-array kernel on exactly the lanes given → numpy tuple."""
    direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS
    tables, _pf_empty, v6_fused, *_rest = pipe._dp_state
    t = tables[(direction, family)]
    kw = dict(ep_count=len(pipe._endpoints), prefilter=prefilter, row_override=ro)
    if attrib:
        rule_tab, n_rules = pipe._dp_state[5]
        kw.update(attrib=True, rule_tab=rule_tab[direction], n_rules=n_rules)
    if family == 4:
        out = process_flows_wide(t, _pack_v4_u32(peer_bytes), ep, dp, pr, **kw)
    else:
        out = process_flows(t, peer_bytes, ep, dp, pr, levels=16, fused=v6_fused, **kw)
    return tuple(np.asarray(x) for x in out)


@pytest.fixture(scope="module")
def world():
    return _world()


class TestPackedKernel:
    @pytest.mark.parametrize("attrib", [False, True], ids=["plain", "attrib"])
    @pytest.mark.parametrize("overlay", [False, True], ids=["lpm", "overlay"])
    @pytest.mark.parametrize("prefilter", [True, False], ids=["pf", "nopf"])
    @pytest.mark.parametrize("ingress", [True, False], ids=["ingress", "egress"])
    @pytest.mark.parametrize("family", [4, 6], ids=["v4", "v6"])
    def test_bit_identical_to_per_array(self, world, family, ingress, prefilter,
                                        overlay, attrib):
        pipe, idents = world
        lanes, m = 1024, 1000
        pb, ep, dp, pr = _flows(family, m, seed=family * 10 + ingress)
        ro = _row_override(pipe, idents, m, seed=5) if overlay else None
        ro_pad = None if ro is None else np.pad(ro, (0, lanes - m), constant_values=-1)
        ref = _per_array(pipe, family, ingress, prefilter,
                         *_pad_flows(lanes - m, pb, ep, dp, pr), ro_pad, attrib)

        buf = np.full((flow_rows(family, overlay), lanes), 12345, np.int32)  # stale lanes
        pack_flow_chunk(buf, family, pb, ep, dp, pr, ro)
        if overlay:
            assert (buf[-1, m:] == -1).all()
        direction = TRAFFIC_INGRESS if ingress else TRAFFIC_EGRESS
        tables, _pf, v6_fused, *_rest = pipe._dp_state
        kw = dict(ep_count=len(pipe._endpoints), prefilter=prefilter)
        if attrib:
            rule_tab, n_rules = pipe._dp_state[5]
            kw.update(attrib=True, rule_tab=rule_tab[direction], n_rules=n_rules)
        t = tables[(direction, family)]
        if family == 4:
            res = process_flows_wide_packed(t, buf, **kw)
        else:
            res = process_flows_packed(t, buf, levels=16, fused=v6_fused, **kw)
        res = np.asarray(res)
        assert res.dtype == np.int32 and res.ndim == 1
        words, counters, rule_w, hits = unpack_flow_result(res, lanes, len(pipe._endpoints),
                                                           attrib)
        np.testing.assert_array_equal((words & 0xFF).astype(np.int8), ref[0])
        np.testing.assert_array_equal((words & REDIRECT_BIT) != 0, ref[1])
        np.testing.assert_array_equal(counters, ref[2])
        if attrib:
            np.testing.assert_array_equal((words & L4_COVERED_BIT) != 0, ref[4])
            np.testing.assert_array_equal(rule_w, ref[3])
            np.testing.assert_array_equal(hits, ref[5])
            assert res.size == 2 * lanes + 3 * len(pipe._endpoints) + ref[5].size
        else:
            assert not (words & L4_COVERED_BIT).any()
            assert res.size == lanes + 3 * len(pipe._endpoints)
        # the world answers every way on these flows
        assert {1, 2, 3} <= set(ref[0][:m].tolist()) or not prefilter
        if ingress and not overlay:
            assert ref[1][:m].any(), "no redirected flow to check"


def _check_dispatch(pipe, ref_pipe, family, m, ingress, overlay, idents, seed):
    pb, ep, dp, pr = _flows(family, m, seed)
    ro = _row_override(ref_pipe, idents, m, seed + 1) if overlay else None
    out = pipe._dispatch(pb, ep, dp, pr, ingress=ingress, family=family, pad_to=1,
                         row_override=ro)
    spans = pipe._chunk_spans(m, bucketed=True, ndev=pipe._dp_state[4])
    exact = all(hi - lo == p for lo, hi, p in spans)
    prefilter = ingress and not ref_pipe._dp_state[1][0 if family == 4 else 1]
    ref = _per_array(ref_pipe, family, ingress, prefilter, pb, ep, dp, pr, ro, True)
    v, red, counters, rule_w, l4x, hits = out
    np.testing.assert_array_equal(v, ref[0])
    np.testing.assert_array_equal(red, ref[1])
    np.testing.assert_array_equal(rule_w, ref[3])
    np.testing.assert_array_equal(l4x, ref[4])
    if exact:
        np.testing.assert_array_equal(counters, ref[2])
        np.testing.assert_array_equal(hits, ref[5])
    else:
        assert counters is None and hits is None
    return len(spans)


# m → (chunks, padded): one padded rung, one exact rung, a full top rung
# plus a padded tail, two exact top rungs
BATCHES = [(20, 1, True), (2048, 1, False), (9000, 2, True), (16384, 2, False)]


class TestPackedDispatch:
    @pytest.mark.parametrize("m,chunks,padded", BATCHES,
                             ids=[f"m{b[0]}" for b in BATCHES])
    @pytest.mark.parametrize("family", [4, 6], ids=["v4", "v6"])
    def test_dispatch_matches_per_array(self, world, family, m, chunks, padded):
        pipe, idents = world
        for ingress, overlay in ((True, False), (False, True)):
            got = _check_dispatch(pipe, pipe, family, m, ingress, overlay, idents,
                                  seed=m + family)
            assert got == chunks

    def test_staging_buffer_is_one_packed_chunk(self, world):
        pipe, _ = world
        pipe._dispatch(*_flows(4, 1500, seed=3), ingress=True, family=4, pad_to=1)
        (buf,) = pipe._staging[(2048, flow_rows(4, False))][-1:]
        assert buf.shape == (4, 2048) and buf.dtype == np.int32


@pytest.fixture(scope="module")
def mesh_world():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for the 2x2 flows x ident plan")
    pipe, idents = _world(PlacementConfig(device_ids=(0, 1, 2, 3), ident_axis=2))
    pipe.set_sharding(True)
    pipe.set_mesh_2d(True)
    pipe.rebuild()
    assert pipe._plan.axes == {"flows": 2, "ident": 2}
    return pipe, idents


class TestPackedMesh:
    @pytest.mark.parametrize("m", [20, 9000])
    @pytest.mark.parametrize("family", [4, 6], ids=["v4", "v6"])
    def test_2x2_plan_matches_one_device(self, world, mesh_world, family, m):
        ref_pipe, idents = world
        pipe, _ = mesh_world
        for ingress, overlay in ((True, False), (False, True)):
            _check_dispatch(pipe, ref_pipe, family, m, ingress, overlay, idents,
                            seed=m + 7 * family)


def _transfers(direction):
    return metrics.device_transfers_total.get({"direction": direction})


class TestTransferCount:
    @pytest.mark.parametrize("m,chunks", [(20, 1), (1500, 1), (9000, 2)])
    def test_one_upload_and_one_pull_per_chunk(self, world, m, chunks):
        pipe, _ = world
        pb, ep, dp, pr = _flows(4, m, seed=m)
        sports = np.arange(m, dtype=np.int32) + 1024 + m
        pipe.conntrack.flush()
        pipe.tracer.enable()
        try:
            h2d0, d2h0 = _transfers("h2d"), _transfers("d2h")
            pipe.submit(_pack_v4_u32(pb), ep, dp, pr, sports=sports).result()
            assert (_transfers("h2d") - h2d0, _transfers("d2h") - d2h0) == (chunks, chunks)
        finally:
            pipe.tracer.disable()

    def test_2x2_plan_uploads_to_each_device(self, mesh_world):
        pipe, _ = mesh_world
        pb, ep, dp, pr = _flows(6, 9000, seed=4)
        pipe.tracer.enable()
        try:
            h2d0, d2h0 = _transfers("h2d"), _transfers("d2h")
            enq = pipe._dispatch_enqueue(pb, ep, dp, pr, ingress=True, family=6,
                                         bucketed=True)
            # each result is replicated, so its one pull reads one device
            assert all(ch.sharding.is_fully_replicated for ch in enq.chunks)
            pipe._dispatch_complete(enq)
            assert (_transfers("h2d") - h2d0, _transfers("d2h") - d2h0) == (2 * 4, 2)
        finally:
            pipe.tracer.disable()

    def test_untraced_dispatch_counts_nothing(self, world):
        pipe, _ = world
        h2d0, d2h0 = _transfers("h2d"), _transfers("d2h")
        pipe._dispatch(*_flows(4, 20, seed=9), ingress=True, family=4, pad_to=1)
        assert (_transfers("h2d"), _transfers("d2h")) == (h2d0, d2h0)
