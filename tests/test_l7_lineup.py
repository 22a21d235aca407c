"""policyd-l7batch lineup tests: fused multi-field DFA dispatch vs the
split per-field path.

Pins the PR's contracts: masks stay bit-identical to host ``re``
(fuzzed, including demoted-pattern fallback), the L7DeviceBatch OFF
path never touches the fused kernels, device tables are interned by
pattern-set key, the length ladder + prewarm keep jit compiles off the
request path, and the vectorized packer matches the per-string
reference exactly (embedded NULs, overlong, empty)."""

from __future__ import annotations

import random
import re

import numpy as np
import pytest

from cilium_tpu import metrics
from cilium_tpu.datapath import l7_pipeline as l7rt
from cilium_tpu.datapath.l7_pipeline import L7_LANE_RUNGS, L7Pipeline, lane_rung
from cilium_tpu.l7 import HTTPPolicy, HTTPRequest, KafkaACL, KafkaRequest, compile_patterns
from cilium_tpu.l7.http_policy import _DEVICE_BATCH_MIN
from cilium_tpu.l7.kafka_policy import _mask_ids
from cilium_tpu.l7.regex_compile import compile_patterns_cached
from cilium_tpu.ops import dfa as dfa_mod
from cilium_tpu.observe.profiler import DeviceProfiler
from cilium_tpu.ops.dfa import (
    DFA_INTERN_CAP,
    L7_LEN_LADDER,
    DeviceDFATable,
    dfa_intern_stats,
    dfa_match_batch_fused,
    dfa_match_batch_pair,
    dfa_match_packed_fused,
    dfa_match_packed_pair,
    fuse_dfas,
    intern_fused_table,
    len_rung,
    pack_walk_rows,
    strings_to_batch,
    strings_to_batch_u8,
)
from cilium_tpu.policy.api import HTTPRule, KafkaRule


@pytest.fixture(autouse=True)
def _reset_l7_runtime():
    """The runtime gate and the intern cache are process-global."""
    l7rt._reset_for_tests()
    dfa_mod._reset_intern_for_tests()
    yield
    l7rt._reset_for_tests()
    dfa_mod._reset_intern_for_tests()


def _ref_pack(strings, max_len):
    """The pre-PR per-string loop packer, kept as the oracle."""
    b = len(strings)
    out = np.zeros((b, max_len), np.int32)
    lens = np.zeros(b, np.int32)
    for i, s in enumerate(strings):
        if len(s) > max_len:
            lens[i] = -1
            continue
        out[i, : len(s)] = np.frombuffer(s, np.uint8)
        lens[i] = len(s)
    return out, lens


class TestVectorizedPacker:
    @pytest.mark.parametrize("seed", range(8))
    def test_matches_loop_reference(self, seed):
        rng = random.Random(seed)
        strings = [
            bytes(rng.randrange(256) for _ in range(rng.choice([0, 1, 3, 15, 16, 17, 40])))
            for _ in range(rng.randrange(0, 30))
        ]
        got, got_lens = strings_to_batch(strings, 16)
        want, want_lens = _ref_pack(strings, 16)
        assert np.array_equal(got, want)
        assert np.array_equal(got_lens, want_lens)

    def test_embedded_nul_preserved(self):
        out, lens = strings_to_batch([b"a\x00b"], 8)
        assert lens[0] == 3
        assert out[0, :3].tolist() == [0x61, 0x00, 0x62]

    def test_overlong_marked_and_zeroed(self):
        out, lens = strings_to_batch([b"x" * 20, b"ok"], 8)
        assert lens.tolist() == [-1, 2]
        assert not out[0].any()

    def test_u8_variant_same_bytes(self):
        strings = [b"hello", b"", b"\xff" * 8]
        i32, li = strings_to_batch(strings, 8)
        u8, lu = strings_to_batch_u8(strings, 8)
        assert u8.dtype == np.uint8
        assert np.array_equal(i32, u8.astype(np.int32))
        assert np.array_equal(li, lu)

    def test_empty_batch(self):
        out, lens = strings_to_batch([], 16)
        assert out.shape == (0, 16) and lens.shape == (0,)


def _device_masks(patterns, probes, max_len=64):
    """probes → [B] uint64 accept masks via the fused device path."""
    table = DeviceDFATable(("t", tuple(patterns)), fuse_dfas([compile_patterns(patterns)]))
    pipe = L7Pipeline(depth=1)
    pending = pipe.submit(table, [(probes, max_len)])
    return pending.result()[0]


class TestFuzzVsStdlibRe:
    """The acceptance contract: fused-path accept masks bit-identical
    to host ``re.fullmatch`` over generated corpora."""

    @pytest.mark.parametrize("seed", range(6))
    def test_fused_masks_vs_re(self, seed):
        rng = random.Random(100 + seed)
        atoms = ["a", "b", "0", "/", "[a-z]", "[0-9]", ".", "x+", "b*", "(ab|ba)", "c?"]
        patterns = []
        while len(patterns) < 12:
            pat = "".join(rng.choice(atoms) for _ in range(rng.randrange(1, 6)))
            try:
                re.compile(pat)
            except re.error:
                continue
            patterns.append(pat)
        alphabet = "ab0/xcyz"
        probes = [
            "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 12))).encode()
            for _ in range(200)
        ]
        masks = _device_masks(patterns, probes, max_len=16)
        for probe, mask in zip(probes, masks):
            for i, pat in enumerate(patterns):
                want = re.fullmatch(pat, probe.decode()) is not None
                got = (int(mask) >> i) & 1 == 1
                assert got == want, f"{pat!r} vs {probe!r}"

    @pytest.mark.parametrize("seed", range(4))
    def test_policy_verdicts_vs_oracle_with_demoted_pattern(self, seed):
        """ON-path verdicts vs the HTTPRule.matches oracle, with one
        pattern demoted to host ``re`` (state-cap overflow) so the
        fused masks and the host overlay compose."""
        rng = random.Random(200 + seed)
        rules = [
            (HTTPRule(method="GET|POST", path="/api/v[0-9]+/[a-z]*"), None),
            (HTTPRule(path="/bad/.*x.{14}y"), None),  # demoted to host re
            (HTTPRule(method="PUT", path="/obj/[a-f0-9]+", host="svc[.]local"), None),
        ]
        l7rt.set_device_batch(True)
        pol = HTTPPolicy(rules)
        assert pol._paths.host_pids  # the demotion actually happened
        reqs = []
        for i in range(max(_DEVICE_BATCH_MIN, 80)):
            reqs.append(HTTPRequest(
                method=rng.choice(["GET", "POST", "PUT", "HEAD"]),
                path=rng.choice([
                    f"/api/v{i % 7}/obj", "/bad/" + "q" * 9 + "x" + "w" * 14 + "y",
                    "/bad/zzz", f"/obj/{i % 16:x}", "/nope",
                ]),
                host=rng.choice(["svc.local", "svcxlocal", ""]),
            ))
        got = pol.check_batch(reqs)
        for req, g in zip(reqs, got):
            want = any(
                r.matches(req.method, req.path, req.host) for r, _ in rules
            )
            assert bool(g) == want, (req, bool(g), want)


def _mixed_requests(n):
    rng = random.Random(7)
    reqs = []
    for i in range(n):
        reqs.append(HTTPRequest(
            method=rng.choice(["GET", "POST", "PUT", "PATCH", "DELETE"]),
            path=rng.choice([
                f"/api/v{i % 12}/x{i}", f"/svc{i % 10}/upload", "/health",
                "/" + "a" * rng.choice([5, 290]),
            ]),
            host=rng.choice(["internal.corp", "example.com", ""]),
            src_identity=rng.choice([17, 99]),
        ))
    return reqs


_HTTP_RULES = [
    (HTTPRule(method="GET", path="/api/v[0-9]+/.*"), None),
    (HTTPRule(method="POST", path="/svc[0-9]/upload", host="internal[.]corp"), None),
    (HTTPRule(path="/health"), {17}),
]


class TestOnOffParity:
    def test_http_bit_identical_and_toggle_back(self):
        reqs = _mixed_requests(200)
        off = HTTPPolicy(_HTTP_RULES).check_batch(reqs)
        l7rt.set_device_batch(True)
        pol = HTTPPolicy(_HTTP_RULES)
        assert pol._fused_table is not None
        assert np.array_equal(off, pol.check_batch(reqs))
        # flipping the option off returns the SAME policy object to the
        # pre-option programs, same verdicts
        l7rt.set_device_batch(False)
        assert np.array_equal(off, pol.check_batch(reqs))

    def test_kafka_bit_identical(self):
        rng = random.Random(11)
        rules = [
            (KafkaRule(api_key="fetch", topic="orders"), None),
            (KafkaRule(role="produce", topic="audit", client_id="svc-a"), {17, 21}),
            (KafkaRule(topic="metrics"), None),
        ]
        reqs = [KafkaRequest(
            api_key=rng.choice([0, 1, 2, 19, 36]),
            api_version=rng.choice([0, 3]),
            client_id=rng.choice(["svc-a", "svc-b", "", "x" * 200]),
            topic=rng.choice(["orders", "audit", "metrics", "unknown", "", "t" * 150]),
            src_identity=rng.choice([17, 21, 99]),
        ) for _ in range(max(_DEVICE_BATCH_MIN, 150))]
        off = KafkaACL(rules).check_batch(reqs)
        l7rt.set_device_batch(True)
        acl = KafkaACL(rules)
        assert acl._fused_table is not None
        assert np.array_equal(off, acl.check_batch(reqs))

    def test_off_path_never_invokes_fused_kernels(self, monkeypatch):
        """The FlowAttribution/DispatchAutoTune pinning discipline: OFF
        keeps compiling the exact pre-option programs — the fused
        kernels must be unreachable."""
        def _boom(*a, **k):
            raise AssertionError("fused kernel invoked with L7DeviceBatch off")
        monkeypatch.setattr(dfa_mod, "dfa_match_batch_fused", _boom)
        monkeypatch.setattr(dfa_mod, "dfa_match_batch_pair", _boom)
        monkeypatch.setattr(l7rt, "dfa_match_packed_fused", _boom)
        monkeypatch.setattr(l7rt, "dfa_match_packed_pair", _boom)
        pol = HTTPPolicy(_HTTP_RULES)
        assert pol._fused_table is None  # not even built
        pol.check_batch(_mixed_requests(200))
        acl = KafkaACL([(KafkaRule(topic="orders"), None)])
        assert acl._fused_table is None
        acl.check_batch([KafkaRequest(api_key=1, topic="orders")] * 64)


class TestInterning:
    def test_same_pattern_set_shares_one_device_table(self):
        l7rt.set_device_batch(True)
        a = HTTPPolicy(_HTTP_RULES)
        b = HTTPPolicy(_HTTP_RULES)
        assert a._fused_table is b._fused_table
        assert dfa_intern_stats()[0] == 1
        assert metrics.l7_dfa_tables_interned.get() == 1
        c = HTTPPolicy([(HTTPRule(path="/other"), None)])
        assert c._fused_table is not a._fused_table
        assert dfa_intern_stats()[0] == 2

    def test_lru_eviction_past_cap(self):
        hits0 = metrics.l7_dfa_intern_total.get({"result": "evict"})
        for i in range(DFA_INTERN_CAP + 3):
            intern_fused_table(
                ("t", i), lambda i=i: fuse_dfas([compile_patterns([f"/p{i}"])])
            )
        assert dfa_intern_stats()[0] == DFA_INTERN_CAP
        assert metrics.l7_dfa_intern_total.get({"result": "evict"}) - hits0 == 3
        assert metrics.l7_dfa_tables_interned.get() == DFA_INTERN_CAP

    def test_hit_does_not_rebuild(self):
        calls = []
        def build():
            calls.append(1)
            return fuse_dfas([compile_patterns(["/x"])])
        t1 = intern_fused_table(("k",), build)
        t2 = intern_fused_table(("k",), build)
        assert t1 is t2 and len(calls) == 1

    def test_compile_cache_shares_multidfa(self):
        d1 = compile_patterns_cached(["/a", "/b"])
        d2 = compile_patterns_cached(["/a", "/b"])
        assert d1 is d2


class TestLadderAndPrewarm:
    def test_len_rung_selection(self):
        assert len_rung(1, 128) == 16
        assert len_rung(16, 128) == 16
        assert len_rung(17, 128) == 32
        assert len_rung(100, 128) == 128
        assert len_rung(5, 24) == 16  # ladder rung under the cap
        assert len_rung(20, 24) == 24  # cap itself is the top rung
        assert len_rung(500, 24) == 24

    def test_lane_rung_selection(self):
        assert lane_rung(1) == L7_LANE_RUNGS[0]
        assert lane_rung(513) == L7_LANE_RUNGS[1]
        assert lane_rung(L7_LANE_RUNGS[-1] + 1) == L7_LANE_RUNGS[-1]

    def test_prewarm_counts_and_claims_shapes(self):
        table = DeviceDFATable(("w",), fuse_dfas([compile_patterns(["/api/.*"])]))
        pipe = L7Pipeline(depth=1)
        warm0 = metrics.jit_shape_buckets_total.get({"site": "l7", "result": "warm"})
        warmed = pipe.prewarm(table, [64])
        # rungs ≤ 64 from the ladder × lane rungs
        assert warmed == 3 * len(L7_LANE_RUNGS)
        assert metrics.jit_shape_buckets_total.get({"site": "l7", "result": "warm"}) - warm0 == warmed
        # a prewarmed shape dispatches as a hit, not a first-use miss
        miss0 = metrics.jit_shape_buckets_total.get({"site": "l7", "result": "miss"})
        hit0 = metrics.jit_shape_buckets_total.get({"site": "l7", "result": "hit"})
        pipe.submit(table, [([b"/api/x"] * 10, 64)]).result()
        assert metrics.jit_shape_buckets_total.get({"site": "l7", "result": "miss"}) == miss0
        assert metrics.jit_shape_buckets_total.get({"site": "l7", "result": "hit"}) == hit0 + 1

    def test_submit_picks_rung_from_longest_string(self):
        table = DeviceDFATable(("r",), fuse_dfas([compile_patterns(["[a-z]*"])]))
        pipe = L7Pipeline(depth=1)
        pipe.submit(table, [([b"ab" * 10], 128)]).result()  # 20 bytes → rung 32
        kinds = {k[3] for k in pipe._seen_shapes}
        assert kinds == {32}

    def test_pad_lane_accounting(self):
        table = DeviceDFATable(("p",), fuse_dfas([compile_patterns(["x*"])]))
        pipe = L7Pipeline(depth=1)
        pad0 = metrics.l7_pad_lanes_total.get({"kind": "lane"})
        live0 = metrics.l7_pad_lanes_total.get({"kind": "lane_live"})
        pipe.submit(table, [([b"x"] * 100, 16)]).result()
        assert metrics.l7_pad_lanes_total.get({"kind": "lane"}) - pad0 == L7_LANE_RUNGS[0] - 100
        assert metrics.l7_pad_lanes_total.get({"kind": "lane_live"}) - live0 == 100


class TestPipeline:
    def _table(self):
        return DeviceDFATable(("pl",), fuse_dfas([compile_patterns(["/a.*", "/b.*"])]))

    def test_fifo_depth_bound_and_results(self):
        table = self._table()
        pipe = L7Pipeline(depth=2)
        pending = [
            pipe.submit(table, [([b"/a1", b"/b2", b"/c3"], 16)])
            for _ in range(5)
        ]
        # depth 2: submitting 5 forces the oldest 3 to completion
        assert sum(p._done for p in pending) >= 3
        for p in pending:
            (mask,) = p.result()
            assert mask.tolist() == [1, 2, 0]

    def test_out_of_order_result_allowed(self):
        table = self._table()
        pipe = L7Pipeline(depth=4)
        p1 = pipe.submit(table, [([b"/a"], 16)])
        p2 = pipe.submit(table, [([b"/b"], 16)])
        assert p2.result()[0].tolist() == [2]  # completes p1 behind it
        assert p1.result()[0].tolist() == [1]

    def test_empty_batch(self):
        pipe = L7Pipeline(depth=2)
        (mask,) = pipe.submit(self._table(), [([], 16)]).result()
        assert mask.shape == (0,)

    def test_multi_field_starts(self):
        """Per-field start states: the same byte string classifies
        against each field's own DFA in one dispatch."""
        d1 = compile_patterns(["GET"])
        d2 = compile_patterns(["/x", "GET"])
        table = DeviceDFATable(("mf",), fuse_dfas([d1, d2]))
        pipe = L7Pipeline(depth=1)
        m1, m2 = pipe.submit(
            table, [([b"GET", b"/x"], 8), ([b"GET", b"/x"], 8)]
        ).result()
        assert m1.tolist() == [1, 0]
        assert m2.tolist() == [2, 1]

    def test_overlong_rows_masked_per_field_cap(self):
        table = DeviceDFATable(("ol",), fuse_dfas([compile_patterns(["x*"])]))
        pipe = L7Pipeline(depth=1)
        (mask,) = pipe.submit(table, [([b"x" * 30, b"xx"], 16)]).result()
        assert mask.tolist() == [0, 1]  # overlong row fails closed

    def test_batches_counter_by_parser(self):
        table = self._table()
        pipe = L7Pipeline(depth=1)
        before = metrics.l7_batches_total.get({"parser": "kafka"})
        pipe.submit(table, [([b"/a"], 16)], parser="kafka").result()
        assert metrics.l7_batches_total.get({"parser": "kafka"}) == before + 1


class TestKafkaDevice:
    def test_mask_ids(self):
        masks = np.array([0, 1, 2, 1 << 63, 1 << 7], np.uint64)
        assert _mask_ids(masks).tolist() == [-2, 0, 1, 63, 7]

    def test_device_ids_match_dict_path(self):
        rules = [(KafkaRule(topic=f"topic-{i}"), None) for i in range(10)]
        l7rt.set_device_batch(True)
        acl = KafkaACL(rules)
        reqs = [KafkaRequest(api_key=1, topic=f"topic-{i % 12}") for i in range(64)]
        dev = acl._device_ids(reqs)
        want = [acl._topic_ids.get(r.topic, -2) for r in reqs]
        assert dev["topic"].tolist() == want

    def test_over_64_literals_fall_back_to_dict(self):
        rules = [(KafkaRule(topic=f"t{i}"), None) for i in range(70)]
        l7rt.set_device_batch(True)
        acl = KafkaACL(rules)
        assert acl._fused_table is None
        reqs = [KafkaRequest(api_key=1, topic="t3")] * 40
        assert acl.check_batch(reqs).all()


class TestRuntimeOption:
    def test_option_spec_registered(self):
        from cilium_tpu.option import OPTION_SPECS
        assert "L7DeviceBatch" in OPTION_SPECS

    def test_depth_validation(self):
        from cilium_tpu.option import DaemonConfig
        cfg = DaemonConfig(l7_pipeline_depth=0)
        with pytest.raises(ValueError):
            cfg.validate()

    def test_toggle_off_drains_shared_pipeline(self):
        l7rt.set_device_batch(True)
        pipe = l7rt.shared_pipeline()
        assert pipe is not None
        table = DeviceDFATable(("d",), fuse_dfas([compile_patterns(["/a"])]))
        pending = pipe.submit(table, [([b"/a"], 16)])
        l7rt.set_device_batch(False)
        assert not l7rt.device_batch_enabled()
        assert l7rt.shared_pipeline() is None
        assert pending.result()[0].tolist() == [1]  # drained, not dropped


# ---------------------------------------------------------------------------
# Packed walk: one buffer up, one [2, lanes] result down per lane chunk
# ---------------------------------------------------------------------------

# two fields: a method-like one capped at 8 bytes, a path-like one at
# the walk's rung, so rows overlong for their field's cap but not for
# the rung are walked too
_PACK_FIELDS = (["GET", "POST", "[A-Z]+"], ["/a[a-c]*", "/b.*x", "[a-c/]*", "/c/.*"])


def _pack_table(kind):
    dfas = [compile_patterns(p) for p in _PACK_FIELDS]
    fused = fuse_dfas(dfas) if kind == "pair" else fuse_dfas(dfas, pair_cap_elems=0)
    table = DeviceDFATable(("pack", kind), fused)
    assert table.has_pair == (kind == "pair")
    return table


def _pack_fields(n_req, rung, seed):
    """Two fields of ``n_req`` strings whose longest is exactly ``rung``
    bytes, with rows overlong for the rung and for the first field's
    cap, empty rows, and rows that match."""
    rng = random.Random(seed)
    methods, paths = [], []
    for i in range(n_req):
        methods.append(rng.choice([b"GET", b"POST", b"PUT", b"", b"DELETEDELETE"]))
        n = rng.choice([0, 1, rng.randint(1, rung), rung, rung + 3])
        body = bytes(rng.choice(b"abcx/") for _ in range(max(0, n - 2)))
        paths.append((rng.choice([b"/a", b"/b", b"/c", b"zz"]) + body)[:n])
    paths[0] = b"/" + b"a" * (rung - 1)
    return [(methods, 8), (paths, rung)]


def _three_array_masks(table, fields, rung, lanes):
    """The three-array path as it was before the packed walk: bytes,
    lengths and start states uploaded apart, lo and hi pulled apart.
    → ([lanes] uint32 lo, [lanes] uint32 hi, live rows)."""
    n_req = len(fields[0][0])
    flat = [s for values, _ in fields for s in values]
    sb, lens = strings_to_batch_u8(flat, rung)
    for f, (_, cap) in enumerate(fields):
        seg = lens[f * n_req : (f + 1) * n_req]
        seg[seg > cap] = -1
    live = len(flat)
    psb = np.zeros((lanes, rung), np.uint8)
    psb[:live] = sb
    plens = np.full(lanes, -1, np.int32)
    plens[:live] = lens
    pstarts = np.zeros(lanes, np.int32)
    pstarts[:live] = np.repeat(table.starts_host, n_req)
    if table.has_pair:
        lo, hi = dfa_match_batch_pair(
            table.pair, table.accept_lo, table.accept_hi, pstarts, psb, plens, rung)
    else:
        lo, hi = dfa_match_batch_fused(
            table.trans, table.accept_lo, table.accept_hi, pstarts, psb, plens, rung)
    return np.asarray(lo), np.asarray(hi), (sb, lens, live)


def _transfers(direction, parser="http"):
    return metrics.l7_device_transfers_total.get(
        {"direction": direction, "parser": parser})


class TestPackedWalk:
    @pytest.mark.parametrize("lanes", L7_LANE_RUNGS)
    @pytest.mark.parametrize("rung", L7_LEN_LADDER + (256,))
    @pytest.mark.parametrize("kind", ["pair", "single"])
    def test_bit_identical_to_three_array_kernels(self, kind, rung, lanes):
        """Every length rung × lane rung × table kind: the packed kernel
        equals the three-array kernel on every row, pad rows included,
        and ``submit`` picks the same rungs and returns the same masks."""
        table = _pack_table(kind)
        n_req = (lanes // 2) * 3 // 8 if lanes > L7_LANE_RUNGS[0] else 150
        fields = _pack_fields(n_req, rung, seed=rung * 7 + lanes)
        lo, hi, (sb, lens, live) = _three_array_masks(table, fields, rung, lanes)
        assert lane_rung(live) == lanes and live < lanes
        assert (lens == -1).any() and (lens == 0).any() and lo.any()

        starts = np.repeat(table.starts_host, n_req)
        packed = pack_walk_rows(sb, lens, starts, lanes)
        assert packed.shape == (lanes, rung + dfa_mod.PACK_HEADER)
        walk = dfa_match_packed_pair if table.has_pair else dfa_match_packed_fused
        got = np.asarray(walk(
            table.pair if table.has_pair else table.trans,
            table.accept_lo, table.accept_hi, packed, rung))
        assert got.dtype == np.uint32 and got.shape == (2, lanes)
        assert np.array_equal(got[0], lo) and np.array_equal(got[1], hi)
        assert not got[:, live:].any()  # pad rows: empty masks

        pipe = L7Pipeline(depth=1)
        masks = pipe.submit(table, fields).result()
        assert {(k[2], k[3]) for k in pipe._seen_shapes} == {(lanes, rung)}
        want = lo[:live].astype(np.uint64) | (hi[:live].astype(np.uint64) << np.uint64(32))
        assert np.array_equal(np.concatenate(masks), want)

    @pytest.mark.parametrize("kind", ["pair", "single"])
    def test_multi_chunk_submit(self, kind):
        """Past the top lane rung a submit walks several chunks; the
        masks equal one three-array walk over every row."""
        table = _pack_table(kind)
        top = L7_LANE_RUNGS[-1]
        n_req = top // 2 + 300  # 2 fields: one full chunk and a tail
        fields = _pack_fields(n_req, 16, seed=3)
        lo, hi, (_, _, live) = _three_array_masks(table, fields, 16, 2 * n_req)
        pipe = L7Pipeline(depth=1)
        h2d0, d2h0 = _transfers("h2d"), _transfers("d2h")
        m1, m2 = pipe.submit(table, fields).result()
        want = lo.astype(np.uint64) | (hi.astype(np.uint64) << np.uint64(32))
        assert np.array_equal(np.concatenate([m1, m2]), want)
        assert {k[2] for k in pipe._seen_shapes} == {top, lane_rung(live - top)}
        assert (_transfers("h2d") - h2d0, _transfers("d2h") - d2h0) == (2, 2)

    @pytest.mark.parametrize("parser", ["http", "kafka"])
    @pytest.mark.parametrize("n_req", [_DEVICE_BATCH_MIN, 171, 2000])
    def test_check_batch_on_equals_off(self, parser, n_req):
        """The policies' answers with L7DeviceBatch on (the packed walk)
        against off, from the smallest device batch to a 16k-lane one."""
        if parser == "http":
            reqs = _mixed_requests(n_req)
            make = lambda: HTTPPolicy(_HTTP_RULES)  # noqa: E731
        else:
            rng = random.Random(n_req)
            reqs = [KafkaRequest(
                api_key=rng.choice([0, 1, 2]), topic=rng.choice(["orders", "audit", "x" * 200]),
                client_id=rng.choice(["svc-a", "", "c" * 300]), src_identity=rng.choice([17, 99]),
            ) for _ in range(n_req)]
            rules = [(KafkaRule(api_key="fetch", topic="orders"), None),
                     (KafkaRule(role="produce", topic="audit", client_id="svc-a"), {17})]
            make = lambda: KafkaACL(rules)  # noqa: E731
        off = make().check_batch(reqs)
        l7rt.set_device_batch(True)
        batches0 = metrics.l7_batches_total.get({"parser": parser})
        on = make()
        assert on._fused_table is not None
        assert np.array_equal(off, on.check_batch(reqs))
        assert metrics.l7_batches_total.get({"parser": parser}) == batches0 + 1


class TestDeviceTransfers:
    def _table(self):
        return DeviceDFATable(("xfer",), fuse_dfas([compile_patterns(["/a.*"])]))

    @pytest.mark.parametrize("chunks", [1, 2, 3])
    def test_one_upload_and_one_pull_per_chunk(self, chunks):
        top = L7_LANE_RUNGS[-1]
        n = (chunks - 1) * top + 5
        pipe = L7Pipeline(depth=1)
        h2d0, d2h0 = _transfers("h2d", "kafka"), _transfers("d2h", "kafka")
        pending = pipe.submit(self._table(), [([b"/a"] * n, 16)], parser="kafka")
        assert (_transfers("h2d", "kafka") - h2d0, _transfers("d2h", "kafka") - d2h0) == (chunks, 0)
        (mask,) = pending.result()
        assert mask.shape == (n,) and (mask == 1).all()
        assert (_transfers("h2d", "kafka") - h2d0, _transfers("d2h", "kafka") - d2h0) == (chunks, chunks)

    def test_profiler_sampled_batch_counts_the_same(self):
        prof = DeviceProfiler(sample_every=1)
        pipe = L7Pipeline(depth=1)
        pipe.profiler = prof
        h2d0, d2h0 = _transfers("h2d"), _transfers("d2h")
        (mask,) = pipe.submit(self._table(), [([b"/a", b"/b"], 16)]).result()
        assert mask.tolist() == [1, 0]
        assert (_transfers("h2d") - h2d0, _transfers("d2h") - d2h0) == (1, 1)
        (sample,) = prof._ring
        assert sample.site == "l7" and sample.notes["chunks"] == 1
        assert sample.notes["rungs"] == [L7_LANE_RUNGS[0]]

    def test_prewarm_compiles_the_packed_programs(self, monkeypatch):
        """Prewarm compiles the packed walk at every rung submit can
        pick, so a request batch never runs an unwarmed program."""
        seen = []
        real = l7rt.dfa_match_packed_pair

        def spy(pair, lo, hi, packed, rung):
            seen.append((packed.shape, rung))
            return real(pair, lo, hi, packed, rung)
        monkeypatch.setattr(l7rt, "dfa_match_packed_pair", spy)
        table = _pack_table("pair")
        warmed = L7Pipeline(depth=1).prewarm(table, [8, 32])
        assert warmed == 2 * len(L7_LANE_RUNGS) == len(seen)
        assert sorted(seen) == sorted(
            ((lanes, rung + dfa_mod.PACK_HEADER), rung)
            for rung in (16, 32) for lanes in L7_LANE_RUNGS)
