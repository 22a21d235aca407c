"""policyd-sparse: O(k) selector/trie device deltas.

The correctness bar is VERDICT parity, not layout parity: a sparse
pipeline driven through a mutation stream must emit bit-identical
verdicts to a from-scratch dense build of the same world state —
including under 2D ident sharding (placed sel_match row/column
patches) and with conntrack replay at pipeline depth 2. The host
patchable-trie mirrors additionally get direct lookup-parity fuzzing
against the classic builders, whose arrays are the ground truth.

Reference analog: the ipcache BPF map's per-key upsert/delete
(pkg/ipcache/bpf.go) versus this repo's prior full-tensor rebuilds.
"""

from __future__ import annotations

import random

import jax.numpy as jnp
import numpy as np

from cilium_tpu import metrics as _metrics
from cilium_tpu.datapath import DatapathPipeline
from cilium_tpu.datapath.conntrack import FlowConntrack
from cilium_tpu.engine import PolicyEngine
from cilium_tpu.identity import IdentityRegistry
from cilium_tpu.ipcache import IPCache, SOURCE_AGENT
from cilium_tpu.labels import parse_label_array
from cilium_tpu.ops.lpm import (
    FLAT_TRIE_MAX_NODES,
    PatchableElidedTrie,
    PatchableFlatTrie,
    build_trie_elided,
    build_wide_trie,
    ip_strings_to_u32,
    ipv6_to_bytes,
    lpm_lookup,
    lpm_lookup_wide,
    make_patchable_wide,
)
from cilium_tpu.policy.api import (
    EndpointSelector,
    IngressRule,
    PortProtocol,
    PortRule,
    rule,
)
from cilium_tpu.policy.repository import Repository


def _canon(cidr: str) -> str:
    """Normalized network/plen key — the ipcache stores masked CIDR
    keys, so the fuzz universes must too (two spellings of one masked
    prefix would be distinct dict keys but one trie entry)."""
    import ipaddress

    return ipaddress.ip_network(cidr, strict=False).with_prefixlen


# ---------------------------------------------------------------------------
# host-level patchable-trie parity vs the classic builders


def _elided_lookup(arrs, ips):
    """Longest-match values for v6 addresses against (child, info,
    common) arrays — the elided-walk semantics the device kernel
    implements, so patched (pow2-padded) and classic (exact-sized)
    tries compare by RESULT, not layout."""
    child, info, common = arrs
    ab = ipv6_to_bytes(ips)
    k = int(np.asarray(common).shape[0])
    ok = np.ones(len(ips), bool)
    if k:
        ok = (ab[:, :k] == np.asarray(common)[None, :]).all(axis=1)
    out = np.asarray(
        lpm_lookup(
            jnp.asarray(child), jnp.asarray(info),
            jnp.asarray(ab[:, k:]), levels=16 - k,
        )
    )
    return np.where(ok, out, 0)


def _wide_lookup(arrs, addrs_u32):
    return np.asarray(
        lpm_lookup_wide(*(jnp.asarray(a) for a in arrs), jnp.asarray(addrs_u32))
    )


class TestPatchableElidedTrie:
    def _seed_set(self):
        # multi-level walk: plens 104..128 share 13 common bytes
        return [
            (f"fd00:aa::{i:x}:0/112", i) for i in range(1, 5)
        ] + [
            (f"fd00:aa::{i:x}/128", 16 + i) for i in range(1, 9)
        ] + [("fd00:aa::/104", 99)]

    def _probes(self, entries, rng):
        ips = [c.split("/")[0] for c, _ in entries]
        ips += [
            f"fd00:aa::{rng.randrange(16):x}:{rng.randrange(512):x}"
            for _ in range(64)
        ]
        ips += ["fd00:bb::1", "::1"]  # outside the elided common
        return ips

    def test_build_matches_classic(self):
        entries = self._seed_set()
        rng = random.Random(1)
        probes = self._probes(entries, rng)
        got = _elided_lookup(PatchableElidedTrie(entries).arrays(), probes)
        want = _elided_lookup(build_trie_elided(entries), probes)
        np.testing.assert_array_equal(got, want)

    def test_incremental_fuzz_matches_classic_rebuild(self):
        rng = random.Random(7)
        # seed every deep node path the universe below can touch: the
        # fuzz exercises in-place parity, not the pool-exhaustion
        # fallback (which demands a full rebuild and has its own test)
        entries = self._seed_set() + [
            # byte14=0 paths for a=0..3, canonical and disjoint from
            # both the seed /128s (::1..::8) and the universe (::a:0..3)
            (_canon(f"fd00:aa::{a:x}:b0/128"), 50 + a) for a in range(4)
        ]
        trie = PatchableElidedTrie(entries)
        dev_child, dev_info, common = (
            jnp.asarray(a) for a in trie.arrays()
        )
        live = dict(entries)
        universe = [
            (_canon(f"fd00:aa::{a:x}:{b:x}/{plen}"), rng.randrange(200))
            for a in range(4)
            for b in range(4)
            for plen in (112, 120, 128)
        ]
        for step in range(12):
            for _ in range(6):
                if live and rng.random() < 0.4:
                    victim = rng.choice(sorted(live))
                    assert trie.delete(victim)
                    del live[victim]
                else:
                    cidr, val = rng.choice(universe)
                    assert trie.insert(cidr, val), cidr
                    live[cidr] = val
            out = trie.flush(dev_child, dev_info)
            assert out is not None
            (dev_child, dev_info), nbytes = out
            assert nbytes > 0 and not trie.dirty
            probes = self._probes(sorted(live.items()), rng)
            got = _elided_lookup(
                (np.asarray(dev_child), np.asarray(dev_info), common),
                probes,
            )
            want = _elided_lookup(
                build_trie_elided(sorted(live.items())), probes
            )
            np.testing.assert_array_equal(got, want, err_msg=f"step {step}")

    def test_insert_outside_common_refuses(self):
        trie = PatchableElidedTrie(self._seed_set())
        # breaks the 13-byte elided prefix → full rebuild must recompute k
        assert not trie.insert("fd00:bb::1/128", 5)
        assert not trie.insert("fd00:aa::/64", 5)  # plen above the elision

    def test_upsert_overwrites_value(self):
        trie = PatchableElidedTrie([("fd00:aa::1/128", 3)])
        assert trie.insert("fd00:aa::1/128", 8)
        got = _elided_lookup(trie.arrays(), ["fd00:aa::1"])
        assert got[0] == 9  # value+1

    def test_node_pool_exhaustion_returns_false(self):
        trie = PatchableElidedTrie([("fd00::1/128", 0)])  # cap rows = 8
        ok = True
        for i in range(1, 64):
            ok = trie.insert(f"fd00::{i:x}:0:{i:x}/128", i)
            if not ok:
                break
        assert not ok, "pool must exhaust before 64 distinct deep paths"

    def test_flush_clean_is_zero_byte_noop(self):
        trie = PatchableElidedTrie(self._seed_set())
        c, i, _ = (jnp.asarray(a) for a in trie.arrays())
        (c2, i2), nbytes = trie.flush(c, i)
        assert nbytes == 0 and c2 is c and i2 is i

    def test_flush_shape_mismatch_returns_none(self):
        trie = PatchableElidedTrie(self._seed_set())
        trie.insert("fd00:aa::77/128", 7)
        assert trie.flush(jnp.zeros((2, 256), jnp.int32),
                          jnp.zeros((2, 256), jnp.int32)) is None


class TestPatchableWideTrie:
    def _seed_set(self):
        return (
            [(f"10.{i}.0.0/16", i) for i in range(3)]
            + [(f"10.0.{i}.0/24", 10 + i) for i in range(4)]
            + [(f"10.0.0.{i}/32", 20 + i) for i in range(1, 6)]
            + [("10.0.0.0/8", 99)]
        )

    def _probes(self, rng):
        ips = [
            f"10.{rng.randrange(4)}.{rng.randrange(5)}.{rng.randrange(8)}"
            for _ in range(96)
        ] + ["10.0.0.1", "10.3.3.3", "192.168.1.1", "0.0.0.0"]
        return ip_strings_to_u32(ips)

    def test_build_matches_classic(self):
        entries = self._seed_set()
        probes = self._probes(random.Random(2))
        trie = make_patchable_wide(entries)
        assert trie is not None
        np.testing.assert_array_equal(
            _wide_lookup(trie.arrays(), probes),
            _wide_lookup(build_wide_trie(entries), probes),
        )

    def test_incremental_fuzz_matches_classic_rebuild(self):
        rng = random.Random(11)
        entries = self._seed_set()
        trie = make_patchable_wide(entries)
        dev = tuple(jnp.asarray(a) for a in trie.arrays())
        live = dict(entries)
        universe = [
            (_canon(f"10.{a}.{b}.{c}/{plen}"), rng.randrange(200))
            for a in range(3)
            for b in range(3)
            for c in (0, 64, 128)
            for plen in (16, 24, 26, 32)
        ]
        for step in range(12):
            for _ in range(5):
                if live and rng.random() < 0.4:
                    victim = rng.choice(sorted(live))
                    assert trie.delete(victim)
                    del live[victim]
                else:
                    cidr, val = rng.choice(universe)
                    assert trie.insert(cidr, val), cidr
                    live[cidr] = val
            out = trie.flush(*dev)
            assert out is not None
            dev, nbytes = out
            assert nbytes > 0 and not trie.dirty
            probes = self._probes(rng)
            np.testing.assert_array_equal(
                _wide_lookup(tuple(np.asarray(a) for a in dev), probes),
                _wide_lookup(build_wide_trie(sorted(live.items())), probes),
                err_msg=f"step {step}",
            )

    def test_deep_node_budget_returns_none(self):
        # 16-8-8 pointer layout (too many deep /16 buckets) is not patched
        entries = [
            (f"10.{i // 256}.{i % 256}.0/24", i)
            for i in range(0, (FLAT_TRIE_MAX_NODES + 1) * 256, 256)
        ]
        assert len({int(e[0].split(".")[1]) for e in entries}) > FLAT_TRIE_MAX_NODES
        assert make_patchable_wide(entries) is None

    def test_node_pool_exhaustion_returns_false(self):
        trie = PatchableFlatTrie([((10 << 24) | (1 << 16), 24, 0)])
        oks = [trie.insert(f"10.{i}.0.0/24", i) for i in range(2, 8)]
        assert not all(oks), "spare-row cap must refuse new hi16 buckets"
        assert any(oks), "headroom must admit at least one new bucket"

    def test_delete_reexposes_shorter_prefix(self):
        trie = make_patchable_wide([("10.0.0.0/16", 1), ("10.0.7.0/24", 2)])
        probe = ip_strings_to_u32(["10.0.7.9"])
        assert _wide_lookup(trie.arrays(), probe)[0] == 3  # /24 wins
        assert trie.delete("10.0.7.0/24")
        assert _wide_lookup(trie.arrays(), probe)[0] == 2  # /16 resurfaces


# ---------------------------------------------------------------------------
# pipeline integration: sparse vs dense verdict parity


def _world(seed=0, n_rules=24, n_idents=12, *, sparse=True, **pipe_kw):
    rng = random.Random(seed)
    repo = Repository()
    rules = []
    for i in range(n_rules):
        subject = [f"k8s:app=a{rng.randrange(8)}"]
        peer = EndpointSelector.make([f"k8s:app=a{rng.randrange(8)}"])
        if i % 3 == 0:
            ing = IngressRule(
                from_endpoints=(peer,),
                to_ports=(PortRule(ports=(PortProtocol(80, "TCP"),)),),
            )
        else:
            ing = IngressRule(from_endpoints=(peer,))
        rules.append(rule(subject, ingress=[ing]))
    repo.add_list(rules)
    reg = IdentityRegistry()
    idents = [
        reg.allocate(
            parse_label_array([f"k8s:app=a{rng.randrange(8)}", f"k8s:z=z{i % 3}"])
        )
        for i in range(n_idents)
    ]
    engine = PolicyEngine(repo, reg)
    cache = IPCache()
    for i, ident in enumerate(idents):
        cache.upsert(f"10.0.{i // 250}.{i % 250 + 1}", ident.id, SOURCE_AGENT)
        cache.upsert(f"fd00:aa::{i + 1:x}", ident.id, SOURCE_AGENT)
    pipe = DatapathPipeline(engine, cache, sparse_deltas=sparse, **pipe_kw)
    pipe.set_endpoints([i.id for i in idents[:6]])
    return repo, reg, engine, cache, pipe, idents


def _flows(n_idents: int, b: int, seed: int, extra_ips=()):
    rng = np.random.default_rng(seed)
    ips = [
        f"10.0.{j // 250}.{j % 250 + 1}" for j in rng.integers(0, n_idents, b)
    ] + list(extra_ips)
    b = len(ips)
    src = ip_strings_to_u32(ips)
    ep = rng.integers(0, 6, b).astype(np.int32)
    dport = rng.choice(np.array([0, 80, 443], np.int32), b)
    proto = np.full(b, 6, np.int32)
    return (src, ep, dport, proto)


def _fresh_dense(repo, reg, cache, endpoints, **pipe_kw):
    engine = PolicyEngine(repo, reg)
    pipe = DatapathPipeline(engine, cache, sparse_deltas=False, **pipe_kw)
    pipe.set_endpoints(endpoints)
    return pipe


def _assert_parity(pipe, repo, reg, cache, idents, seed, extra_ips=(), **kw):
    flows = _flows(len(idents), 1024, seed, extra_ips)
    got_v, got_r = pipe.process(*flows)
    fresh = _fresh_dense(repo, reg, cache, [i.id for i in idents[:6]], **kw)
    want_v, want_r = fresh.process(*flows)
    np.testing.assert_array_equal(got_v, want_v)
    np.testing.assert_array_equal(got_r, want_r)


class TestPipelineSparseTries:
    def test_ipcache_churn_patches_not_rebuilds(self, monkeypatch):
        repo, reg, engine, cache, pipe, idents = _world(0)
        pipe.rebuild()
        assert pipe._trie_patch is not None
        p4, p6 = pipe._trie_patch[4], pipe._trie_patch[6]
        assert p4 is not None and p6 is not None
        before = _metrics.lpm_trie_patches_total.get({"family": "4"})

        # pure ipcache churn: no engine deltas, so the incremental
        # trie gate must take the O(delta) path
        cache.upsert("172.16.0.9", idents[3].id, SOURCE_AGENT)
        cache.upsert("fd00:aa::77", idents[4].id, SOURCE_AGENT)
        cache.delete(f"10.0.0.{len(idents)}", SOURCE_AGENT)
        pipe.rebuild()
        assert pipe._trie_patch[4] is p4, "v4 mirror must survive (patched)"
        assert pipe._trie_patch[6] is p6, "v6 mirror must survive (patched)"
        assert _metrics.lpm_trie_patches_total.get({"family": "4"}) > before

        live = idents[: len(idents) - 1]
        _assert_parity(
            pipe, repo, reg, cache, idents, 3,
            extra_ips=["172.16.0.9", "172.16.0.10"],
        )
        # v6 flows through the patched elided trie
        peers = ipv6_to_bytes(
            [f"fd00:aa::{i + 1:x}" for i in range(len(live))] + ["fd00:aa::77"]
        )
        b = peers.shape[0]
        ep = np.arange(b, dtype=np.int32) % 6
        v6_flows = (peers, ep, np.full(b, 80, np.int32), np.full(b, 6, np.int32))
        got = pipe.process_v6(*v6_flows)
        fresh = _fresh_dense(repo, reg, cache, [i.id for i in idents[:6]])
        want = fresh.process_v6(*v6_flows)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])

    def test_elision_violation_falls_back_to_full_rebuild(self):
        repo, reg, engine, cache, pipe, idents = _world(1)
        pipe.rebuild()
        p6 = pipe._trie_patch[6]
        # outside the elided fd00:aa:: common prefix: the mirror
        # refuses and the classic rebuild recomputes the elision
        cache.upsert("fd00:bb::1", idents[0].id, SOURCE_AGENT)
        pipe.rebuild()
        assert pipe._trie_patch[6] is not p6, "must have full-rebuilt"
        _assert_parity(pipe, repo, reg, cache, idents, 5)

    def test_fuzzed_mutation_stream_parity(self):
        repo, reg, engine, cache, pipe, idents = _world(2)
        pipe.rebuild()
        rng = random.Random(13)
        extra = []
        added_rules = 0
        new_idents = []
        for step in range(8):
            roll = rng.random()
            if roll < 0.3:  # ipcache-only churn (the patch path)
                ip = f"172.16.{step}.{rng.randrange(1, 200)}"
                cache.upsert(ip, rng.choice(idents).id, SOURCE_AGENT)
                extra.append(ip)
            elif roll < 0.55:  # identity churn (row events + trie follow)
                ident = reg.allocate(
                    parse_label_array(
                        [f"k8s:app=a{rng.randrange(8)}", f"k8s:fuzz=f{step}"]
                    )
                )
                new_idents.append(ident)
                ip = f"172.17.0.{step + 1}"
                cache.upsert(ip, ident.id, SOURCE_AGENT)
                extra.append(ip)
                engine.refresh()
            elif roll < 0.8:  # rule append with a new selector
                repo.add_list([
                    rule(
                        [f"k8s:app=a{rng.randrange(8)}"],
                        ingress=[IngressRule(from_endpoints=(
                            EndpointSelector.make([f"k8s:fuzz=f{step}"]),
                        ),)],
                        labels=[f"k8s:policy=fuzz-{step}"],
                    )
                ])
                added_rules += 1
                engine.refresh()
            elif new_idents:  # identity release
                ident = new_idents.pop(rng.randrange(len(new_idents)))
                reg.release(ident)
                engine.refresh()
            pipe.rebuild()
            _assert_parity(
                pipe, repo, reg, cache, idents, 100 + step, extra_ips=extra
            )

    def test_kill_switch_off_never_touches_patch_paths(self, monkeypatch):
        repo, reg, engine, cache, pipe, idents = _world(3, sparse=False)
        import cilium_tpu.datapath.pipeline as plmod

        def boom(*a, **kw):
            raise AssertionError("sparse patch path reached while OFF")

        monkeypatch.setattr(plmod.DatapathPipeline, "_patch_tries_locked", boom)
        monkeypatch.setattr(plmod.DatapathPipeline, "_patch_placed_sel", boom)
        monkeypatch.setattr(plmod, "patch_selector_cols", boom)
        monkeypatch.setattr(plmod, "patch_selector_rows", boom)
        monkeypatch.setattr(plmod, "PatchableElidedTrie", boom)
        monkeypatch.setattr(plmod, "make_patchable_wide", boom)
        pipe.rebuild()
        assert pipe._trie_patch is None
        cache.upsert("172.16.0.9", idents[3].id, SOURCE_AGENT)
        ident = reg.allocate(parse_label_array(["k8s:app=a1", "k8s:off=y"]))
        engine.refresh()
        pipe.rebuild()
        assert pipe._trie_patch is None
        _assert_parity(
            pipe, repo, reg, cache, idents, 7, extra_ips=["172.16.0.9"]
        )

    def test_toggle_drops_and_rebuilds_patch_state(self):
        repo, reg, engine, cache, pipe, idents = _world(4, sparse=False)
        pipe.rebuild()
        assert pipe._trie_patch is None
        pipe.set_sparse_deltas(True)
        pipe.rebuild()
        assert pipe._trie_patch is not None
        assert pipe._trie_patch[4] is not None
        pipe.set_sparse_deltas(False)
        pipe.rebuild()
        assert pipe._trie_patch is None
        _assert_parity(pipe, repo, reg, cache, idents, 9)


class TestSparse2DPlacement:
    def test_ident_sharded_row_patch_parity(self, monkeypatch):
        repo, reg, engine, cache, pipe, idents = _world(
            5, sparse=True, sharding=True, mesh_2d=True,
        )
        import cilium_tpu.datapath.pipeline as plmod

        calls = []
        orig_rows = plmod.patch_selector_rows

        def spy_rows(*a, **kw):
            calls.append("rows")
            return orig_rows(*a, **kw)

        monkeypatch.setattr(plmod, "patch_selector_rows", spy_rows)
        pipe.rebuild()
        pipe.process(*_flows(len(idents), 256, 1))  # prime placed caches

        # identity churn: a "rows" delta must patch the cached
        # ident-placed sel_match copy, not re-place the matrix
        ident = reg.allocate(parse_label_array(["k8s:app=a2", "k8s:mesh=m1"]))
        cache.upsert("172.18.0.1", ident.id, SOURCE_AGENT)
        engine.refresh()
        pipe.rebuild()
        assert calls, "2D ident-placed sel_match must take the row patch"
        plan = pipe._plan
        placed = pipe._placed_sel[2]
        assert placed is not None
        assert placed.sharding.spec == plan.ident_sharding.spec, (
            "patch must preserve the ident sharding (jit caches survive)"
        )
        _assert_parity(
            pipe, repo, reg, cache, idents, 11, extra_ips=["172.18.0.1"],
            sharding=True, mesh_2d=True,
        )


class TestSparseCTReplay:
    def test_depth2_ct_replay_parity(self):
        """Sparse and dense pipelines driven through the SAME batch +
        mutation sequence at pipeline depth 2 with conntrack: CT
        creation from patched tables must agree with the dense build
        (established-entry bypass replays old verdicts identically)."""
        repo, reg, engine, cache, pipe, idents = _world(
            6, sparse=True,
            conntrack=FlowConntrack(capacity_bits=12), pipeline_depth=2,
        )
        dense = DatapathPipeline(
            engine, cache, sparse_deltas=False,
            conntrack=FlowConntrack(capacity_bits=12), pipeline_depth=2,
        )
        dense.set_endpoints([i.id for i in idents[:6]])
        for p in (pipe, dense):
            p.rebuild()

        rng = np.random.default_rng(21)
        def batch(seed, extra=()):
            src, ep, dport, proto = _flows(len(idents), 512, seed, extra)
            sports = rng.integers(1024, 60000, src.shape[0]).astype(np.int32)
            return src, ep, dport, proto, sports

        src, ep, dport, proto, sports = batch(1)
        va = pipe.process(src, ep, dport, proto, sports=sports)
        vb = dense.process(src, ep, dport, proto, sports=sports)
        np.testing.assert_array_equal(va[0], vb[0])

        # mutate: ipcache churn + identity churn, both pipelines rebuild
        cache.upsert("172.19.0.1", idents[2].id, SOURCE_AGENT)
        ident = reg.allocate(parse_label_array(["k8s:app=a3", "k8s:ct=c1"]))
        cache.upsert("172.19.0.2", ident.id, SOURCE_AGENT)
        engine.refresh()
        pipe.rebuild()
        dense.rebuild()

        # replay the same 5-tuples (CT hits) plus fresh flows
        src2, ep2, dport2, proto2, sports2 = batch(
            2, ["172.19.0.1", "172.19.0.2"]
        )
        for s, e, d, pr, sp in (
            (src, ep, dport, proto, sports),
            (src2, ep2, dport2, proto2, sports2),
        ):
            va = pipe.process(s, e, d, pr, sports=sp)
            vb = dense.process(s, e, d, pr, sports=sp)
            np.testing.assert_array_equal(va[0], vb[0])
            np.testing.assert_array_equal(va[1], vb[1])


# ---------------------------------------------------------------------------
class TestSparseDeltasOption:
    def test_sparse_deltas_daemon_patch_tripwire(self, tmp_path):
        """OPT001 tripwire: the "SparseDeltas" option must be reachable
        through the daemon's config-patch surface, flip the pipeline
        flag both ways, and land back on the exact pre-option layout
        (OFF-path bit-identical contract, ROADMAP)."""
        from cilium_tpu.daemon import Daemon

        d = Daemon(state_dir=str(tmp_path), conntrack=False)
        try:
            assert d.pipeline._sparse_deltas is False
            out = d.config_patch({"SparseDeltas": "true"})
            assert "SparseDeltas" in out["changed"]
            assert d.pipeline._sparse_deltas is True
            # toggling ON drops the trie/placement caches so the next
            # rebuild constructs the patchable mirrors from scratch
            assert d.pipeline._tries is None
            assert d.pipeline._trie_patch is None
            out = d.config_patch({"SparseDeltas": "false"})
            assert "SparseDeltas" in out["changed"]
            assert d.pipeline._sparse_deltas is False
            # OFF sheds the pow2 headroom: classic exact-size tries
            # rebuild on the next tick, no patch state lingers
            assert d.pipeline._tries is None
            assert d.pipeline._trie_patch is None
        finally:
            d.shutdown()
