"""LPM trie, ipcache, prefilter, and datapath pipeline tests.

Differential: the device stride-8 trie must agree with a host LPM walk
over random prefix sets (the kernel LPM_TRIE contract of cilium_ipcache,
bpf/lib/maps.h); the pipeline must agree with the policy engine on
verdicts after identity derivation.
"""

from __future__ import annotations

import ipaddress
import random

import numpy as np
import jax.numpy as jnp
import pytest

from cilium_tpu.ipcache import IPCache, PreFilter, SOURCE_AGENT, SOURCE_K8S, SOURCE_KVSTORE
from cilium_tpu.ops.lpm import build_trie, ipv4_to_bytes, ip_strings_to_u32, lpm_lookup


class TestTrie:
    def test_basic_lpm(self):
        child, info = build_trie(
            [("10.0.0.0/8", 1), ("10.1.0.0/16", 2), ("10.1.2.0/24", 3), ("0.0.0.0/0", 9)]
        )
        ips = ip_strings_to_u32(["10.1.2.3", "10.1.9.9", "10.9.9.9", "8.8.8.8"])
        got = np.asarray(lpm_lookup(jnp.asarray(child), jnp.asarray(info), jnp.asarray(ipv4_to_bytes(ips))))
        assert list(got - 1) == [3, 2, 1, 9]

    def test_non_octet_prefixes(self):
        child, info = build_trie([("192.168.128.0/17", 5), ("192.168.0.0/20", 6)])
        ips = ip_strings_to_u32(["192.168.200.1", "192.168.1.1", "192.168.100.1"])
        got = np.asarray(lpm_lookup(jnp.asarray(child), jnp.asarray(info), jnp.asarray(ipv4_to_bytes(ips))))
        assert list(got) == [6, 7, 0]

    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_differential(self, seed):
        rng = random.Random(seed)
        prefixes = []
        for i in range(300):
            plen = rng.choice([8, 12, 16, 20, 24, 28, 32])
            addr = ipaddress.ip_address(rng.getrandbits(32))
            net = ipaddress.ip_network(f"{addr}/{plen}", strict=False)
            prefixes.append((str(net), i))
        # dedupe: last writer wins in both oracle and trie
        nets = {p: v for p, v in prefixes}
        child, info = build_trie(list(nets.items()))
        probe = [str(ipaddress.ip_address(rng.getrandbits(32))) for _ in range(500)]
        probe += [p.split("/")[0] for p in list(nets)[:100]]
        got = np.asarray(
            lpm_lookup(jnp.asarray(child), jnp.asarray(info), jnp.asarray(ipv4_to_bytes(ip_strings_to_u32(probe))))
        )
        parsed = [(ipaddress.ip_network(p), v) for p, v in nets.items()]
        for ip_s, g in zip(probe, got):
            ip = ipaddress.ip_address(ip_s)
            best, best_len = 0, -1
            for net, v in parsed:
                if ip in net and net.prefixlen > best_len:
                    best, best_len = v + 1, net.prefixlen
            assert int(g) == best, f"{ip_s}: trie={int(g)} oracle={best}"


class TestIPCache:
    def test_source_priority(self):
        c = IPCache()
        assert c.upsert("10.0.0.1", 100, SOURCE_K8S)
        assert c.upsert("10.0.0.1", 200, SOURCE_KVSTORE)  # kvstore beats k8s
        assert not c.upsert("10.0.0.1", 300, SOURCE_K8S)  # k8s can't downgrade
        assert c.lookup_exact("10.0.0.1/32").identity == 200
        assert not c.delete("10.0.0.1", SOURCE_K8S)
        assert c.delete("10.0.0.1", SOURCE_AGENT)
        assert c.lookup_exact("10.0.0.1") is None

    def test_lpm_lookup_host(self):
        c = IPCache()
        c.upsert("10.0.0.0/8", 7, SOURCE_AGENT)
        c.upsert("10.1.0.0/16", 8, SOURCE_AGENT)
        assert c.lookup_by_ip("10.1.2.3").identity == 8
        assert c.lookup_by_ip("10.200.0.1").identity == 7
        assert c.lookup_by_ip("11.0.0.1") is None

    @pytest.mark.parametrize("family", [4, 6])
    def test_lpm_lookups_match_a_plain_walk(self, family):
        def plain(c, ip):
            # every prefix length, longest first, each parsed as a network
            for plen in range(bits, -1, -1):
                e = c.lookup_exact(f"{ip}/{plen}")
                if e is not None:
                    return e
            return None

        rng = random.Random(family)
        bits = 32 if family == 4 else 128
        base = int(ipaddress.ip_address("10.0.0.0" if family == 4 else "fd00::"))
        cls = ipaddress.IPv4Address if family == 4 else ipaddress.IPv6Address
        c = IPCache()
        # nested prefixes of random lengths inside one /8, host entries
        # among them; then a default route
        addrs = [cls(base | rng.getrandbits(bits - 8)) for _ in range(300)]
        for i, a in enumerate(addrs[:150]):
            plen = rng.randint(12, bits)
            c.upsert(str(ipaddress.ip_network(f"{a}/{plen}", strict=False)), i + 1, SOURCE_K8S)
        for default in (False, True):
            if default:
                c.upsert("0.0.0.0/0" if family == 4 else "::/0", 999, SOURCE_K8S)
            want = [plain(c, a) for a in addrs]
            assert c.lookup_many(addrs) == want
            assert [c.lookup_by_ip(str(a)) for a in addrs] == want
            assert (None in want) != default
            assert any(e is not None and e.identity != 999 for e in want)
        assert any(e.identity == 999 for e in want)

    def test_listeners_and_identity_index(self):
        c = IPCache()
        events = []
        c.add_listener(lambda cidr, old, new: events.append((cidr, old, new)))
        c.upsert("10.0.0.1", 5, SOURCE_AGENT)
        c.upsert("10.0.0.2", 5, SOURCE_AGENT)
        assert sorted(c.prefixes_for_identity(5)) == ["10.0.0.1/32", "10.0.0.2/32"]
        c.delete("10.0.0.1", SOURCE_AGENT)
        assert c.prefixes_for_identity(5) == ["10.0.0.2/32"]
        assert len(events) == 3
        # replay for late listener
        late = []
        c.add_listener(lambda cidr, old, new: late.append(cidr), replay=True)
        assert late == ["10.0.0.2/32"]


class TestPreFilter:
    def test_revision_guard(self):
        pf = PreFilter()
        rev = pf.revision
        rev = pf.insert(rev, ["10.0.0.0/8", "1.2.3.4/32"])
        with pytest.raises(ValueError):
            pf.insert(rev - 1, ["2.0.0.0/8"])
        rev2, cidrs = pf.dump()
        assert rev2 == rev and "10.0.0.0/8" in cidrs and "1.2.3.4/32" in cidrs
        pf.delete(rev, ["10.0.0.0/8"])
        assert "10.0.0.0/8" not in pf.dump()[1]


class TestPipeline:
    def _world(self):
        from cilium_tpu.engine import PolicyEngine
        from cilium_tpu.identity import IdentityRegistry
        from cilium_tpu.labels import parse_label_array
        from cilium_tpu.policy.api import EndpointSelector, IngressRule, PortProtocol, PortRule, rule
        from cilium_tpu.policy.repository import Repository
        from cilium_tpu.datapath import DatapathPipeline

        repo = Repository()
        repo.add_list([
            rule(["k8s:app=b"], ingress=[
                IngressRule(from_endpoints=(EndpointSelector.make(["k8s:app=a"]),)),
                IngressRule(from_entities=("world",),
                            to_ports=(PortRule(ports=(PortProtocol(443, "TCP"),)),)),
            ]),
        ])
        reg = IdentityRegistry()
        a = reg.allocate(parse_label_array(["k8s:app=a"]))
        b = reg.allocate(parse_label_array(["k8s:app=b"]))
        engine = PolicyEngine(repo, reg)
        cache = IPCache()
        cache.upsert("10.0.0.1", a.id, SOURCE_AGENT)
        cache.upsert("10.0.0.2", b.id, SOURCE_AGENT)
        pipe = DatapathPipeline(engine, cache)
        pipe.set_endpoints([b.id])
        return pipe, a, b

    def test_end_to_end_verdicts(self):
        from cilium_tpu.datapath import DROP_POLICY, DROP_PREFILTER, FORWARD

        pipe, a, b = self._world()
        ips = ip_strings_to_u32(["10.0.0.1", "8.8.8.8", "10.0.0.1", "8.8.8.8"])
        eps = np.zeros(4, np.int32)
        ports = np.array([0, 0, 443, 443], np.int32)
        protos = np.array([6, 6, 6, 6], np.int32)
        v, red = pipe.process(ips, eps, ports, protos)
        # a → b allowed at L3; world denied at L3; both allowed on 443
        # (world via entity rule; a via... a is not world → L3 allow).
        assert list(v) == [FORWARD, DROP_POLICY, FORWARD, FORWARD]
        assert pipe.counters[0, 0] == 3 and pipe.counters[0, 1] == 1

    def test_prefilter_drop(self):
        from cilium_tpu.datapath import DROP_PREFILTER, FORWARD

        pipe, a, b = self._world()
        rev = pipe.prefilter.revision
        pipe.prefilter.insert(rev, ["10.0.0.0/24"])
        ips = ip_strings_to_u32(["10.0.0.1", "8.8.8.8"])
        v, _ = pipe.process(ips, np.zeros(2, np.int32), np.array([443, 443], np.int32), np.full(2, 6, np.int32))
        assert list(v) == [DROP_PREFILTER, FORWARD]

    def test_rebuild_on_ipcache_change(self):
        from cilium_tpu.datapath import DROP_POLICY, FORWARD

        pipe, a, b = self._world()
        ips = ip_strings_to_u32(["10.0.0.9"])
        v, _ = pipe.process(ips, np.zeros(1, np.int32), np.zeros(1, np.int32), np.full(1, 6, np.int32))
        assert list(v) == [DROP_POLICY]  # unknown ip → world → denied at L3
        pipe.ipcache.upsert("10.0.0.9", a.id, SOURCE_AGENT)
        v, _ = pipe.process(ips, np.zeros(1, np.int32), np.zeros(1, np.int32), np.full(1, 6, np.int32))
        assert list(v) == [FORWARD]


class TestWideTrie:
    def test_wide_matches_stride8_on_random_prefixes(self):
        """The IPv4 wide trie (dense 16-bit first stride) must agree
        with the stride-8 trie on every query — same LPM semantics,
        different layout."""
        import numpy as np

        from cilium_tpu.ops.lpm import (
            build_trie,
            build_wide_trie,
            ipv4_to_bytes,
            lpm_lookup,
            lpm_lookup_wide,
        )

        rng = np.random.default_rng(17)
        prefixes = []
        for i in range(3000):
            a = int(rng.integers(0, 2**32))
            pl = int(rng.choice([0, 5, 8, 12, 15, 16, 17, 20, 24, 28, 31, 32]))
            a &= (0xFFFFFFFF << (32 - pl)) & 0xFFFFFFFF if pl else 0
            import ipaddress

            prefixes.append((f"{ipaddress.ip_address(a)}/{pl}", i % 60000))
        child, info = build_trie(prefixes, ipv6=False)
        wide = build_wide_trie(prefixes)
        import jax.numpy as jnp

        q = rng.integers(0, 2**32, 20000, dtype=np.uint64).astype(np.uint32)
        # bias half the queries INTO covered space so matches happen
        hit_targets = rng.integers(0, len(prefixes), 10000)
        import ipaddress as _ipa

        for j, t in enumerate(hit_targets):
            net = _ipa.ip_network(prefixes[t][0], strict=False)
            q[j] = int(net.network_address) + int(
                rng.integers(0, max(1, min(net.num_addresses, 1000)))
            )
        r8 = lpm_lookup(
            jnp.asarray(child), jnp.asarray(info),
            jnp.asarray(ipv4_to_bytes(q)), levels=4,
        )
        rw = lpm_lookup_wide(*(jnp.asarray(a) for a in wide), jnp.asarray(q))
        assert np.array_equal(np.asarray(r8), np.asarray(rw))
        assert (np.asarray(r8) > 0).sum() > 5000  # matches actually occur


class TestFlatTrieParity:
    def test_flat_and_wide_layouts_agree(self):
        """build_wide_trie's two layouts (2-gather flat 16+16 vs
        3-gather 16-8-8) must return identical LPM results on the same
        prefix set — the layout switch at FLAT_TRIE_MAX_NODES must
        never change semantics."""
        import numpy as np

        import jax.numpy as jnp

        from cilium_tpu.ops.lpm import (
            FlatTrieBuilder,
            WideTrieBuilder,
            lpm_lookup_wide,
        )

        rng = np.random.default_rng(21)
        hi16 = rng.integers(0, 2**16, 9, dtype=np.uint64).astype(np.uint32)
        n = 4000
        addrs = (
            (rng.choice(hi16, n) << np.uint32(16))
            | rng.integers(0, 2**16, n, dtype=np.uint64).astype(np.uint32)
        )
        plens = rng.choice(np.array([8, 12, 16, 17, 20, 24, 28, 31, 32]), n)
        flat, wide = FlatTrieBuilder(), WideTrieBuilder()
        for a, pl in zip(addrs.tolist(), plens.tolist()):
            flat.insert(a, pl, a % 60000)
            wide.insert(a, pl, a % 60000)
        q = np.concatenate([
            addrs[:2000],  # exact hits
            (rng.choice(hi16, 2000) << np.uint32(16))
            | rng.integers(0, 2**16, 2000, dtype=np.uint64).astype(np.uint32),
            rng.integers(0, 2**32, 2000, dtype=np.uint64).astype(np.uint32),
        ]).astype(np.uint32)
        rf = np.asarray(lpm_lookup_wide(*[jnp.asarray(a) for a in flat.arrays()], jnp.asarray(q)))
        rw = np.asarray(lpm_lookup_wide(*[jnp.asarray(a) for a in wide.arrays()], jnp.asarray(q)))
        assert flat.arrays()[3].shape[-1] == 65536  # flat layout actually built
        assert wide.arrays()[3].shape[-1] == 256
        np.testing.assert_array_equal(rf, rw)


class TestElidedV6Trie:
    def test_elided_matches_full_walk(self):
        """build_trie_elided must agree with the full 16-level walk on
        in-prefix, out-of-prefix, and miss addresses — and a shorter
        prefix in the set must disable (shrink) the elision rather
        than break matching."""
        import numpy as np

        import jax.numpy as jnp

        from cilium_tpu.ops.lpm import (
            build_trie,
            build_trie_elided,
            ipv6_to_bytes,
            lpm_lookup,
        )

        prefixes = [
            ("fd00:aa::1/128", 5),
            ("fd00:aa::2/128", 6),
            ("fd00:aa::/64", 7),
            ("fd00:aa:0:1::/64", 8),
        ]
        queries = ipv6_to_bytes([
            "fd00:aa::1", "fd00:aa::2", "fd00:aa::9",  # under /64
            "fd00:aa:0:1::42",                          # second /64
            "fd00:bb::1", "2001:db8::1",                # outside common
        ])
        full = np.asarray(lpm_lookup(
            *[jnp.asarray(a) for a in build_trie(prefixes, ipv6=True)],
            jnp.asarray(queries), levels=16,
        ))
        child, info, common = build_trie_elided(prefixes, ipv6=True)
        k = common.shape[0]
        assert k > 0  # elision actually engaged
        sub = np.asarray(lpm_lookup(
            jnp.asarray(child), jnp.asarray(info),
            jnp.asarray(queries[:, k:]), levels=16 - k,
        ))
        ok = (queries[:, :k] == common[None, :]).all(axis=1)
        elided = np.where(ok, sub, 0)
        np.testing.assert_array_equal(elided, full)
        assert full[0] == 6 and full[1] == 7  # value+1 of the /128s
        assert full[4] == 0 and full[5] == 0

        # a wide deny (fd00::/16-ish) must shrink the elision
        child2, info2, common2 = build_trie_elided(
            prefixes + [("fd00::/16", 9)], ipv6=True
        )
        assert common2.shape[0] <= 2
        q2 = ipv6_to_bytes(["fd00:bb::1"])
        k2 = common2.shape[0]
        hit = np.asarray(lpm_lookup(
            jnp.asarray(child2), jnp.asarray(info2),
            jnp.asarray(q2[:, k2:]), levels=16 - k2,
        ))
        ok2 = (q2[:, :k2] == common2[None, :]).all(axis=1)
        assert np.where(ok2, hit, 0)[0] == 10  # the /16 catches it


class TestMergedDenyIdentityTrie:
    """The fused deny+identity flat walk (ops/lpm.py merge_flat_tries):
    one 2-gather pass must agree with the two classic walks on every
    address — including deny prefixes shadowed by longer identity
    prefixes (the case a naive set-union merge gets wrong)."""

    def _arrays(self, prefixes):
        from cilium_tpu.ops.lpm import build_wide_trie

        return build_wide_trie(prefixes)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_merged_walk_parity_fuzz(self, seed):
        import jax.numpy as jnp

        from cilium_tpu.ops.lpm import (
            DENY_BIT,
            MERGED_VALUE_MASK,
            lpm_lookup_wide,
            merge_flat_tries,
        )

        rng = np.random.default_rng(seed)
        # identity prefixes: /32 pods under a handful of /16s + some
        # broader allocations
        ip_prefixes = []
        for i in range(600):
            a, b = int(rng.integers(0, 4)), int(rng.integers(0, 256))
            ip_prefixes.append(
                (f"10.{a}.{b}.{int(rng.integers(1, 255))}/32", i + 1)
            )
        ip_prefixes += [("10.9.0.0/16", 7000), ("172.16.0.0/12", 7001)]
        # deny prefixes: some INSIDE identity space (shadowing cases),
        # some outside, various lengths
        deny = [
            ("10.0.7.0/24", 0), ("10.1.0.0/16", 0), ("192.0.2.0/24", 0),
            ("10.9.128.0/17", 0), ("0.0.0.0/5", 0),
            (f"10.2.{int(rng.integers(0, 256))}.0/28", 0),
        ]
        ipa = self._arrays(ip_prefixes)
        dna = self._arrays(deny)
        merged = merge_flat_tries(ipa, dna)
        assert merged is not None, "expected flat layouts"

        b = 4096
        pool = []
        for cidr, _v in ip_prefixes + deny:
            base = int(ipaddress.ip_network(cidr).network_address)
            pool += [base, base + 1, base + 255]
        pool = np.asarray(pool, np.uint32)
        q = np.concatenate([
            pool[rng.integers(0, len(pool), b // 2)],
            rng.integers(0, 2 ** 32, b // 2, dtype=np.uint64).astype(
                np.uint32
            ),
        ])
        qj = jnp.asarray(q)
        base_hit = np.asarray(lpm_lookup_wide(
            *[jnp.asarray(a) for a in ipa], qj
        ))
        base_deny = np.asarray(lpm_lookup_wide(
            *[jnp.asarray(a) for a in dna], qj
        )) > 0
        packed = np.asarray(lpm_lookup_wide(
            *[jnp.asarray(a) for a in merged], qj
        ))
        np.testing.assert_array_equal(packed & MERGED_VALUE_MASK, base_hit)
        np.testing.assert_array_equal((packed & DENY_BIT) != 0, base_deny)
        # the fuzz must exercise all four (identity?, denied?) quadrants
        quads = {
            (bool(h), bool(d)) for h, d in zip(base_hit > 0, base_deny)
        }
        assert len(quads) == 4, quads

    def test_pipeline_fused_verdicts_match_unfused(self):
        """End to end: a pipeline with a live prefilter must produce
        identical verdicts whether or not the fused table is present
        (the fused path self-selects; force-compare by stripping it)."""
        import dataclasses as _dc

        import jax.numpy as jnp

        from cilium_tpu.datapath.pipeline import (
            TRAFFIC_INGRESS,
            DatapathPipeline,
            process_flows_wide,
        )
        from cilium_tpu.engine import PolicyEngine
        from cilium_tpu.identity import IdentityRegistry
        from cilium_tpu.ipcache.ipcache import IPCache
        from cilium_tpu.ipcache.prefilter import PreFilter
        from cilium_tpu.labels import parse_label_array
        from cilium_tpu.policy.api import EndpointSelector, IngressRule, rule
        from cilium_tpu.policy.repository import Repository

        repo = Repository()
        repo.add_list([rule(
            ["k8s:app=web"],
            ingress=[IngressRule(from_endpoints=(
                EndpointSelector.make(["k8s:app=client"]),
            ))],
        )])
        reg = IdentityRegistry()
        idents = [
            reg.allocate(parse_label_array([f"k8s:app={n}"]))
            for n in ("web", "client", "other")
        ]
        engine = PolicyEngine(repo, reg)
        cache = IPCache()
        for i, ident in enumerate(idents):
            cache.upsert(f"10.0.0.{i + 1}/32", ident.id, source="k8s")
        pf = PreFilter()
        # deny "other"'s address + an external range; the client's
        # (10.0.0.2) stays clean so the allow quadrant is exercised
        pf.insert(pf.revision, ["10.0.0.3/32", "192.0.2.0/24"])
        pipe = DatapathPipeline(engine, cache, pf, conntrack=None)
        pipe.set_endpoints([idents[0].id])
        pipe.rebuild()
        t = pipe._tables[(TRAFFIC_INGRESS, 4)]
        assert t.merged_sub_info.shape[-1] == 65536, "fusion not built"

        rng = np.random.default_rng(4)
        b = 2048
        pool = np.asarray([
            (10 << 24) | 1, (10 << 24) | 2, (10 << 24) | 3,
            (192 << 24) | (0 << 16) | (2 << 8) | 9,
            (8 << 24) | (8 << 16) | (8 << 8) | 8,
        ], np.uint32)
        peers = jnp.asarray(pool[rng.integers(0, len(pool), b)])
        eps = jnp.asarray(np.zeros(b, np.int32))
        dports = jnp.asarray(np.full(b, 80, np.int32))
        protos = jnp.asarray(np.full(b, 6, np.int32))
        v_fused, r_fused, c_fused = process_flows_wide(
            t, peers, eps, dports, protos, ep_count=1, prefilter=True
        )
        # a genuinely UNFUSED pipeline over the same world (fusion
        # disabled → the classic two-walk tables get built/uploaded)
        import cilium_tpu.datapath.pipeline as _pl

        orig_merge = _pl.merge_flat_tries
        _pl.merge_flat_tries = lambda *_a, **_k: None
        try:
            pipe_u = DatapathPipeline(engine, cache, pf, conntrack=None)
            pipe_u.set_endpoints([idents[0].id])
            pipe_u.rebuild()
        finally:
            _pl.merge_flat_tries = orig_merge
        t_u = pipe_u._tables[(TRAFFIC_INGRESS, 4)]
        assert t_u.merged_sub_info.shape[-1] == 1  # fusion absent
        v_base, r_base, c_base = process_flows_wide(
            t_u, peers, eps, dports, protos, ep_count=1, prefilter=True
        )
        np.testing.assert_array_equal(np.asarray(v_fused), np.asarray(v_base))
        np.testing.assert_array_equal(np.asarray(r_fused), np.asarray(r_base))
        np.testing.assert_array_equal(np.asarray(c_fused), np.asarray(c_base))
        # the batch exercises allow, policy-deny, AND prefilter-drop
        assert len(set(np.asarray(v_fused).tolist())) >= 3


class TestMergedV6Trie:
    """The fused v6 deny+identity elided walk (ops/lpm.py
    merge_trie_entries → build_trie_elided): one stride-8 pass must
    agree with the two classic walks on every address."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_merged_v6_parity_fuzz(self, seed):
        from cilium_tpu.ops.lpm import (
            DENY_BIT,
            MERGED_VALUE_MASK,
            build_trie_elided,
            lpm_lookup,
            merge_trie_entries,
        )

        rng = np.random.default_rng(seed)
        ip_prefixes = []
        for i in range(400):
            a, b = int(rng.integers(0, 4)), int(rng.integers(0, 256))
            ip_prefixes.append(
                (f"fd00:{a:x}::{b:x}:{int(rng.integers(1, 255)):x}/128",
                 i + 1)
            )
        ip_prefixes += [("fd00:9::/32", 9000), ("2001:db8::/32", 9001)]
        deny = [
            ("fd00:1::/32", 0),              # whole identity /32 denied
            (f"fd00:2::{int(rng.integers(0, 256)):x}:0/112", 0),
            ("2001:db8:dead::/48", 0),       # inside a broad identity
            ("fc00::/7", 0),                 # covers everything fd00::
        ]
        if seed == 2:
            deny = deny[:2]  # variant without the broad /7
        ipa = build_trie_elided(ip_prefixes, ipv6=True)
        dna = build_trie_elided(deny, ipv6=True)
        merged_list = merge_trie_entries(ip_prefixes, deny, ipv6=True)
        assert merged_list is not None
        mrg = build_trie_elided(merged_list, ipv6=True)

        def walk(arrays, q):
            child, info, common = [jnp.asarray(a) for a in arrays]
            k = common.shape[0]
            hit = lpm_lookup(child, info, q[:, k:], levels=16 - k)
            if k:
                ok = jnp.all(q[:, :k] == common[None, :], axis=1)
                hit = jnp.where(ok, hit, 0)
            return np.asarray(hit)

        b = 2048
        pool = []
        for cidr, _v in ip_prefixes + deny:
            base = ipaddress.ip_network(cidr, strict=False).network_address
            pool.append(base.packed)
            pool.append((int(base) + 1).to_bytes(16, "big"))
        qs = [pool[int(i)] for i in rng.integers(0, len(pool), b // 2)]
        qs += [bytes(rng.integers(0, 256, 16, dtype=np.uint8).tolist())
               for _ in range(b // 2)]
        q = jnp.asarray(np.array([list(x) for x in qs], np.int32))

        base_hit = walk(ipa, q)
        base_deny = walk(dna, q) > 0
        raw = walk(mrg, q)
        packed = np.where(raw > 0, raw - 1, 0)
        np.testing.assert_array_equal(packed & MERGED_VALUE_MASK, base_hit)
        np.testing.assert_array_equal((packed & DENY_BIT) != 0, base_deny)
        quads = {(bool(h), bool(d)) for h, d in zip(base_hit > 0, base_deny)}
        assert len(quads) >= 3, quads

    def test_pipeline_v6_fused_matches_unfused(self):
        """process_flows with fused=True over the built merged tables
        must equal fused=False over the classic tables, end to end."""
        from cilium_tpu.datapath.pipeline import (
            TRAFFIC_INGRESS,
            DatapathPipeline,
            process_flows,
        )
        from cilium_tpu.engine import PolicyEngine
        from cilium_tpu.identity import IdentityRegistry
        from cilium_tpu.ipcache.ipcache import IPCache
        from cilium_tpu.ipcache.prefilter import PreFilter
        from cilium_tpu.labels import parse_label_array
        from cilium_tpu.policy.api import EndpointSelector, IngressRule, rule
        from cilium_tpu.policy.repository import Repository

        repo = Repository()
        repo.add_list([rule(
            ["k8s:app=web"],
            ingress=[IngressRule(from_endpoints=(
                EndpointSelector.make(["k8s:app=client"]),
            ))],
        )])
        reg = IdentityRegistry()
        idents = [
            reg.allocate(parse_label_array([f"k8s:app={n}"]))
            for n in ("web", "client", "other")
        ]
        engine = PolicyEngine(repo, reg)
        cache = IPCache()
        for i, ident in enumerate(idents):
            cache.upsert(f"fd00::{i + 1}/128", ident.id, source="k8s")
        pf = PreFilter()
        pf.insert(pf.revision, ["fd00::3/128", "2001:db8::/32"])
        pipe = DatapathPipeline(engine, cache, pf, conntrack=None)
        pipe.set_endpoints([idents[0].id])
        pipe.rebuild()
        assert pipe._v6_fused, "v6 fusion not built"
        t = pipe._tables[(TRAFFIC_INGRESS, 6)]

        rng = np.random.default_rng(6)
        b = 1024
        pool = []
        for tail in (1, 2, 3):
            a = bytearray(16); a[0] = 0xFD; a[15] = tail
            pool.append(bytes(a))
        bad = bytearray(16); bad[0] = 0x20; bad[1] = 0x01
        bad[2] = 0x0D; bad[3] = 0xB8; bad[15] = 9
        pool.append(bytes(bad))
        unk = bytearray(16); unk[0] = 0xFE; unk[15] = 7
        pool.append(bytes(unk))
        qs = [pool[int(i)] for i in rng.integers(0, len(pool), b)]
        peers = jnp.asarray(np.array([list(x) for x in qs], np.int32))
        eps = jnp.asarray(np.zeros(b, np.int32))
        dports = jnp.asarray(np.full(b, 80, np.int32))
        protos = jnp.asarray(np.full(b, 6, np.int32))
        kw = dict(ep_count=1, levels=16, prefilter=True)
        v_f, r_f, c_f = process_flows(
            t, peers, eps, dports, protos, fused=True, **kw
        )
        # genuinely UNFUSED pipeline (fusion disabled → the classic
        # deny trie gets built; the fused pipeline elides it)
        import cilium_tpu.datapath.pipeline as _pl

        orig = _pl.merge_trie_entries
        _pl.merge_trie_entries = lambda *_a, **_k: None
        try:
            pipe_u = DatapathPipeline(engine, cache, pf, conntrack=None)
            pipe_u.set_endpoints([idents[0].id])
            pipe_u.rebuild()
        finally:
            _pl.merge_trie_entries = orig
        assert not pipe_u._v6_fused
        t_u = pipe_u._tables[(TRAFFIC_INGRESS, 6)]
        v_b, r_b, c_b = process_flows(
            t_u, peers, eps, dports, protos, fused=False, **kw
        )
        np.testing.assert_array_equal(np.asarray(v_f), np.asarray(v_b))
        np.testing.assert_array_equal(np.asarray(r_f), np.asarray(r_b))
        np.testing.assert_array_equal(np.asarray(c_f), np.asarray(c_b))
        assert len(set(np.asarray(v_f).tolist())) >= 3
