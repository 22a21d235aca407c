"""A ClusterMesh node at scale: the linear remote import, indexed rule
resolution (the ``PolicySubjectIndex`` option and its OFF tripwire),
cluster-scoped identities, the engine's tables placed on a 2D plan,
the import and resolution spans and counters, and a rehearsal of the
committed ``clustermesh-100k`` configuration at a tiny size.

Every fast path here is held to the plain path it replaces: the
per-rule walk, the per-entry pump, per-entry ipcache writes and
``insert_global`` one call at a time."""

from __future__ import annotations

import dataclasses
import json
import os
import random
import subprocess
import sys

import jax
import numpy as np
import pytest

from cilium_tpu import metrics as M
from cilium_tpu.identity.model import MAX_USER_IDENTITY, MIN_USER_IDENTITY, user_identity_range
from cilium_tpu.identity.registry import IdentityRegistry
from cilium_tpu.ipcache.ipcache import SOURCE_AGENT, SOURCE_KVSTORE, IPCache
from cilium_tpu.kvstore import InMemoryBackend, InMemoryStore
from cilium_tpu.kvstore.clustermesh import RemoteCluster, _key_to_labels
from cilium_tpu.kvstore.paths import IDENTITIES_PATH, IP_IDENTITIES_PATH
from cilium_tpu.labels import Label, LabelArray, parse_label_array
from cilium_tpu.observe import tracer as tracer_mod
from cilium_tpu.option import DaemonConfig, get_config, set_config
from cilium_tpu.policy import repository as repo_mod
from cilium_tpu.policy import PortContext, Repository, SearchContext
from cilium_tpu.policy.api import (
    EgressRule,
    EndpointSelector,
    HTTPRule,
    IngressRule,
    L7Rules,
    MatchExpression,
    PortProtocol,
    PortRule,
    Rule,
)
from cilium_tpu.policy.api import selector as selector_mod
from cilium_tpu.xds.cache import NETWORK_POLICY_HOSTS_TYPE, ResourceCache
from cilium_tpu.xds.npds import wire_nphds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def boot_config():
    """Daemons read the process-wide DaemonConfig at boot."""
    old = get_config()
    yield lambda **kw: set_config(DaemonConfig(**kw))
    set_config(old)


# ---------------------------------------------------------------- selectors

KEYS = ("app", "tier", "env")
VALUES = {"app": [f"a{i}" for i in range(6)], "tier": ["t0", "t1", "t2"],
          "env": ["prod", "dev"]}
SOURCES = ("k8s", "unspec", "any")


def _label_set(rng: random.Random) -> LabelArray:
    out = []
    for k in KEYS:
        if rng.random() < 0.8:
            out.append(Label(rng.choice(("k8s", "k8s", "container")), k, rng.choice(VALUES[k])))
    if rng.random() < 0.3:
        out.append(Label("reserved", "host"))
    return LabelArray(out)


def _sel_key(rng, k):
    src = rng.choice(SOURCES)
    return k if src == "unspec" else f"{src}:{k}"


def _selector(rng: random.Random) -> EndpointSelector:
    """matchLabels, matchExpressions, both, or the empty selector."""
    shape = rng.choice(("labels", "labels", "expr", "both", "empty"))
    labels = {}
    if shape in ("labels", "both"):
        for k in rng.sample(KEYS, rng.randint(1, 2)):
            labels[_sel_key(rng, k)] = rng.choice(VALUES[k])
    exprs = []
    if shape in ("expr", "both"):
        for _ in range(rng.randint(1, 2)):
            k = rng.choice(KEYS)
            op = rng.choice(("In", "NotIn", "Exists", "DoesNotExist"))
            vals = tuple(rng.sample(VALUES[k], rng.randint(1, 2))) if op in ("In", "NotIn") else ()
            exprs.append(MatchExpression(_sel_key(rng, k), op, vals))
    return EndpointSelector.make(labels, exprs)


def _matches_loop(sel: EndpointSelector, labels: LabelArray) -> bool:
    """The selector semantics, parsing every label on every call: the
    evaluator ``EndpointSelector.matches`` had before it parsed once."""
    parse = selector_mod._parse_selector_label
    for key, value in sel.match_labels:
        if not labels.has(parse(key, value)):
            return False
    for expr in sel.match_expressions:
        probe = parse(expr.key)
        has_key = any(l.key == probe.key and (probe.source == "any" or probe.source == l.source)
                      for l in labels)
        if expr.operator == "Exists" and not has_key:
            return False
        if expr.operator == "DoesNotExist" and has_key:
            return False
        if expr.operator == "In" and not any(labels.has(parse(expr.key, v)) for v in expr.values):
            return False
        if expr.operator == "NotIn" and any(labels.has(parse(expr.key, v)) for v in expr.values):
            return False
    return True


@pytest.mark.parametrize("seed", range(4))
def test_selector_matches_equals_the_parsing_loop(seed):
    rng = random.Random(seed)
    sels = [_selector(rng) for _ in range(60)]
    sets = [_label_set(rng) for _ in range(40)]
    for sel in sels:
        for labels in sets:
            assert sel.matches(labels) == _matches_loop(sel, labels), (sel, labels)


def test_selector_parses_its_labels_once(monkeypatch):
    calls = []
    real = selector_mod._parse_selector_label
    monkeypatch.setattr(selector_mod, "_parse_selector_label",
                        lambda *a: calls.append(a) or real(*a))
    sel = EndpointSelector.make({"k8s:app": "a1", "tier": "t0"},
                                [MatchExpression("env", "In", ("prod", "dev"))])
    labels = parse_label_array(["k8s:app=a1", "k8s:tier=t0", "k8s:env=dev"])
    for _ in range(50):
        assert sel.matches(labels)
    assert len(calls) == 2 + 1 + 2          # two labels, the key probe, two values
    # equality and hashing still see the fields only
    twin = EndpointSelector.make({"k8s:app": "a1", "tier": "t0"},
                                 [MatchExpression("env", "In", ("prod", "dev"))])
    assert twin == sel and hash(twin) == hash(sel)
    assert sel.required_labels() == (("app", "a1"), ("tier", "t0"))


# ------------------------------------------------------ indexed resolution

def _port_rules(rng):
    out = []
    for _ in range(rng.randint(0, 2)):
        port = rng.choice((80, 443, 8080, 53))
        l7 = L7Rules()
        if port == 8080 and rng.random() < 0.6:
            l7 = L7Rules(http=(HTTPRule(method="GET", path=f"/p{rng.randint(0, 3)}"),))
        out.append(PortRule(ports=(PortProtocol(port, rng.choice(("TCP", "UDP", "ANY"))),),
                            rules=l7 if port == 8080 else L7Rules()))
    return tuple(out)


def _rule(rng: random.Random, group: int) -> Rule:
    ingress, egress = [], []
    for _ in range(rng.randint(0, 2)):
        ingress.append(IngressRule(
            from_endpoints=tuple(_selector(rng) for _ in range(rng.randint(0, 2))),
            from_requires=(_selector(rng),) if rng.random() < 0.1 else (),
            to_ports=_port_rules(rng)))
    for _ in range(rng.randint(0, 1)):
        egress.append(EgressRule(
            to_endpoints=tuple(_selector(rng) for _ in range(rng.randint(0, 2))),
            to_requires=(_selector(rng),) if rng.random() < 0.1 else (),
            to_ports=_port_rules(rng)))
    return Rule(endpoint_selector=_selector(rng), ingress=tuple(ingress), egress=tuple(egress),
                labels=parse_label_array([f"grp=g{group}", f"n={rng.randint(0, 1 << 30)}"]))


class _Retarget:
    """A pure translator: rules of one group get a new subject
    selector (and so move between index buckets), the rest stay."""

    def __init__(self, group: int, sel: EndpointSelector) -> None:
        self.group, self.sel = group, sel

    def translate(self, r: Rule) -> Rule:
        if r.labels.has(Label("unspec", "grp", f"g{self.group}")):
            return dataclasses.replace(r, endpoint_selector=self.sel)
        return r


def _canon(pol):
    return [[(k, repr(f)) for k, f in m.filters.items()] for m in (pol.ingress, pol.egress)]


def _ops(rng: random.Random):
    """A seeded stream of repository changes: adds, deletes,
    replaces and translations."""
    ops = [("add", [_rule(rng, rng.randint(0, 5)) for _ in range(40)])]
    for _ in range(10):
        kind = rng.choice(("add", "delete", "replace", "translate"))
        g = rng.randint(0, 5)
        if kind == "add":
            ops.append(("add", [_rule(rng, rng.randint(0, 5)) for _ in range(rng.randint(1, 8))]))
        elif kind == "delete":
            ops.append(("delete", parse_label_array([f"grp=g{g}"])))
        elif kind == "replace":
            ops.append(("replace", (parse_label_array([f"grp=g{g}"]),
                                    [_rule(rng, g) for _ in range(rng.randint(0, 5))])))
        else:
            ops.append(("translate", _Retarget(g, _selector(rng))))
    return ops


def _apply(repo: Repository, op) -> None:
    kind, arg = op
    if kind == "add":
        repo.add_list(arg)
    elif kind == "delete":
        repo.take_by_labels(arg)
    elif kind == "replace":
        repo.replace_by_labels(*arg)
    else:
        repo.translate_rules(arg)


@pytest.mark.parametrize("seed", range(6))
def test_indexed_resolution_equals_the_per_rule_walk(seed):
    rng = random.Random(seed)
    walk, indexed = Repository(), Repository()
    indexed.set_subject_index(True)
    endpoints = [_label_set(rng) for _ in range(25)]
    peers = [_label_set(rng) for _ in range(6)]
    for op in _ops(rng):
        _apply(walk, op)
        _apply(indexed, op)
        assert walk.rules == indexed.rules
        for ep in endpoints:
            assert _canon(indexed.resolve_l4_policy(ep)) == _canon(walk.resolve_l4_policy(ep))
            for peer in peers:
                for ingress in (True, False):
                    ctx = dict(src=peer, dst=ep) if ingress else dict(src=ep, dst=peer)
                    ports = (PortContext(80, "TCP"), PortContext(8080, "TCP"))
                    call = "allows_ingress" if ingress else "allows_egress"
                    assert (getattr(indexed, call)(SearchContext(dports=ports, **ctx))
                            == getattr(walk, call)(SearchContext(dports=ports, **ctx)))
    # the index kept in step selects what an index built afresh from
    # the rules selects, and what the walk selects, in the same order
    fresh = repo_mod._SubjectIndex(indexed.rules)
    for ep in endpoints:
        want = [r for r in walk.rules if r.endpoint_selector.matches(ep)]
        for index in (indexed._index, fresh):
            assert [r for r in index.candidates(ep) if r.endpoint_selector.matches(ep)] == want


def test_index_visits_only_candidates_and_counts_them():
    rules = [Rule(endpoint_selector=EndpointSelector.make({"k8s:app": f"s{k % 50}"}),
                  ingress=(IngressRule(to_ports=(PortRule(ports=(PortProtocol(80, "TCP"),)),)),),
                  labels=parse_label_array([f"r={k}"]))
             for k in range(500)]
    rules.append(Rule(endpoint_selector=EndpointSelector.make(
        None, [MatchExpression("k8s:app", "Exists")]), labels=parse_label_array(["r=any"])))
    ep = parse_label_array(["k8s:app=s7", "k8s:io.kubernetes.pod.namespace=ns7"])
    for on, visited in ((False, 501), (True, 10 + 1)):
        repo = Repository()
        repo.set_subject_index(on)
        repo.add_list(list(rules))
        before = {d: M.policy_rules_visited_total.get({"direction": d})
                  for d in ("ingress", "egress")}
        pol = repo.resolve_l4_policy(ep)
        assert [f.key() for f in pol.ingress] == ["80/TCP"]
        for d in ("ingress", "egress"):
            assert M.policy_rules_visited_total.get({"direction": d}) - before[d] == visited


def test_policy_subject_index_off_runs_the_walk(monkeypatch, boot_config):
    """Tripwire for PolicySubjectIndex: OFF (the default) builds no
    index and tests every rule's selector; ON resolves from the index;
    OFF again drops it."""
    from cilium_tpu.daemon import Daemon

    def boom(*a, **k):
        raise AssertionError("subject index used while PolicySubjectIndex is off")

    boot_config()
    monkeypatch.setattr(repo_mod._SubjectIndex, "__init__", boom)
    monkeypatch.setattr(repo_mod._SubjectIndex, "candidates", boom)
    d = Daemon(conntrack=False)
    try:
        assert d.options.get("PolicySubjectIndex") is False
        d.endpoint_add(1, ["k8s:app=a0"], ipv4="10.200.0.2")
        before = M.policy_rules_visited_total.get({"direction": "ingress"})
        rules = [{"endpointSelector": {"matchLabels": {"k8s:app": f"a{k}"}},
                  "ingress": [{"fromEndpoints": [{"matchLabels": {"k8s:app": "b"}}],
                               "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}]}]}]}
                 for k in range(30)]
        d.policy_add(json.dumps(rules))
        assert d.repo._index is None and d.repo.subject_index is False
        # the endpoint's regeneration tested every rule
        assert M.policy_rules_visited_total.get({"direction": "ingress"}) - before >= 30
        monkeypatch.undo()
        d.options.set("PolicySubjectIndex", True)
        d.policy_add(json.dumps(rules[:1]))
        assert d.repo._index is not None
        d.options.set("PolicySubjectIndex", False)
        assert d.repo._index is None
    finally:
        d.shutdown()


def test_policy_subject_index_boots_from_its_field(boot_config):
    from cilium_tpu.daemon import Daemon

    boot_config(policy_subject_index=True)
    d = Daemon(conntrack=False)
    try:
        assert d.options.get("PolicySubjectIndex") is True
        assert d.repo.subject_index is True
    finally:
        d.shutdown()


# ------------------------------------------------------------ xDS cache

def test_resource_cache_snapshot_holds_and_versions_bump_as_before():
    c = ResourceCache()
    t = NETWORK_POLICY_HOSTS_TYPE
    assert c.upsert(t, "a", {"v": 1}) == 1
    assert c.upsert(t, "a", {"v": 1}) == 1          # no-op: no bump
    assert c.upsert(t, "b", {"v": 2}) == 2
    version, snap = c.get(t)
    assert version == 2 and snap == {"a": {"v": 1}, "b": {"v": 2}}
    assert c.upsert(t, "a", {"v": 3}) == 3
    assert c.delete(t, "b") == 4
    assert c.delete(t, "missing") == 4              # no-op: no bump
    assert c.upsert(t, "c", {"v": 5}) == 5
    assert snap == {"a": {"v": 1}, "b": {"v": 2}}   # the earlier snapshot never moved
    assert c.get(t, ["a", "c", "x"]) == (5, {"a": {"v": 3}, "c": {"v": 5}})
    # one transaction, one bump; a batch that changes nothing, none
    assert c.apply(t, {"a": None, "d": {"v": 6}, "e": {"v": 7}}) == 6
    assert c.apply(t, {"a": None, "d": {"v": 6}}) == 6
    assert c.get(t)[1] == {"c": {"v": 5}, "d": {"v": 6}, "e": {"v": 7}}
    assert c.wait_newer(t, 5, timeout=0.1) == 6


# ------------------------------------------------------------ the ipcache

def _entries(rng, n):
    out = []
    for _ in range(n):
        r = rng.random()
        host = f"10.{rng.randint(0, 3)}.{rng.randint(0, 3)}.{rng.randint(1, 9)}"
        if r < 0.1:
            out.append(("not-a-cidr", 300, None))
        elif r < 0.3:
            out.append((host, None, None))
        elif r < 0.45:
            out.append((f"fd00::{rng.randint(1, 20):x}/128", rng.randint(256, 260), None))
        else:
            out.append((f"{host}/32", rng.randint(256, 260), f"192.168.0.{rng.randint(1, 3)}"))
    return out


@pytest.mark.parametrize("seed", range(3))
def test_update_many_equals_per_entry_writes(seed):
    rng = random.Random(seed)
    one, many = IPCache(), IPCache()
    for c in (one, many):
        c.upsert("10.0.0.1/32", 999, SOURCE_AGENT)   # owned by a higher source
    seen_one, seen_many, batches = [], [], []
    one.add_listener(lambda *ch: seen_one.append(ch), replay=False)
    many.add_listener(lambda *ch: seen_many.append(ch), replay=False)
    many.add_batch_listener(batches.append, replay=False)
    updates = _entries(rng, 300) + [("10.0.0.1/32", 5, None), ("10.0.0.1", None, None)]
    for cidr, ident, host in updates:
        try:
            if ident is None:
                one.delete(cidr, SOURCE_KVSTORE)
            else:
                one.upsert(cidr, ident, SOURCE_KVSTORE, host_ip=host)
        except ValueError:
            pass                                   # the per-entry write refuses it
    changed = many.update_many(updates, SOURCE_KVSTORE)
    assert sorted(one.items()) == sorted(many.items())
    assert one.version == many.version
    assert seen_one == seen_many                   # per-entry listeners: same calls, same order
    assert len(batches) == 1 and changed == len(batches[0]) == len(seen_many)
    for ident in range(256, 261):
        assert one.prefixes_for_identity(ident) == many.prefixes_for_identity(ident)


def test_insert_global_many_equals_insert_global_one_by_one():
    labels = [parse_label_array([f"k8s:app=s{k}"]) for k in range(6)]
    items = [(70000 + k, labels[k]) for k in range(6)]
    items += [(70001, labels[1]), (70009, labels[2]), (70002, labels[3])]  # ref, split brain, conflict
    one, many = IdentityRegistry(), IdentityRegistry()
    want = []
    for num, lab in items:
        try:
            one.insert_global(num, lab)
            want.append(True)
        except ValueError:
            want.append(False)
    assert many.insert_global_many(items) == want
    assert {i.id: i.labels for i in one} == {i.id: i.labels for i in many}
    assert [many.insert_global_many([it], skip_known=True)[0] for it in items[:2]] == [False, False]


def test_dense_view_equals_per_row_packing():
    rng = random.Random(5)
    reg = IdentityRegistry(cluster_id=2)
    idents = [reg.allocate(parse_label_array([f"k8s:app=s{k}", f"k8s:ns=n{k % 7}",
                                              f"x:t{rng.randint(0, 40)}"]))
              for k in range(700)]
    for ident in idents[::5]:
        reg.release(ident)
    bitmaps, ids, live = reg.dense_view()
    rows, words = reg.padded_rows(), reg.vocab.num_words
    want = np.zeros((rows, words), np.uint32)
    for r, num in enumerate(reg._id_of_row):
        assert ids[r] == num
        ident = reg.get(num)
        assert live[r] == (ident is not None)
        if ident is not None:
            want[r] = reg.vocab.pack(reg.vocab.identity_bits(ident.labels), words)
    assert bitmaps.shape == (rows, words) and (bitmaps == want).all()
    assert not live[len(reg._id_of_row):].any()


# ------------------------------------------------------------- the pump

def _remote_store(n_ids, n_pods, cluster_id=3, name="cluster2", junk=True):
    """A remote cluster's kvstore as its agents write it."""
    store = InMemoryStore()
    for s in range(n_ids):
        store.put(f"{IDENTITIES_PATH}/id/{cluster_id << 16 | 256 + s}",
                  f"k8s:app=s{s};k8s:io.cilium.k8s.policy.cluster={name}".encode(), None)
    for p in range(n_pods):
        ident = cluster_id << 16 | 256 + p % n_ids
        for cidr in (f"10.{cluster_id}.{p >> 8 & 255}.{p & 255}/32", f"fd00:{cluster_id}::{p:x}/128"):
            store.put(f"{IP_IDENTITIES_PATH}/{name}/{cidr}",
                      json.dumps({"identity": ident, "ip": cidr}).encode(), None)
    if junk:
        store.put(f"{IDENTITIES_PATH}/id/notanumber", b"k8s:app=x", None)
        store.put(f"{IDENTITIES_PATH}/id/{cluster_id << 16 | 9000}", b"\xff\xfe", None)
        store.put(f"{IP_IDENTITIES_PATH}/{name}/10.9.9.9/32", b"{not json", None)
        store.put(f"{IP_IDENTITIES_PATH}/{name}/bad-cidr", b'{"identity": 5}', None)
    return store


def _per_entry_pump(backend, registry, ipcache, name):
    """The pump as it was: one registry insert and one ipcache write
    per event (a CIDR that does not parse is skipped here)."""
    id_prefix = f"{IDENTITIES_PATH}/id/"
    ip_prefix = f"{IP_IDENTITIES_PATH}/{name}/"
    w_ids = backend.list_and_watch("ref-ids", id_prefix)
    w_ips = backend.list_and_watch("ref-ips", ip_prefix)

    def pump():
        for ev in w_ids.drain():
            if ev.typ == "list-done" or ev.key is None:
                continue
            try:
                num = int(ev.key[len(id_prefix):])
            except ValueError:
                continue
            if ev.typ == "delete":
                registry.release_by_id(num)
            elif registry.get(num) is None:
                try:
                    registry.insert_global(num, _key_to_labels((ev.value or b"").decode()))
                except ValueError:
                    pass
        for ev in w_ips.drain():
            if ev.typ == "list-done" or ev.key is None:
                continue
            cidr = ev.key[len(ip_prefix):]
            try:
                if ev.typ == "delete":
                    ipcache.delete(cidr, SOURCE_KVSTORE)
                else:
                    try:
                        payload = json.loads((ev.value or b"{}").decode())
                    except ValueError:
                        continue
                    ipcache.upsert(cidr, int(payload.get("identity", 0)), SOURCE_KVSTORE,
                                   host_ip=payload.get("host_ip"))
            except ValueError:
                continue
    return pump


def _nphds(cache):
    return cache.get(NETWORK_POLICY_HOSTS_TYPE)[1]


def test_batched_pump_equals_the_per_entry_pump():
    from cilium_tpu.kvstore.backend import EventTypeDelete, EventTypeListDone

    assert (EventTypeDelete, EventTypeListDone) == ("delete", "list-done")
    store = _remote_store(40, 150)
    sides = []
    for batched in (True, False):
        reg, ipc, xds = IdentityRegistry(), IPCache(), ResourceCache()
        wire_nphds(xds, ipc)
        # a local binding the remote cluster conflicts with: local wins
        reg.insert_global(3 << 16 | 256 + 5, parse_label_array(["k8s:app=local"]))
        backend = InMemoryBackend(store, "node")
        if batched:
            rc = RemoteCluster("cluster2", backend, reg, ipc)
            pump = rc.pump
        else:
            pump = _per_entry_pump(backend, reg, ipc, "cluster2")
            pump()
        sides.append((reg, ipc, xds, pump))
    # churn after the first list: deletes, re-adds, new pods
    store.delete(f"{IDENTITIES_PATH}/id/{3 << 16 | 256 + 7}")
    for p in range(0, 150, 9):
        store.delete(f"{IP_IDENTITIES_PATH}/cluster2/10.3.0.{p}/32")
    store.put(f"{IP_IDENTITIES_PATH}/cluster2/10.3.7.7/32",
              json.dumps({"identity": 3 << 16 | 300, "ip": "10.3.7.7/32"}).encode(), None)
    for *_, pump in sides:
        pump()
    (reg_a, ipc_a, xds_a, _), (reg_b, ipc_b, xds_b, _) = sides
    assert {i.id: i.labels for i in reg_a} == {i.id: i.labels for i in reg_b}
    assert sorted(ipc_a.items()) == sorted(ipc_b.items())
    assert _nphds(xds_a) == _nphds(xds_b)
    assert len(ipc_a) > 250


def test_import_is_linear_in_its_events(monkeypatch):
    """One batch listener call and one NPHDS transaction per pump, and
    one NPHDS row written per identity, at n and at 4n entries: the
    work per event does not grow with the table (it grew with it while
    every upsert copied the NPHDS dict)."""
    counts = {}

    def count(name, fn):
        def wrapped(*a, **k):
            counts[name] = counts.get(name, 0) + 1
            return fn(*a, **k)
        return wrapped

    per_event = []
    rows = []
    real_apply = ResourceCache.apply
    monkeypatch.setattr(ResourceCache, "apply",
                        count("apply", lambda self, t, up: rows.append(len(up))
                              or real_apply(self, t, up)))
    monkeypatch.setattr(ResourceCache, "get", count("get", ResourceCache.get))
    for n in (60, 240):
        counts.clear()
        rows.clear()
        reg, ipc, xds = IdentityRegistry(), IPCache(), ResourceCache()
        wire_nphds(xds, ipc)
        ipc.add_batch_listener(count("listener", lambda changes: None), replay=False)
        v0 = xds.version(NETWORK_POLICY_HOSTS_TYPE)
        RemoteCluster("cluster2", InMemoryBackend(_remote_store(n // 4, n, junk=False), "node"),
                      reg, ipc)
        events = 2 * n
        assert len(ipc) == events
        assert counts["listener"] == 1 and counts["apply"] == 1 and "get" not in counts
        assert xds.version(NETWORK_POLICY_HOSTS_TYPE) - v0 == 1
        assert sum(rows) == n // 4                  # one row per identity
        per_event.append((counts["listener"] + counts["apply"] + sum(rows)) / (events + n // 4))
    # the batch overhead shrinks per event, the rows stay one per identity
    assert per_event[1] <= per_event[0]


def test_pump_span_and_event_counter(monkeypatch):
    from cilium_tpu.observe.tracer import Tracer

    names = []

    class _Ann:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_mod, "annotation", lambda name, **m: _Ann(name))
    tr = Tracer()
    tr.enable()
    before = {k: M.clustermesh_events_total.get({"kind": k}) for k in ("identity", "ip", "node")}
    RemoteCluster("cluster2", InMemoryBackend(_remote_store(10, 30, junk=False), "node"),
                  IdentityRegistry(), IPCache(), tracer=tr)
    assert names == ["policyd.clustermesh.pump"]
    got = {k: M.clustermesh_events_total.get({"kind": k}) - v for k, v in before.items()}
    # every stored key, and one list-done per watcher
    assert got == {"identity": 10 + 1, "ip": 60 + 1, "node": 1}
    tr.disable()
    RemoteCluster("cluster2", InMemoryBackend(_remote_store(2, 2, junk=False), "node"),
                  IdentityRegistry(), IPCache(), tracer=tr)
    assert names == ["policyd.clustermesh.pump"]


def test_resolve_span(monkeypatch):
    from cilium_tpu.observe.tracer import Tracer

    names = []
    monkeypatch.setattr(tracer_mod, "annotation",
                        lambda name, **m: names.append(name) or _NullCtx())
    repo = Repository()
    repo.tracer = Tracer()
    repo.resolve_l4_policy(parse_label_array(["k8s:app=a"]))
    assert names == []
    repo.tracer.enable()
    repo.resolve_l4_policy(parse_label_array(["k8s:app=a"]))
    assert names == ["policyd.policy.resolve"]


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_gc_pause_nests_across_threads_and_restores():
    import gc
    import threading

    from cilium_tpu.utils import gcpause

    assert gc.isenabled()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with gcpause.paused():
            inside.set()
            release.wait(10)

    t = threading.Thread(target=worker)
    t.start()
    try:
        assert inside.wait(10)
        with gcpause.paused():
            with gcpause.paused():
                assert not gc.isenabled()
            assert not gc.isenabled()
        assert not gc.isenabled()          # the worker's section is still open
    finally:
        release.set()
        t.join(10)
    assert not t.is_alive() and gc.isenabled()
    gc.disable()
    try:
        with gcpause.paused():
            pass
        assert not gc.isenabled()          # off before: stays off
    finally:
        gc.enable()


def test_bulk_imports_run_with_the_collector_paused(monkeypatch, boot_config):
    import gc

    from cilium_tpu import daemon as daemon_mod
    from cilium_tpu.daemon import Daemon

    seen = []
    real_parse = daemon_mod.rules_from_json
    monkeypatch.setattr(daemon_mod, "rules_from_json",
                        lambda text: seen.append(gc.isenabled()) or real_parse(text))
    real_pump = RemoteCluster._pump
    monkeypatch.setattr(RemoteCluster, "_pump",
                        lambda self: seen.append(gc.isenabled()) or real_pump(self))
    boot_config()
    d = Daemon(conntrack=False)
    try:
        d.policy_add(json.dumps([{"endpointSelector": {"matchLabels": {"k8s:app": "a"}}}]))
        RemoteCluster("cluster2", InMemoryBackend(_remote_store(3, 3, junk=False), "node"),
                      d.registry, d.ipcache)
    finally:
        d.shutdown()
    assert seen == [False, False] and gc.isenabled()


@pytest.mark.parametrize("seed", range(3))
def test_parse_cidr_equals_ipaddress(seed):
    import ipaddress

    from cilium_tpu.ops.lpm import parse_cidr

    rng = random.Random(seed)
    cases = ["10.1.2.3/32", "10.1.2.3", "10.1.2.3/24", "0.0.0.0/0", "fd00:1::1:2/128",
             "fd00:1::/64", "::/0", "::1", "FD00::1/128", "fd00:0:0::1/128",
             "::ffff:1.2.3.4/128", "1.2.3.04/32", "1.2.3.4/+8", "1.2.3.4/33",
             "fe80::1%eth0/128", "10.0.0.0/255.0.0.0", "2001:db8:0:0:1:0:0:1/64", "x/8", ""]
    for _ in range(500):
        if rng.random() < 0.5:
            a = ipaddress.IPv4Address(rng.getrandbits(32))
            p = rng.randint(0, 32)
        else:
            a = ipaddress.IPv6Address(rng.getrandbits(128) & ~((1 << rng.randint(0, 120)) - 1))
            p = rng.randint(0, 128)
        cases += [f"{a}/{p}", f"{a.exploded}/{p}", str(a)]
    for c in cases:
        try:
            n = ipaddress.ip_network(c, strict=False)
            want = (n.version, n.network_address.packed, n.prefixlen)
        except ValueError:
            want = ValueError
        try:
            got = parse_cidr(c)
        except ValueError:
            got = ValueError
        assert got == want, c


def test_endpoint_row_gather_compiles_per_bucket(monkeypatch, boot_config):
    """The materializer gathers the endpoints' subject rows in
    power-of-two buckets: a node adding endpoints one at a time does
    not compile the gather for every endpoint count."""
    import jax.numpy as jnp

    from cilium_tpu.daemon import Daemon
    from cilium_tpu.ops import materialize as mz

    lengths = set()
    real_take = jnp.take

    def spy(a, idx, *args, **kw):
        lengths.add(int(np.shape(idx)[0]))
        return real_take(a, idx, *args, **kw)

    monkeypatch.setattr(mz.jnp, "take", spy)
    boot_config()
    d = Daemon(conntrack=False)
    try:
        d.policy_add(json.dumps([{"endpointSelector": {"matchLabels": {"k8s:app": "s1"}},
                                  "ingress": [{"fromEndpoints": [{"matchLabels": {"k8s:app": "s2"}}],
                                               "toPorts": [{"ports": [{"port": "80"}]}]}]}]))
        for i in range(19):
            d.endpoint_add(i + 1, [f"k8s:app=s{i % 4}"], ipv4=f"10.200.0.{i + 2}")
        snaps = d.pipeline.snapshots()
        assert len(snaps) == 19
    finally:
        d.shutdown()
    # the gather of 1..19 endpoints' rows saw only bucket sizes (the
    # sweep's own 1024-row blocks go through the same function)
    assert lengths and all(n & (n - 1) == 0 and n >= 8 for n in lengths), lengths


def test_endpoint_adds_compile_each_sweep_once(boot_config):
    """A node adding endpoints one at a time, each with labels of its
    own, compiles the matrix sweep once per direction and the selector
    match once per 256 labels: the sweep is padded to at least 128
    segments, leaves the identities' label bits out, and the label
    bitmaps grow eight words at a time."""
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.ops import materialize as mz
    from cilium_tpu.ops.bitmap import compute_selector_matches

    boot_config()
    d = Daemon(conntrack=False)
    try:
        d.policy_add(json.dumps([{"endpointSelector": {"matchLabels": {"k8s:app": "s1"}},
                                  "ingress": [{"fromEndpoints": [{"matchLabels": {"k8s:app": "s2"}}],
                                               "toPorts": [{"ports": [{"port": "80"}]}]}]}]))
        d.endpoint_add(1, ["k8s:app=s1"], ipv4="10.201.0.2")
        sweeps = mz._sweep_device_matrix._cache_size()
        matches = compute_selector_matches._cache_size()
        words = d.registry.vocab.num_words
        for i in range(2, 41):
            d.endpoint_add(i, [f"k8s:app=s{i % 4}", f"k8s:pod=p{i}", f"k8s:zone=z{i}"],
                           ipv4=f"10.201.0.{i + 1}")
        assert len(d.pipeline.snapshots()) == 40
        assert d.registry.vocab.num_words == words == 8     # 80 new labels: one width
        assert mz._sweep_device_matrix._cache_size() - sweeps <= 0
        assert compute_selector_matches._cache_size() - matches <= 0
    finally:
        d.shutdown()


def test_label_words_pad_to_eight():
    from cilium_tpu.labels.vocab import LabelVocab

    v = LabelVocab()
    assert v.num_words == 8
    i = 0
    while len(v) <= 256:
        assert v.num_words == 8
        v.identity_bits(parse_label_array([f"k8s:app=a{i}"]))
        i += 1
    assert 256 < len(v) <= 512 and v.num_words == 16
    bits = v.identity_bits(parse_label_array(["k8s:app=a3", f"k8s:app=a{i - 1}"]))
    packed = v.pack(bits)
    assert packed.shape == (16,)
    assert [w * 32 + b for w in range(16) for b in range(32) if packed[w] >> b & 1] == sorted(set(bits))


@pytest.mark.parametrize("seed", [0, 1])
def test_host_row_pack_equals_the_device_pack(seed):
    import jax.numpy as jnp

    from cilium_tpu.ops import materialize as mz
    from cilium_tpu.ops.bitmap import pack_bool_bits

    x = np.random.default_rng(seed).random((37, 192)) < 0.3
    assert np.array_equal(mz._pack_rows(x), np.asarray(pack_bool_bits(jnp.asarray(x))))


@pytest.mark.parametrize("chunk", [2, 4])
def test_matrix_sweep_chunking_does_not_change_the_policymap(monkeypatch, chunk):
    """The matrix sweep takes up to ``_MATRIX_SEG_CHUNK`` segments a
    dispatch; split into small chunks, every table comes out the same."""
    from test_policygen_fuzz import World

    from cilium_tpu.ops import materialize as mz

    w = World(7, n_rules=24, n_idents=24, family=4)
    compiled, device = w.engine.snapshot()
    eps = [i.id for i in w.ep_idents]
    for ingress in (True, False):
        one = mz.materialize_endpoints_state(compiled, device, eps, ingress=ingress)
        monkeypatch.setattr(mz, "_MATRIX_SEG_CHUNK", chunk)
        many = mz.materialize_endpoints_state(compiled, device, eps, ingress=ingress)
        monkeypatch.undo()
        assert sum(len(s) + 1 for s in one.ep_slots) > chunk   # more than one chunk
        assert np.array_equal(one.allow_nc, many.allow_nc)
        assert np.array_equal(one.red_nc, many.red_nc)
        assert np.array_equal(np.asarray(one.tables.id_bits), np.asarray(many.tables.id_bits))
        assert [dict(x.entries) for x in one.snapshots] == [dict(x.entries) for x in many.snapshots]


# -------------------------------------------------- cluster-scoped numbers

def test_user_identity_range_by_cluster():
    assert user_identity_range(0) == (MIN_USER_IDENTITY, MAX_USER_IDENTITY)
    assert user_identity_range(1) == (1 << 16 | 256, 1 << 16 | 65535)
    assert user_identity_range(255)[1] < 1 << 24     # below the node-local range


@pytest.mark.parametrize("cluster_id", [0, 1, 255])
def test_registry_allocates_in_its_cluster_range(cluster_id):
    reg = IdentityRegistry(cluster_id=cluster_id)
    lo, hi = user_identity_range(cluster_id)
    got = [reg.allocate(parse_label_array([f"k8s:app=s{k}"])).id for k in range(3)]
    assert got == [lo, lo + 1, lo + 2]
    assert reg.allocate(parse_label_array(["cidr:10.0.0.0/8"]), local=True).id == 1 << 24
    # a global number inside the range moves the cursor past it, one
    # of another cluster does not
    reg.insert_global(lo + 10, parse_label_array(["k8s:app=g"]))
    reg.insert_global((cluster_id + 1) % 256 << 16 | 300, parse_label_array(["k8s:app=r"]))
    assert reg.allocate(parse_label_array(["k8s:app=next"])).id == lo + 11
    reg._next_user = hi + 1
    with pytest.raises(RuntimeError):
        reg.allocate(parse_label_array(["k8s:app=full"]))


@pytest.mark.parametrize("cluster_id", [0, 1, 7])
def test_node_numbers_its_identities_under_its_cluster(boot_config, cluster_id):
    from cilium_tpu.cluster import ClusterNode
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.nodes.registry import Node

    boot_config(cluster_name="c", cluster_id=cluster_id)
    d = Daemon(conntrack=False)
    try:
        for i in range(4):
            d.endpoint_add(i + 1, [f"k8s:app=s{i % 3}"], ipv4=f"10.200.0.{i + 2}")
        ids = [ep.identity.id for ep in d.endpoint_manager.endpoints()]
        base = cluster_id << 16
        assert sorted(set(ids)) == [base | 256, base | 257, base | 258]
        assert d.registry.user_range == user_identity_range(cluster_id)
        node = ClusterNode(d, InMemoryBackend(InMemoryStore(), "n1"), Node(name="n1", cluster="c"),
                           cluster="c")
        # joining numbers through the cluster's CAS allocator, in the
        # same range: no endpoint is renumbered
        assert [ep.identity.id for ep in d.endpoint_manager.endpoints()] == ids
        lo, hi = user_identity_range(cluster_id)
        assert (node.identities.alloc.min_id, node.identities.alloc.max_id) == (lo, hi)
        fresh = d.allocate_identity(parse_label_array(["k8s:app=new"]))
        assert fresh.id == base | 259
        node.close()
    finally:
        d.shutdown()


def test_two_nodes_of_a_cluster_agree_on_scoped_numbers(boot_config):
    from cilium_tpu.cluster import ClusterNode
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.nodes.registry import Node

    boot_config(cluster_name="c", cluster_id=4)
    store = InMemoryStore()
    daemons, nodes = [Daemon(conntrack=False), Daemon(conntrack=False)], []
    try:
        for k, d in enumerate(daemons):
            nodes.append(ClusterNode(d, InMemoryBackend(store, f"n{k}"),
                                     Node(name=f"n{k}", cluster="c"), cluster="c"))
        a = daemons[0].allocate_identity(parse_label_array(["k8s:app=web"]))
        for n in nodes:
            n.pump()
        b = daemons[1].allocate_identity(parse_label_array(["k8s:app=web"]))
        assert a.id == b.id == 4 << 16 | 256
    finally:
        for n in nodes:
            n.close()
        for d in daemons:
            d.shutdown()


# ------------------------------------------------ placement on a 2D plan

def _bytes_by_device():
    import gc

    gc.collect()
    out = {}
    for a in jax.live_arrays():
        for sh in a.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


@pytest.mark.parametrize("placed", [False, True])
def test_rule_tables_upload_contiguous_and_transpose_on_device(monkeypatch, placed):
    """Every rule table leaves the host as a C-contiguous array (no host
    transpose, and none per device), and the device holds the same
    transposed relations as before."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from test_policygen_fuzz import World

    from cilium_tpu.ops import verdict as vd

    w = World(3, n_rules=24, n_idents=24, family=4)
    compiled, _ = w.engine.snapshot()
    sent = []
    real_put, real_asarray = vd.jax.device_put, vd.jnp.asarray

    def spy(fn):
        def put(a, *args, **kw):
            if isinstance(a, np.ndarray):
                sent.append(a.flags.c_contiguous)
            return fn(a, *args, **kw)
        return put

    monkeypatch.setattr(vd.jax, "device_put", spy(real_put))
    monkeypatch.setattr(vd.jnp, "asarray", spy(real_asarray))
    sharding = None
    if placed:
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("flows", "ident"))
        sharding = NamedSharding(mesh, PartitionSpec())
    t = vd.DeviceTables.from_host(compiled.ingress, sharding)
    monkeypatch.undo()
    assert len(sent) == 14 and all(sent)
    p = compiled.ingress
    for got, want in ((t.deny_t, p.deny_mat.T), (t.allow_t, p.allow_mat.T),
                      (t.en_t, p.en_mat.T), (t.ee_t, p.ee_mat.T)):
        assert np.array_equal(np.asarray(got), want)
        if placed:
            assert got.sharding == sharding


def test_engine_tables_follow_the_2d_plan(boot_config):
    """On a 2D plan the rule tables live replicated on every device of
    the plan and sel_match is split over ident: no full table stays on
    device 0 alone. Leaving the 2D plan puts them back on the default
    device."""
    from jax.sharding import NamedSharding

    from cilium_tpu.daemon import Daemon

    boot_config(verdict_sharding=True, mesh_sharding_2d=True, mesh_ident_axis=2,
                mesh_devices="0,1,2,3")
    d = Daemon(conntrack=False)
    try:
        for i in range(6):
            d.endpoint_add(i + 1, [f"k8s:app=s{i}"], ipv4=f"10.200.0.{i + 2}")
        rules = [{"endpointSelector": {"matchLabels": {"k8s:app": f"s{k % 6}"}},
                  "ingress": [{"fromEndpoints": [{"matchLabels": {"k8s:app": f"s{(k + 1) % 6}"}}],
                               "toPorts": [{"ports": [{"port": str(80 + k), "protocol": "TCP"}]}]}]}
                 for k in range(40)]
        d.policy_add(json.dumps(rules))
        dev = d.engine._device
        plan = d.pipeline._plan
        assert plan.is_2d
        assert dev.sel_match.sharding == plan.ident_sharding
        for leaf in [dev.id_bits, *jax.tree_util.tree_leaves(dev.ingress),
                     *jax.tree_util.tree_leaves(dev.egress)]:
            assert isinstance(leaf.sharding, NamedSharding)
            assert leaf.sharding == plan.table_sharding
        held = _bytes_by_device()
        plan_held = [held.get(i, 0) for i in plan.device_ids]
        assert max(plan_held) <= 1.25 * min(plan_held)
        d.options.set("MeshSharding2D", False)
        d.pipeline.rebuild()
        assert not d.pipeline._plan.is_2d
        assert not d.engine._device.sel_match.committed
    finally:
        d.shutdown()


# ------------------------------------------- the committed configuration

def test_clustermesh_100k_rehearses_on_a_2x2_plan():
    """The committed ``clustermesh-100k`` configuration and ``resolve``
    mix, cut to a tiny size, through the benchmark's own run on eight
    virtual CPU devices: every verdict matches the reference, the plan
    is 2x2 over devices 0-3, and the node's own identities carry its
    cluster id."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from benchmark import run, world as W\n"
        "booted = []\n"
        "real = W.boot_daemon\n"
        "def boot(*a, **k):\n"
        "    d, steps = real(*a, **k)\n"
        "    booted.append(d)\n"
        "    return d, steps\n"
        "W.boot_daemon = boot\n"
        "ov = {'config': {'local_endpoints': 12, 'remote_pods': 1200, 'services': 60,\n"
        "                 'ingress_rules': 600, 'prefilter_prefixes': 500, 'egress_rules': 4},\n"
        "      'traffic': {'rate': 20000}}\n"
        "res = run.run_cell('clustermesh-100k.resolve', 2**31 + 2027, 1.5, False,\n"
        "                   rehearsal=True, overrides=ov)\n"
        "print(json.dumps(res))\n"
        "d = booted[0]\n"
        "print(json.dumps({'eps': [ep.identity.id for ep in d.endpoint_manager.endpoints()],\n"
        "                  'ids': sorted(i.id for i in d.registry)}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    res, ids = lines[-2:]
    assert res["correct"] is True and all(v == 0 for v, _ in res["checks"].values())
    assert res["device"]["count"] == 4 and res["attempted"] > 0
    place = [ln for ln in out.stdout.splitlines() if ln.startswith("phase=placement")]
    assert len(place) == 1
    assert 'axes={"flows":2,"ident":2}' in place[0] and "devices=[0,1,2,3]" in place[0]
    assert "ident_sharded=True" in place[0]
    assert all(i >> 16 == 1 for i in ids["eps"])
    assert {i >> 16 for i in ids["ids"] if 256 <= i & 0xFFFF and i < 1 << 24} == set(range(1, 11))
