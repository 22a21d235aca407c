#!/usr/bin/env python3
"""Smoke run of the served verdict path on one TPU, through the daemon.

Drives what a node agent serves, once, at the size of the headline
deployment in BASELINE.json (10,000 rules, 2,048 identities, 64 local
endpoints, a 50,000-prefix prefilter), with data made from ``--seed``:

1. ``Daemon`` boot, 64 ``endpoint_add`` calls, remote pods written into
   the ipcache the way the k8s/kvstore watchers do, the prefilter set
   loaded, and the policy imported as Cilium JSON through
   ``policy_add`` (the ``cilium policy import`` path).
2. IPv4 ingress batches at the top ladder rung, a ragged batch, a replay
   (conntrack hits), an egress batch and an IPv6 batch, all through
   ``daemon.pipeline.submit()`` at depth 2 with ``sports`` so the
   conntrack pre-pass runs.
3. One HTTP batch through the in-process proxy with ``L7DeviceBatch`` on.

Every batch is checked on a sample of flows against a reference that
shares no code with the device path: ``Repository.allows_ingress`` /
``allows_egress`` on the rules that select the endpoint, the generator's
own address→labels table, plain prefix-set membership for the
prefilter, and host ``re`` for the HTTP rules. A few flows also go
through ``Daemon.policy_resolve`` (the ``cilium policy trace`` path).

The run fails (exit 1) on any mismatch, on any batch resolved by the
host fallback or quarantined, or if the failsafe ladder leaves level 0.
It exits 2, printing no result, when JAX finds no TPU. The last line of
stdout is one JSON object naming the device.

``--four-chips`` runs only the sharded path: the same world on a 2x2
flows x ident mesh, compared bit for bit with a single-device pipeline
placed on device 0 in the same process.

Timings printed here are smoke timings of one cold run, not benchmark
numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import ipaddress
import json
import re
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Scale:
    rules: int = 10_000     # BASELINE headline: policy verdicts at 10k rules
    apps: int = 512         # bench.build_world's app vocabulary
    endpoints: int = 64
    identities: int = 2_048
    prefixes: int = 50_000  # BASELINE config 2: 50k-prefix prefilter
    batch: int = 8_192      # top rung of the dispatch bucket ladder
    ragged: int = 5_000
    v6_batch: int = 4_096
    check: int = 2_048      # flows per batch checked against the reference
    http: int = 256
    traces: int = 8         # flows also run through policy_resolve


FULL = Scale()

PORTS = (80, 443, 8080, 53, 5432, 22)
L7_PORT = 8080
FORWARD, DROP_POLICY, DROP_PREFILTER = 1, 2, 3
WORLD = ("reserved:world",)


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


# -- the world --------------------------------------------------------------

def _ip4(v: int) -> str:
    return f"{v >> 24 & 255}.{v >> 16 & 255}.{v >> 8 & 255}.{v & 255}"


def _v6_bytes(prefix: int, host: int) -> bytes:
    """fd00:<prefix>::<host> as 16 bytes."""
    return bytes([0xFD, 0x00, prefix >> 8 & 255, prefix & 255]
                 + [0] * 8 + list(int(host).to_bytes(4, "big")))


def _v6_addr(prefix: int, host: int) -> ipaddress.IPv6Address:
    return ipaddress.IPv6Address(_v6_bytes(prefix, host))


@dataclasses.dataclass
class World:
    rules_json: List[dict]
    ep_labels: List[Tuple[str, ...]]      # datapath endpoint index order
    ep_ip4: List[int]
    pod_labels: List[Tuple[str, ...]]     # one per remote identity
    pod_ip4: np.ndarray                   # [P] uint32, remote pod addresses
    pod_ident: np.ndarray                 # [P] index into pod_labels
    prefixes: Dict[int, np.ndarray]       # prefix length -> network ints
    allow_by_app: Dict[str, List[Tuple[str, Optional[Tuple[int, str]]]]]
    egress_by_app: Dict[str, List[Tuple[str, Optional[Tuple[int, str]]]]]
    l7_ep: int
    l7_peer_apps: Tuple[str, ...]
    http_rules: Tuple[Tuple[str, str], ...]
    pods_of_app: Dict[str, np.ndarray]    # app label -> pod indices


def build_world(scale: Scale, seed: int) -> World:
    """Policy, endpoints, remote pods and prefilter set, all from
    ``seed``. The rules are bench.build_world's shape (one subject app,
    one peer app, ~30% with an L4 port) written as Cilium policy JSON,
    plus one L7 HTTP rule and a few egress rules so those paths carry
    verdicts other than deny."""
    rng = np.random.default_rng(seed)
    app = lambda k: f"a{int(k)}"  # noqa: E731
    rules: List[dict] = []
    allow_by_app: Dict[str, list] = defaultdict(list)
    for _ in range(scale.rules):
        subj, peer = app(rng.integers(scale.apps)), app(rng.integers(scale.apps))
        ing: dict = {"fromEndpoints": [{"matchLabels": {"k8s:app": peer}}]}
        port = None
        if rng.random() < 0.3:
            p = int(rng.choice([80, 443, 8080, 53, 5432]))
            port = (p, "UDP" if p == 53 else "TCP")
            ing["toPorts"] = [{"ports": [{"port": str(p), "protocol": port[1]}]}]
        rules.append({"endpointSelector": {"matchLabels": {"k8s:app": subj}},
                      "ingress": [ing]})
        allow_by_app[subj].append((peer, port))

    ep_labels: List[Tuple[str, ...]] = []
    for i in range(scale.endpoints - 1):
        lbl = [f"k8s:app={app(rng.integers(scale.apps))}", f"k8s:zone=z{i % 8}",
               f"k8s:io.kubernetes.pod.namespace=local{i}"]
        ep_labels.append(tuple(lbl))
    # the L7 service: only the HTTP rule below selects it
    l7_ep = len(ep_labels)
    ep_labels.append(("k8s:app=l7svc", "k8s:zone=z0",
                      "k8s:io.kubernetes.pod.namespace=local-l7"))
    ep_ip4 = [(10 << 24) | (200 << 16) | (i + 2) for i in range(scale.endpoints)]

    l7_peer_apps = tuple(app(k) for k in rng.choice(scale.apps, 4, replace=False))
    http_rules = (
        ("GET", "/api/v[0-9]+/items/[a-z0-9]+"),
        ("POST", "/upload/.*"),
        ("GET|HEAD", "/healthz"),
    )
    rules.append({
        "endpointSelector": {"matchLabels": {"k8s:app": "l7svc"}},
        "ingress": [{
            "fromEndpoints": [{"matchLabels": {"k8s:app": a}} for a in l7_peer_apps],
            "toPorts": [{
                "ports": [{"port": str(L7_PORT), "protocol": "TCP"}],
                "rules": {"http": [{"method": m, "path": p} for m, p in http_rules]},
            }],
        }],
    })

    egress_by_app: Dict[str, list] = defaultdict(list)
    for i in range(min(16, scale.endpoints - 1)):
        subj = ep_labels[i][0].split("=", 1)[1]
        peer = app(rng.integers(scale.apps))
        eg: dict = {"toEndpoints": [{"matchLabels": {"k8s:app": peer}}]}
        port = None
        if i % 2:
            port = (443, "TCP")
            eg["toPorts"] = [{"ports": [{"port": "443", "protocol": "TCP"}]}]
        rules.append({"endpointSelector": {"matchLabels": {"k8s:app": subj}},
                      "egress": [eg]})
        egress_by_app[subj].append((peer, port))

    # remote identities: app/zone like bench.build_world, the namespace
    # makes each label set (and so each identity) distinct
    n_remote = scale.identities - scale.endpoints
    pod_labels = []
    for j in range(n_remote):
        lbl = [f"k8s:app={app(rng.integers(scale.apps))}", f"k8s:zone=z{j % 8}",
               f"k8s:io.kubernetes.pod.namespace=ns{j // 8}"]
        if rng.random() < 0.5:
            lbl.append(f"k8s:env={'prod' if rng.random() < 0.5 else 'dev'}")
        pod_labels.append(tuple(lbl))
    # one or two pods per identity, in 10.1.0.0/16.. (remote nodes' pod CIDRs)
    pod_ident = np.repeat(np.arange(n_remote), rng.integers(1, 3, n_remote))
    pod_ip4 = ((10 << 24) | (1 << 16)) + 3 * np.arange(len(pod_ident)) + 1

    # prefilter: a blocklist-like spread of /16../32 outside 10/8
    lens = rng.choice([16, 20, 22, 24, 24, 24, 28, 32, 32], scale.prefixes)
    bases = rng.integers(64 << 24, 224 << 24, scale.prefixes, dtype=np.int64)
    prefixes: Dict[int, np.ndarray] = {}
    for plen in np.unique(lens):
        mask = (0xFFFFFFFF << (32 - int(plen))) & 0xFFFFFFFF
        prefixes[int(plen)] = np.unique(bases[lens == plen] & mask)

    by_app: Dict[str, list] = defaultdict(list)
    for p, ident in enumerate(pod_ident):
        by_app[pod_labels[ident][0].split("=", 1)[1]].append(p)

    return World(rules, ep_labels, ep_ip4, pod_labels,
                 pod_ip4.astype(np.uint32), pod_ident, prefixes,
                 dict(allow_by_app), dict(egress_by_app), l7_ep,
                 l7_peer_apps, http_rules,
                 {k: np.asarray(v) for k, v in by_app.items()})


def prefix_strings(w: World) -> List[str]:
    return [f"{_ip4(int(n))}/{plen}" for plen, nets in w.prefixes.items()
            for n in nets]


def in_prefilter(w: World, addrs: np.ndarray) -> np.ndarray:
    a = addrs.astype(np.int64)
    hit = np.zeros(a.shape[0], bool)
    for plen, nets in w.prefixes.items():
        mask = (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF
        hit |= np.isin(a & mask, nets)
    return hit


# -- traffic ----------------------------------------------------------------

@dataclasses.dataclass
class Batch:
    name: str
    family: int
    ingress: bool
    peer: np.ndarray            # [B] uint32 (v4) or [B, 16] int32 (v6)
    ep_idx: np.ndarray
    dports: np.ndarray
    protos: np.ndarray
    sports: np.ndarray
    peer_labels: List[Tuple[str, ...]]  # what the generator put at that address


def make_batch(w: World, name: str, n: int, rng, *, ingress=True,
               family=4) -> Batch:
    """Flows of the mix a node sees: half aimed at what some rule allows
    (so FORWARD and redirect are exercised), the rest random pods, local
    endpoints, prefilter-listed sources (v4 ingress) and unknown world
    addresses."""
    ep_idx = rng.integers(0, len(w.ep_labels), n).astype(np.int32)
    dports = rng.choice(np.array(PORTS, np.int32), n)
    pod = rng.integers(0, len(w.pod_ip4), n)
    kind = rng.random(n)
    table = w.allow_by_app if ingress else w.egress_by_app
    for i in np.nonzero(kind < 0.5)[0]:
        if ingress and rng.random() < 0.15:
            ep_idx[i] = w.l7_ep
            dports[i] = L7_PORT
            app = w.l7_peer_apps[rng.integers(len(w.l7_peer_apps))]
        else:
            opts = table.get(w.ep_labels[ep_idx[i]][0].split("=", 1)[1])
            if not opts:
                continue
            app, port = opts[rng.integers(len(opts))]
            if port is not None:
                dports[i] = port[0]
        cands = w.pods_of_app.get(app)
        if cands is not None:
            pod[i] = cands[rng.integers(len(cands))]
    protos = np.where(dports == 53, 17, 6).astype(np.int32)
    sports = rng.integers(32768, 61000, n).astype(np.int32)

    peer4 = w.pod_ip4[pod].astype(np.uint32)
    labels: List[Tuple[str, ...]] = [w.pod_labels[w.pod_ident[p]] for p in pod]
    local = (kind >= 0.80) & (kind < 0.88)
    world = kind >= 0.88
    lidx = rng.integers(0, len(w.ep_labels), n)
    for i in np.nonzero(local)[0]:
        peer4[i] = w.ep_ip4[lidx[i]]
        labels[i] = w.ep_labels[lidx[i]]
    if family == 4:
        # prefilter-listed sources: a random host inside a listed prefix
        plens = np.array(sorted(w.prefixes))
        listed = world & (rng.random(n) < 0.5)
        for i in np.nonzero(listed)[0]:
            plen = int(plens[rng.integers(len(plens))])
            nets = w.prefixes[plen]
            host = int(rng.integers(0, 1 << (32 - plen))) if plen < 32 else 0
            peer4[i] = int(nets[rng.integers(len(nets))]) | host
            labels[i] = WORLD
        rest = world & ~listed
        peer4[rest] = rng.integers(64 << 24, 224 << 24, int(rest.sum()))
        for i in np.nonzero(rest)[0]:
            labels[i] = WORLD
        peer = peer4
    else:
        peer = np.zeros((n, 16), np.int32)
        for i in range(n):
            if world[i]:
                b = _v6_bytes(0xBEEF, int(rng.integers(1, 1 << 31)))
                labels[i] = WORLD
            elif local[i]:
                b = _v6_bytes(0x200, int(lidx[i]) + 2)
            else:
                b = _v6_bytes(0x1, int(pod[i]) + 1)
            peer[i] = np.frombuffer(b, np.uint8)
    return Batch(name, family, ingress, peer, ep_idx, dports, protos,
                 sports, labels)


# -- the reference ----------------------------------------------------------

class Reference:
    """Host answers that share no code with the device path."""

    def __init__(self, w: World) -> None:
        from cilium_tpu.labels import parse_label_array
        from cilium_tpu.policy.api.serialization import rules_from_json
        from cilium_tpu.policy.repository import Repository

        self._parse = parse_label_array
        self.w = w
        # only rules that select an endpoint can decide its verdicts, so
        # each endpoint is traced against its own small repository (the
        # full 10k-rule trace costs ~0.1 s per flow)
        rules = rules_from_json(json.dumps(w.rules_json))
        self._repos = []
        self._ep = []
        for lbl in w.ep_labels:
            ep = parse_label_array(list(lbl))
            repo = Repository()
            repo.add_list([r for r in rules if r.endpoint_selector.matches(ep)])
            self._repos.append(repo)
            self._ep.append(ep)
        self._cache: Dict[tuple, int] = {}
        self._http = [(re.compile(m), re.compile(p)) for m, p in w.http_rules]

    def decision(self, ep: int, peer: Tuple[str, ...], dport: int,
                 proto: int, ingress: bool) -> int:
        from cilium_tpu.policy.search import PortContext, SearchContext

        key = (ep, peer, dport, proto, ingress)
        got = self._cache.get(key)
        if got is None:
            port = (PortContext(dport, "UDP" if proto == 17 else "TCP"),)
            peer_l = self._parse(list(peer))
            if ingress:
                ctx = SearchContext(src=peer_l, dst=self._ep[ep], dports=port)
                ok = self._repos[ep].allows_ingress(ctx)
            else:
                ctx = SearchContext(src=self._ep[ep], dst=peer_l, dports=port)
                ok = self._repos[ep].allows_egress(ctx)
            got = FORWARD if str(ok) == "allowed" else DROP_POLICY
            self._cache[key] = got
        return got

    def check_batch(self, b: Batch, verdicts: np.ndarray, redirect: np.ndarray,
                    sample: np.ndarray) -> Dict[str, int]:
        pf = (in_prefilter(self.w, b.peer) if b.family == 4 and b.ingress
              else np.zeros(len(b.ep_idx), bool))
        bad = 0
        counts = defaultdict(int)
        for i in sample:
            want = DROP_PREFILTER if pf[i] else self.decision(
                int(b.ep_idx[i]), b.peer_labels[i], int(b.dports[i]),
                int(b.protos[i]), b.ingress)
            want_red = (want == FORWARD and b.ingress
                        and int(b.ep_idx[i]) == self.w.l7_ep
                        and int(b.dports[i]) == L7_PORT and int(b.protos[i]) == 6)
            counts[want] += 1
            if int(verdicts[i]) != want or bool(redirect[i]) != want_red:
                if bad < 5:
                    print(f"  mismatch {b.name}[{i}]: ep={b.ep_idx[i]} "
                          f"peer={b.peer_labels[i]} dport={b.dports[i]} "
                          f"proto={b.protos[i]} got=({verdicts[i]},"
                          f"{bool(redirect[i])}) want=({want},{want_red})",
                          file=sys.stderr)
                bad += 1
        return {"checked": len(sample), "mismatches": bad,
                "forward": counts[FORWARD], "drop_policy": counts[DROP_POLICY],
                "drop_prefilter": counts[DROP_PREFILTER]}

    def http_allows(self, peer: Tuple[str, ...], method: str, path: str) -> bool:
        apps = {l.split("=", 1)[1] for l in peer if l.startswith("k8s:app=")}
        if not apps & set(self.w.l7_peer_apps):
            return False
        return any(m.fullmatch(method) and p.fullmatch(path)
                   for m, p in self._http)


# -- the daemon -------------------------------------------------------------

class CompileLog:
    """Backend compile seconds per jitted function, from JAX's own
    monitoring events."""

    def __init__(self) -> None:
        import jax

        self.secs: Dict[str, float] = defaultdict(float)
        self.count = 0

        def on_event(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs[str(kw.get("fun_name", "?"))] += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def boot_daemon(w: World, *, four_chips: bool):
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.ipcache.ipcache import SOURCE_KVSTORE
    from cilium_tpu.labels import parse_label_array
    from cilium_tpu.option import DaemonConfig, set_config

    cfg = DaemonConfig(verdict_pipeline_depth=2)
    if four_chips:  # the 2x2 flows x ident mesh over devices 0-3
        cfg = dataclasses.replace(cfg, verdict_sharding=True, mesh_sharding_2d=True,
                                  mesh_ident_axis=2, mesh_devices="0,1,2,3")
    set_config(cfg)
    t0 = time.perf_counter()
    d = Daemon(ct_gc_interval=0)
    for i, lbl in enumerate(w.ep_labels):
        d.endpoint_add(i + 1, list(lbl), ipv4=_ip4(w.ep_ip4[i]),
                       ipv6=str(_v6_addr(0x200, i + 2)))
    # remote pods as the k8s/kvstore watchers write them: identity from
    # the allocator, then the pod /32 and /128 into the ipcache
    ids = [d.allocate_identity(parse_label_array(list(l))) for l in w.pod_labels]
    for p, ident in enumerate(w.pod_ident):
        nid = ids[ident].id
        d.ipcache.upsert(f"{_ip4(int(w.pod_ip4[p]))}/32", nid, source=SOURCE_KVSTORE)
        d.ipcache.upsert(f"{_v6_addr(0x1, p + 1)}/128", nid, source=SOURCE_KVSTORE)
    d.prefilter.insert(d.prefilter.revision, prefix_strings(w))
    out = d.policy_add(json.dumps(w.rules_json))
    _check(out["count"] == len(w.rules_json), f"policy import took {out}")
    n_ids = sum(1 for x in d.identity_list() if x["id"] >= 256)
    _check(n_ids == len(w.ep_labels) + len(w.pod_labels),
           f"{n_ids} identities allocated")
    _say(phase="world", rules=out["count"], endpoints=len(d.endpoint_list()),
         identities=n_ids, pods=len(w.pod_ip4),
         prefixes=len(d.prefilter.dump()[1]),
         seconds=round(time.perf_counter() - t0, 3))
    for ep_i in range(len(w.ep_labels)):
        _check(d.pipeline.endpoint_id_at(ep_i) == ep_i + 1,
               "endpoint index order differs from endpoint_add order")
    return d


def table_shapes(d) -> str:
    """Shapes of the device tables the verdict programs read, one JSON
    object (tests/test_tpu_compile.py compiles at these widths)."""
    import jax

    def leaves(prefix, tree, out):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if hasattr(leaf, "shape"):
                out[prefix + jax.tree_util.keystr(path)] = list(leaf.shape)

    out: Dict[str, list] = {}
    leaves("policy", d.engine.device_policy, out)
    for (direction, family), t in sorted(d.pipeline._tables.items()):
        leaves(f"dp{direction}v{family}", t, out)
    return json.dumps(out, separators=(",", ":"))


def submit(pipe, b: Batch):
    if b.family == 4:
        return pipe.submit(b.peer, b.ep_idx, b.dports, b.protos,
                           ingress=b.ingress, sports=b.sports)
    return pipe.submit_v6(b.peer, b.ep_idx, b.dports, b.protos,
                          ingress=b.ingress, sports=b.sports)


def assert_healthy(pipe, where: str) -> None:
    """The failsafe ladder is a daemon feature; on this path it must not
    have moved: level 0, no breaker fault, nothing quarantined."""
    st = pipe.failsafe_state()
    _check(st["level"] == 0 and st["breaker_faults"] == 0
           and st["quarantined_batches"] == 0,
           f"failsafe engaged after {where}: {st}")


def make_traffic(w: World, scale: Scale, seed: int) -> List[Batch]:
    rng = np.random.default_rng(seed + 1)
    first = make_batch(w, "v4-ingress-0", scale.batch, rng)
    return [first,
            make_batch(w, "v4-ingress-1", scale.batch, rng),
            make_batch(w, "v4-ragged", scale.ragged, rng),
            dataclasses.replace(first, name="v4-replay"),  # conntrack hits
            make_batch(w, "v4-egress", scale.batch, rng, ingress=False),
            make_batch(w, "v6-ingress", scale.v6_batch, rng, family=6)]


def run_one_chip(scale: Scale, seed: int) -> None:
    import jax

    from cilium_tpu import metrics
    from cilium_tpu.datapath.pipeline import DROP_DEGRADED
    from cilium_tpu.l7.http_policy import _DEVICE_BATCH_MIN

    compiles = CompileLog()
    w = build_world(scale, seed)
    d = boot_daemon(w, four_chips=False)
    try:
        ref = Reference(w)
        pipe = d.pipeline
        traffic = make_traffic(w, scale, seed)

        # first batch alone: its wall time includes the compiles
        t0 = time.perf_counter()
        v, red = submit(pipe, traffic[0]).result()
        first_s = time.perf_counter() - t0
        results = {traffic[0].name: (v, red)}
        assert_healthy(pipe, traffic[0].name)
        # the rest back to back at depth 2
        pend = [(b, submit(pipe, b)) for b in traffic[1:]]
        for b, p in pend:
            results[b.name] = p.result()
            assert_healthy(pipe, b.name)
        # warm: a top-rung batch whose programs are compiled
        warm = make_batch(w, "v4-warm", scale.batch,
                          np.random.default_rng(seed + 2))
        t0 = time.perf_counter()
        results[warm.name] = submit(pipe, warm).result()
        warm_s = time.perf_counter() - t0
        assert_healthy(pipe, warm.name)
        traffic.append(warm)

        rng = np.random.default_rng(seed + 3)
        mismatches = 0
        for b in traffic:
            v, red = results[b.name]
            _check(v.shape == (len(b.ep_idx),), f"{b.name}: verdict shape {v.shape}")
            _check(not (v == DROP_DEGRADED).any(), f"{b.name}: degraded verdicts")
            sample = rng.choice(len(b.ep_idx), min(scale.check, len(b.ep_idx)),
                                replace=False)
            got = ref.check_batch(b, v, red, sample)
            mismatches += got["mismatches"]
            _say(phase="batch", name=b.name, flows=len(b.ep_idx),
                 **got, redirects=int(red.sum()))
        _check(mismatches == 0, f"{mismatches} verdict mismatches")

        # `cilium policy trace` for a few flows: host trace vs device
        tb = traffic[1]
        for i in range(scale.traces):
            ep = int(tb.ep_idx[i])
            dport = f"{int(tb.dports[i])}/{'UDP' if tb.protos[i] == 17 else 'TCP'}"
            res = d.policy_resolve(list(tb.peer_labels[i]), list(w.ep_labels[ep]),
                                   [dport])
            want = ref.decision(ep, tb.peer_labels[i], int(tb.dports[i]),
                                int(tb.protos[i]), True)
            _check(res["parity"] and res["allowed"] == (want == FORWARD),
                   f"policy trace {i}: {res['verdict']} device={res['device_allowed']}"
                   f" want={want}")
        _say(phase="policy-trace", flows=scale.traces, parity="ok")

        # L7: one HTTP batch through the proxy redirect, on the device DFA
        d.config_patch({"L7DeviceBatch": True})
        redirect = d.proxy.lookup(w.l7_ep + 1, L7_PORT, ingress=True)
        _check(redirect is not None and redirect.http_policy is not None,
               "no HTTP redirect on the L7 endpoint")
        _check(scale.http >= _DEVICE_BATCH_MIN, "HTTP batch below the device floor")
        reqs, peers = _http_requests(w, d, scale.http, rng)
        before = metrics.l7_batches_total.get({"parser": "http"})
        t0 = time.perf_counter()
        allows = np.asarray(d.proxy.check_http(redirect, reqs), bool)
        l7_s = time.perf_counter() - t0
        _check(metrics.l7_batches_total.get({"parser": "http"}) > before,
               "HTTP batch did not take the L7 device path")
        want = np.array([ref.http_allows(p, r.method, r.path)
                         for p, r in zip(peers, reqs)])
        bad = int((allows != want).sum())
        _say(phase="l7-http", requests=len(reqs), allowed=int(allows.sum()),
             mismatches=bad, first_batch_s=round(l7_s, 4))
        _check(bad == 0, f"{bad} HTTP verdict mismatches")
        assert_healthy(pipe, "l7")

        _say(phase="tables", shapes=table_shapes(d))
        st = pipe.failsafe_state()
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        _say(phase="summary", device_kind=repr(dev.device_kind),
             device_count=len(jax.devices()), verdict_mismatches=mismatches,
             ladder_level=st["level"], quarantined=st["quarantined_batches"],
             peak_bytes_in_use=stats.get("peak_bytes_in_use", "not-reported"))
        _say(phase="smoke-timing", note="one cold run, not a benchmark",
             first_batch_s=round(first_s, 4), warm_batch_s=round(warm_s, 4),
             compiles=compiles.count)
        for name, s in sorted(compiles.secs.items(), key=lambda kv: -kv[1])[:16]:
            _say(phase="compile", program=name, seconds=round(s, 3))
    finally:
        d.config_patch({"L7DeviceBatch": False})
        d.shutdown()


def _http_requests(w: World, d, n: int, rng):
    from cilium_tpu.l7.http_policy import HTTPRequest
    from cilium_tpu.labels import parse_label_array

    methods = ["GET", "POST", "HEAD", "PUT", "DELETE"]
    paths = ["/api/v1/items/abc123", "/api/v22/items/x9", "/api/v1/items/",
             "/upload/blob/7", "/healthz", "/healthz/x", "/admin", "/api/v1/Items/a"]
    allowed = [int(w.pod_ident[p]) for a in w.l7_peer_apps
               for p in w.pods_of_app.get(a, ())]
    reqs, peers = [], []
    for _ in range(n):
        ident = (allowed[rng.integers(len(allowed))]
                 if allowed and rng.random() < 0.7
                 else int(rng.integers(len(w.pod_labels))))
        labels = w.pod_labels[ident]
        nid = d.registry.lookup_by_labels(parse_label_array(list(labels))).id
        reqs.append(HTTPRequest(method=methods[rng.integers(len(methods))],
                                path=paths[rng.integers(len(paths))],
                                host="svc.local", src_identity=nid))
        peers.append(labels)
    return reqs, peers


def run_four_chips(scale: Scale, seed: int) -> None:
    """The world on a 2x2 flows x ident mesh vs one device, bit for bit."""
    import jax
    from jax.sharding import PartitionSpec as P

    from cilium_tpu.datapath.conntrack import FlowConntrack
    from cilium_tpu.datapath.pipeline import DatapathPipeline
    from cilium_tpu.datapath.placement import PlacementConfig

    _check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, "
           f"JAX sees {len(jax.devices())}")
    w = build_world(scale, seed)
    d = boot_daemon(w, four_chips=True)
    try:
        mesh = d.pipeline
        single = DatapathPipeline(
            d.engine, d.ipcache, d.prefilter, conntrack=FlowConntrack(),
            pipeline_depth=2, placement=PlacementConfig(device_ids=(0,)))
        single.set_endpoints([(ep.id, ep.identity.id)
                              for ep in d.endpoint_manager.endpoints()])
        traffic = make_traffic(w, scale, seed)
        got_m = [(b, submit(mesh, b)) for b in traffic]
        got_s = [(b, submit(single, b)) for b in traffic]
        for (b, pm), (_, ps) in zip(got_m, got_s):
            vm, rm = pm.result()
            vs, rs = ps.result()
            same = np.array_equal(vm, vs) and np.array_equal(rm, rs)
            _say(phase="mesh-vs-single", name=b.name, flows=len(b.ep_idx),
                 identical=same, forward=int((vm == FORWARD).sum()))
            _check(same, f"{b.name}: 2x2 mesh verdicts differ from device 0")
            assert_healthy(mesh, b.name)
            assert_healthy(single, b.name)
        _check(np.array_equal(mesh.counters, single.counters),
               "per-endpoint counters differ")

        plan = mesh.placement_state()
        _, _, placed_sel = mesh._placed_sel
        flow_sharding = mesh._dp_state[3]
        sel_devs = sorted(x.id for x in placed_sel.sharding.device_set)
        flow_devs = sorted(x.id for x in flow_sharding.device_set)
        _say(phase="placement", axes=json.dumps(plan["axes"]).replace(" ", ""),
             sel_match_devices=sel_devs, sel_match_spec=str(placed_sel.sharding.spec),
             flow_devices=flow_devs,
             single_devices=single.placement_state()["devices"])
        _check(len(sel_devs) == 4 and placed_sel.sharding.spec == P("ident", None),
               f"sel_match placed on {sel_devs}")
        _check(len(flow_devs) == 4, f"flow arrays placed on {flow_devs}")
        _check(plan["ident_sharded"] and len(plan["devices"]) == 4,
               f"mesh plan {plan}")
    finally:
        d.shutdown()


def device_or_exit(require_tpu: bool) -> Dict:
    """The device JAX found; without a TPU, exit 2 and print no result."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {d0.platform}); refusing to "
              "run on another device", file=sys.stderr)
        sys.exit(2)
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def run(scale: Scale = FULL, seed: int = 0, *, four_chips: bool = False,
        require_tpu: bool = True) -> Dict:
    """Run the smoke; returns the result line. Raises SmokeFailure."""
    device = device_or_exit(require_tpu)
    from cilium_tpu import compile_cache, probes
    from cilium_tpu.option import get_config, set_config

    compile_cache.enable()
    probe = probes._probe_device()
    _check(probe.get("ok") and probe["platform"] == device["platform"],
           f"device probe disagrees with JAX: {probe}")
    _say(phase="device", platform=device["platform"],
         device_kind=repr(device["kind"]), device_count=device["count"])
    cfg = get_config()
    try:
        if four_chips:
            run_four_chips(scale, seed)
        else:
            run_one_chip(scale, seed)
    finally:
        set_config(cfg)
    return {"ok": True, "device": device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh vs single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        out = run(FULL, args.seed, four_chips=args.four_chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _say(phase="done", seconds=round(time.perf_counter() - t0, 3))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
