"""A deployment's world from its configuration file and ``--seed``, and
its import into a booted ``Daemon`` through the daemon's normal API.

Generalised from ``chip_smoke.build_world`` / ``boot_daemon`` (PR 21):
the same rule shape (one subject app, peer apps, ~30% with one L4
port), the same remote-pod addressing and prefilter spread, but every
size comes from ``benchmark/configs/<name>.json`` and the arrays are
kept vectorised, because the traffic generator and the reference read
them per flow.

App indices: ``0 .. services-1`` are the services; ``services`` is the
extra ``l7svc`` app when the configuration asks for it; ``-1`` is the
world (no labels a rule can select).

A configuration with ``clusters`` describes a node of a ClusterMesh:
every service exists in each of ``count`` clusters, so an app index is
an identity over (cluster, service), ``c * services + s``, and its
labels carry ``io.cilium.k8s.policy.cluster``. The node's own cluster
is ``local``; the others are imported as a clustermesh node learns
them, from one kvstore per remote cluster (``remote_stores``)."""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PORTS = (80, 443, 8080, 53, 5432, 22)
RULE_PORTS = (80, 443, 8080, 53, 5432)
WORLD_APP = -1


def load_json(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json`` (configs, traffic)."""
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def ip4(v: int) -> str:
    return f"{v >> 24 & 255}.{v >> 16 & 255}.{v >> 8 & 255}.{v & 255}"


def v6_bytes(prefix: int, host: np.ndarray) -> np.ndarray:
    """fd00:<prefix>::<host> for each host, as [N, 16] uint8."""
    host = np.asarray(host, np.uint64)
    out = np.zeros((host.shape[0], 16), np.uint8)
    out[:, 0], out[:, 1] = 0xFD, 0x00
    out[:, 2], out[:, 3] = prefix >> 8 & 255, prefix & 255
    for k in range(4):
        out[:, 12 + k] = (host >> np.uint64(8 * (3 - k))) & np.uint64(255)
    return out


def v6_str(prefix: int, host: int) -> str:
    """fd00:<prefix>::<host> as text (the same address as v6_bytes)."""
    return f"fd00:{prefix:x}::{host >> 16:x}:{host & 0xFFFF:x}"


POD_V6, EP_V6, WORLD_V6 = 0x1, 0x200, 0xBEEF
CLUSTER_LABEL = "io.cilium.k8s.policy.cluster"
MIN_USER_IDENTITY = 256   # a cluster's first identity number (Cilium's user range)


@dataclasses.dataclass
class Clusters:
    """The mesh a ``clusters`` configuration describes: cluster ``c`` is
    named ``cluster<c>`` and has the id ``c + 1`` (bits 16-23 of its
    identity numbers, 1-255)."""
    names: List[str]
    local: int
    services: int         # identities per cluster
    pinned_peer_share: float

    @classmethod
    def from_cfg(cls, cfg: dict) -> Optional["Clusters"]:
        c = cfg.get("clusters")
        if not c:
            return None
        count, local, n_svc = int(c["count"]), int(c["local"]), int(cfg["services"])
        if not (1 <= count <= 255 and 0 <= local < count):
            raise SystemExit(f"clusters {c}: count must be 1-255 and local in [0, count)")
        if MIN_USER_IDENTITY + n_svc > 1 << 16:
            raise SystemExit(f"{n_svc} services do not fit a cluster's 16-bit identity space")
        return cls([f"cluster{k}" for k in range(count)], local, n_svc,
                   float(c.get("pinned_peer_share", 0.0)))

    def number(self, app: int) -> int:
        """The identity number app's own cluster gives it."""
        return (app // self.services + 1) << 16 | (MIN_USER_IDENTITY + app % self.services)


@dataclasses.dataclass
class World:
    cfg: dict
    app_names: List[str]              # app index -> k8s:app value
    app_labels: List[Tuple[str, ...]]  # app index -> identity labels
    ep_app: np.ndarray                # [E] app index of each local endpoint
    ep_ip4: np.ndarray                # [E] uint32
    pod_app: np.ndarray               # [P] app index of each remote pod
    pod_ip4: np.ndarray               # [P] uint32
    pods_of_app: Dict[int, np.ndarray]
    # (subject app, peer app, port or -1, proto or -1) -> L7 rule set or -1
    ingress: Dict[Tuple[int, int, int, int], int]
    egress: Dict[Tuple[int, int, int, int], int]
    allow_in: Dict[int, List[Tuple[int, int, int]]]   # subj -> [(peer, port, proto)]
    allow_eg: Dict[int, List[Tuple[int, int, int]]]
    egress_eps: np.ndarray            # endpoints some egress rule selects
    http_sets: List[List[dict]]       # rule set -> [{method, path, gen}]
    prefixes: Dict[int, np.ndarray]   # prefix length -> network ints
    rules_json: List[dict]
    l7_port: int
    clusters: Optional[Clusters] = None

    @property
    def n_apps(self) -> int:
        return len(self.app_names)

    @property
    def n_idents(self) -> int:
        """Apps that pods run: every (cluster, service) identity."""
        return int(self.cfg["services"]) * (len(self.clusters.names) if self.clusters else 1)


def _app_labels(name: str, ns: str) -> Tuple[str, ...]:
    return (f"k8s:app={name}", f"k8s:io.kubernetes.pod.namespace={ns}")


def build_world(cfg: dict, seed: int) -> World:
    """Policy, endpoints, remote pods and prefilter set, all from
    ``seed``. Rule k selects service k (one policy per service); its
    peers are ``peers_per_rule`` random services; a service with an
    HTTP rule set allows them on ``l7_port`` with that set, the others
    take one L4 port with probability ``l4_port_share``.

    With ``clusters``, rule subjects are the local cluster's services,
    and each peer selector names its service across the mesh or, with
    probability ``clusters.pinned_peer_share``, in one cluster drawn
    uniformly; the tables hold every identity a selector matches."""
    rng = np.random.default_rng(seed)
    n_svc = int(cfg["services"])
    mesh = Clusters.from_cfg(cfg)
    n_cl, loc = (len(mesh.names), mesh.local) if mesh else (1, 0)
    names = [f"s{k}" for _ in range(n_cl) for k in range(n_svc)]
    labels = [_app_labels(f"s{k}", f"ns{k % 64}") for _ in range(n_cl) for k in range(n_svc)]
    if mesh:
        labels = [lab + (f"k8s:{CLUSTER_LABEL}={mesh.names[a // n_svc]}",)
                  for a, lab in enumerate(labels)]
    templates = cfg.get("http_rule_sets", [])
    segment = cfg.get("http_service_segment", "")
    http_sets: List[List[dict]] = []
    l7_port = int(cfg.get("l7_port", 8080))
    l7_app = None
    if cfg.get("l7_endpoint"):
        l7_app = len(names)
        names.append("l7svc")
        labels.append(_app_labels("l7svc", "local-l7") +
                      ((f"k8s:{CLUSTER_LABEL}={mesh.names[loc]}",) if mesh else ()))

    ingress: Dict[Tuple[int, int, int, int], int] = {}
    egress: Dict[Tuple[int, int, int, int], int] = {}
    rules: List[dict] = []

    def peer_sel(svcs) -> List[Tuple[int, int]]:
        """(service, cluster or -1 for the whole mesh) per peer."""
        if not mesh:
            return [(p, -1) for p in svcs]
        pinned = rng.random(len(svcs)) < mesh.pinned_peer_share
        cl = rng.integers(0, n_cl, len(svcs))
        return [(p, int(c) if pin else -1) for p, pin, c in zip(svcs, pinned, cl)]

    def matched(p, c):
        """The identities a peer selector matches."""
        return [p + k * n_svc for k in (range(n_cl) if c < 0 else (c,))]

    def add(table, subj, peers, port, proto, l7_set, direction):
        sel = "fromEndpoints" if direction == "ingress" else "toEndpoints"
        body: dict = {sel: [{"matchLabels": {"k8s:app": names[p]} if c < 0 else
                             {"k8s:app": names[p], f"k8s:{CLUSTER_LABEL}": mesh.names[c]}}
                            for p, c in peers]}
        if port >= 0:
            pr = {"ports": [{"port": str(port), "protocol": "UDP" if proto == 17 else "TCP"}]}
            if l7_set >= 0:
                pr["rules"] = {"http": [{"method": r["method"], "path": r["path"]}
                                        for r in http_sets[l7_set]]}
            body["toPorts"] = [pr]
        rules.append({"endpointSelector": {"matchLabels": {"k8s:app": names[subj]}},
                      direction: [body]})
        for p, c in peers:
            for a in matched(p, c):
                table[(subj, a, port, proto)] = l7_set

    for k in range(int(cfg["ingress_rules"])):
        subj = k % n_svc + loc * n_svc
        peers = peer_sel([int(x) for x in
                          rng.choice(n_svc, int(cfg["peers_per_rule"]), replace=False)])
        port, proto, l7_set = -1, -1, -1
        if templates:
            # the service's own API: one of the templates' shapes, under
            # a path segment of its own
            seg = segment.format(svc=subj % n_svc)
            shape = templates[int(rng.integers(len(templates)))]
            http_sets.append([dict(r, path=seg + r["path"], gen=seg + r["gen"])
                              for r in shape])
            port, proto, l7_set = l7_port, 6, len(http_sets) - 1
        elif rng.random() < float(cfg["l4_port_share"]):
            port = int(rng.choice(RULE_PORTS))
            proto = 17 if port == 53 else 6
        add(ingress, subj, peers, port, proto, l7_set, "ingress")

    n_ep = int(cfg["local_endpoints"])
    ep_app = rng.integers(0, n_svc, n_ep) + loc * n_svc
    if l7_app is not None:
        ep_app[-1] = l7_app
        peers = peer_sel([int(x) for x in
                          rng.choice(n_svc, int(cfg["l7_endpoint"]["peers"]), replace=False)])
        http_sets.append(list(cfg["l7_endpoint"]["http_rules"]))
        add(ingress, l7_app, peers, l7_port, 6, len(http_sets) - 1, "ingress")
    ep_ip4 = ((10 << 24) | (200 << 16)) + 2 + np.arange(n_ep, dtype=np.uint32)

    # egress rules select the services of the first local endpoints, so
    # every egress rule governs some endpoint here (Cilium enforces
    # egress only on endpoints an egress rule selects)
    for i in range(int(cfg.get("egress_rules", 0))):
        subj = int(ep_app[i % n_ep])
        peers = peer_sel([int(rng.integers(n_svc))])
        port = 443 if i % 2 else -1
        add(egress, subj, peers, port, 6 if port >= 0 else -1, -1, "egress")
    eg_subj = {k[0] for k in egress}
    egress_eps = np.array([i for i in range(n_ep) if int(ep_app[i]) in eg_subj], np.int32)

    n_pods, n_ident = int(cfg["remote_pods"]), n_svc * n_cl
    pod_app = np.concatenate([np.arange(n_ident),
                              rng.integers(0, n_ident, max(0, n_pods - n_ident))])[:n_pods]
    pod_ip4 = (((10 << 24) | (1 << 16)) + 3 * np.arange(n_pods) + 1).astype(np.uint32)
    order = np.argsort(pod_app, kind="stable")
    bounds = np.searchsorted(pod_app[order], np.arange(n_ident + 1))
    pods_of_app = {a: order[bounds[a]:bounds[a + 1]] for a in range(n_ident)}

    prefixes: Dict[int, np.ndarray] = {}
    n_pf = int(cfg.get("prefilter_prefixes", 0))
    if n_pf:
        lens = rng.choice([16, 20, 22, 24, 24, 24, 28, 32, 32], n_pf)
        bases = rng.integers(64 << 24, 224 << 24, n_pf, dtype=np.int64)
        for plen in np.unique(lens):
            mask = (0xFFFFFFFF << (32 - int(plen))) & 0xFFFFFFFF
            prefixes[int(plen)] = np.unique(bases[lens == plen] & mask)

    def by_subject(table):
        out: Dict[int, list] = {}
        for (s, p, port, proto) in table:
            out.setdefault(s, []).append((p, port, proto))
        return out

    return World(cfg, names, labels, ep_app.astype(np.int32), ep_ip4,
                 pod_app.astype(np.int32), pod_ip4, pods_of_app, ingress, egress,
                 by_subject(ingress), by_subject(egress), egress_eps, http_sets,
                 prefixes, rules, l7_port, mesh)


def prefix_strings(w: World) -> List[str]:
    return [f"{ip4(int(n))}/{plen}" for plen, nets in w.prefixes.items() for n in nets]


def v6_canon(prefix: int, host: int) -> str:
    """The address of ``v6_str`` in the canonical text agents write."""
    hi, lo = host >> 16, host & 0xFFFF
    return f"fd00:{prefix:x}::{hi:x}:{lo:x}" if hi else f"fd00:{prefix:x}::{lo:x}"


def remote_stores(w: World) -> Dict[str, object]:
    """Each remote cluster's kvstore as that cluster's agents leave it:
    every identity its pods run under, at ``IDENTITIES_PATH/id/<num>``
    (the labels' allocator key), and each pod's /32 and /128 at
    ``IP_IDENTITIES_PATH/<cluster>/<cidr>``. Written by the harness,
    before the node boots; the node learns them in ``boot_daemon``."""
    if w.clusters is None:
        return {}
    from cilium_tpu.kvstore import InMemoryStore
    from cilium_tpu.kvstore.paths import IDENTITIES_PATH, IP_IDENTITIES_PATH

    mesh, out = w.clusters, {}
    pod_cl = w.pod_app // mesh.services
    for c, name in enumerate(mesh.names):
        if c == mesh.local:
            continue
        store = out[name] = InMemoryStore()
        pods = np.flatnonzero(pod_cl == c)
        for a in np.unique(w.pod_app[pods]).tolist():
            store.put(f"{IDENTITIES_PATH}/id/{mesh.number(a)}",
                      ";".join(sorted(w.app_labels[a])).encode(), None)
        ip_path = f"{IP_IDENTITIES_PATH}/{name}/"
        for p in pods.tolist():
            num = mesh.number(int(w.pod_app[p]))
            for cidr in (f"{ip4(int(w.pod_ip4[p]))}/32", f"{v6_canon(POD_V6, p + 1)}/128"):
                store.put(ip_path + cidr,
                          json.dumps({"identity": num, "ip": cidr}, sort_keys=True).encode(), None)
    return out


# -- the daemon -------------------------------------------------------------

def daemon_config(cfg: dict, **settings):
    """The node's ``DaemonConfig``: the traffic kind's ``settings``,
    then the configuration's own ``daemon`` keys over them. A key that
    ``DaemonConfig`` lacks stops the run here, before anything boots."""
    from cilium_tpu.option import DaemonConfig

    own = cfg.get("daemon", {})
    unknown = sorted(set(own) - {f.name for f in dataclasses.fields(DaemonConfig)})
    if unknown:
        raise SystemExit(f"configuration {cfg.get('name')!r}: daemon keys {unknown} "
                         "name no DaemonConfig field")
    return dataclasses.replace(DaemonConfig(**settings), **own)


def boot_daemon(w: World, *, phase_tracing: bool, stores: Optional[Dict[str, object]] = None,
                on_step: Optional[Callable[[str, float], None]] = None, **settings):
    """Boot a Daemon and import the world through its API, in the order
    a node comes up: local endpoints, remote identities and pod
    addresses as the kvstore watcher writes them, the prefilter set,
    then the policy as Cilium JSON. Returns (daemon, {step: seconds}).

    In a ``clusters`` world the pods above are the local cluster's; the
    node then joins its cluster as a ``ClusterNode``, subscribes each
    remote cluster's kvstore (``stores``, from ``remote_stores``) and
    pumps until no event is left. ``on_step(name, seconds)`` is called
    as each step ends."""
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.ipcache.ipcache import SOURCE_KVSTORE
    from cilium_tpu.labels import parse_label_array
    from cilium_tpu.option import set_config

    set_config(daemon_config(w.cfg, phase_tracing=phase_tracing, **settings))
    steps: Dict[str, float] = {}
    t = time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        steps[name] = now - t
        t = now
        if on_step is not None:
            on_step(name, steps[name])

    mesh = w.clusters
    mine = (np.arange(len(w.pod_app)) if mesh is None
            else np.flatnonzero(w.pod_app // mesh.services == mesh.local)).tolist()
    d = Daemon()
    lap("boot")
    for i in range(len(w.ep_app)):
        d.endpoint_add(i + 1, list(w.app_labels[int(w.ep_app[i])]),
                       ipv4=ip4(int(w.ep_ip4[i])), ipv6=v6_str(EP_V6, i + 2))
    lap("endpoint_add")
    ids = {}
    for a in np.unique(w.pod_app[mine]):
        ids[int(a)] = d.allocate_identity(parse_label_array(list(w.app_labels[int(a)]))).id
    lap("identity_allocate")
    up = d.ipcache.upsert
    for p in mine:
        nid = ids[int(w.pod_app[p])]
        up(f"{ip4(int(w.pod_ip4[p]))}/32", nid, source=SOURCE_KVSTORE)
        up(f"{v6_str(POD_V6, p + 1)}/128", nid, source=SOURCE_KVSTORE)
    lap("ipcache_upsert")
    if mesh is not None:
        join_cluster(d, w, stores or {}, lap)
    if w.prefixes:
        d.prefilter.insert(d.prefilter.revision, prefix_strings(w))
    lap("prefilter_insert")
    out = d.policy_add(json.dumps(w.rules_json))
    lap("policy_add")
    if out["count"] != len(w.rules_json):
        raise RuntimeError(f"policy import took {out}")
    for i in range(len(w.ep_app)):
        if d.pipeline.endpoint_id_at(i) != i + 1:
            raise RuntimeError("endpoint index order differs from endpoint_add order")
    return d, steps


def join_cluster(d, w: World, stores: Dict[str, object], lap) -> None:
    """The node joins its own cluster's kvstore as a ``ClusterNode``
    (``cluster_join``), then adds each remote cluster and pumps until
    no event is left (``remote_import``): the path a clustermesh node
    learns other clusters' identities and pod addresses by."""
    from cilium_tpu.cluster import ClusterNode
    from cilium_tpu.kvstore import InMemoryBackend, InMemoryStore
    from cilium_tpu.nodes.registry import Node

    local = w.clusters.names[w.clusters.local]
    before = [ep.identity.id for ep in d.endpoint_manager.endpoints()]
    node = ClusterNode(d, InMemoryBackend(InMemoryStore(), "bench-node"),
                       Node(name="bench-node", cluster=local), cluster=local)
    if [ep.identity.id for ep in d.endpoint_manager.endpoints()] != before:
        raise RuntimeError("joining the cluster renumbered the local endpoints")
    lap("cluster_join")
    for name, store in stores.items():
        node.add_remote_cluster(name, InMemoryBackend(store, "bench-node"))
    while node.pump():
        pass
    lap("remote_import")
